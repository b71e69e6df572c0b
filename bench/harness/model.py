"""A configuration file, the weights and adapters made from the seed,
and the engine built over them.

The benchmark makes every weight itself, on the device, in the type it
is served in, and hands it to the program; the reference
(``bench/references``) reads the same arrays. Parameter names are the
program's flat layout (``repro.models.base.param_shapes``): that is the
program's input format, not something the program made.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

#: Published config keys -> ``repro.models.base.ModelConfig`` fields.
CONFIG_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
}

#: The LoRA-adapted projections and their (d_in, d_out).
TARGETS = ("q", "k", "v", "o")


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the benchmark itself needs (weights, reference, FLOPs)."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    qk_norm: bool
    tied: bool

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def proj(self, t: str) -> tuple[int, int]:
        return {"q": (self.d_model, self.q_dim),
                "k": (self.d_model, self.kv_dim),
                "v": (self.d_model, self.kv_dim),
                "o": (self.q_dim, self.d_model)}[t]


class ConfigFile:
    """``bench/configs/<config>.json``: the configuration as it is run,
    its source, what was cut and why, and the engine's sizes."""

    def __init__(self, path: Path):
        self.path = Path(path)
        self.data = json.loads(self.path.read_text())
        c = self.data["config"]
        self.dims = Dims(
            n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim",
                           c["hidden_size"] // c["num_attention_heads"]),
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            rope_theta=float(c["rope_theta"]),
            norm_eps=float(c["rms_norm_eps"]),
            qk_norm=bool(self.data["reference_options"]["qk_norm"]),
            tied=bool(c.get("tie_word_embeddings", False)))
        self.serving = self.data["serving"]
        self.reference = self.data["reference"]

    def model_config(self):
        """The program's ModelConfig: its registry entry with every
        number this file states."""
        from repro.configs import get_config
        c = self.data["config"]
        kw = {f: c[k] for k, f in CONFIG_KEYS.items() if k in c}
        kw["head_dim"] = self.dims.head_dim
        kw["qk_norm"] = self.dims.qk_norm
        return dataclasses.replace(get_config(self.data["registry"]), **kw)


def weight_shapes(d: Dims) -> dict[str, tuple]:
    L, D, F = d.n_layers, d.d_model, d.d_ff
    s = {"embed/tok": (d.vocab, D), "final_norm": (D,),
         "layers/attn_norm": (L, D), "layers/q": (L, D, d.q_dim),
         "layers/k": (L, D, d.kv_dim), "layers/v": (L, D, d.kv_dim),
         "layers/o": (L, d.q_dim, D), "layers/mlp_norm": (L, D),
         "layers/gate": (L, D, F), "layers/up": (L, D, F),
         "layers/down": (L, F, D)}
    if not d.tied:
        s["lm_head"] = (D, d.vocab)
    if d.qk_norm:
        s["layers/q_norm"] = (L, d.head_dim)
        s["layers/k_norm"] = (L, d.head_dim)
    return s


def make_weights(d: Dims, key, dtype: str = "float32") -> dict:
    """Every weight, from ``key``, in one jitted program on the device.
    Matrices are N(0, 1/fan_in); the embedding N(0, 1); norm gains
    1 + N(0, 0.1^2), so a norm that drops its gain shows."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(d)
    names = sorted(shapes)

    def init(key):
        out = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            shp = shapes[name]
            z = jax.random.normal(k, shp, jnp.float32)
            if "norm" in name:
                x = 1.0 + 0.1 * z
            elif name == "embed/tok":
                x = z
            else:
                x = z * shp[-2] ** -0.5
            out[name] = x.astype(dtype)
        return out

    return jax.jit(init)(key)


def make_adapters(d: Dims, ranks: list[int], r_max: int, key
                  ) -> dict[int, dict]:
    """Every adapter, as host arrays in the type the slot arena serves
    (bfloat16), zero-padded past its rank to ``r_max``:
    {id: {target: (A (L, d_in, r_max), B (L, r_max, d_out))}}.
    A is N(0, 1/d_in); B is N(0, 1/(16 rank)), so an adapter moves a
    projection by about a quarter of its size. One jitted program per
    target makes one adapter's part on the device."""
    import jax
    import jax.numpy as jnp

    def one(key, rank, din, dout):
        ka, kb = jax.random.split(key)
        cols = jnp.arange(r_max) < rank
        a = jax.random.normal(ka, (d.n_layers, din, r_max)) * din ** -0.5
        b = (jax.random.normal(kb, (d.n_layers, r_max, dout))
             * 0.25 * jax.lax.rsqrt(rank.astype(jnp.float32)))
        return (jnp.where(cols[None, None, :], a, 0).astype(jnp.bfloat16),
                jnp.where(cols[None, :, None], b, 0).astype(jnp.bfloat16))

    fns = {t: jax.jit(one, static_argnums=(2, 3)) for t in TARGETS}
    keys = jax.random.split(key, len(ranks))
    out = {}
    for aid, rank in enumerate(ranks):
        tk = jax.random.split(keys[aid], len(TARGETS))
        out[aid] = {t: tuple(np.asarray(x) for x in fns[t](
            tk[i], jnp.int32(rank), *d.proj(t)))
            for i, t in enumerate(TARGETS)}
    return out


def catalog(cfg, ranks: list[int], r_max: int, weights: dict):
    """The program's host adapter store, holding the benchmark's
    adapters. ``AdapterCatalog`` draws its own weights; it is built
    empty and filled here, with each adapter's size priced as the
    catalog prices it (padded bytes, in bfloat16 KV-token units)."""
    from repro.core import AdapterInfo
    from repro.serving.engine import AdapterCatalog

    cat = AdapterCatalog(cfg, 0, r_max)
    cat.ranks = list(ranks)
    cat.weights = weights
    kv_tok = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * 2
    for aid, w in weights.items():
        nbytes = sum(a.nbytes + b.nbytes for a, b in w.values())
        cat.infos[aid] = AdapterInfo(
            adapter_id=aid, rank=ranks[aid], size_bytes=nbytes,
            size_tokens=max(1, nbytes // kv_tok))
    return cat


def build_engine(cf: ConfigFile, cfg, params, cat, seed: int):
    """The engine as it serves by default (paged, fused, prefix cache
    in exact mode, kernel LoRA backend, async loads, both prefetchers,
    real host-to-device copies), with the sizes the config file sets and
    any engine setting its ``serving.engine`` names, each with a reason
    in the file."""
    from repro.serving.engine import EngineConfig
    from repro.serving.systems import build_engine as build

    s = cf.serving
    ecfg = EngineConfig(max_slots=s["max_slots"], max_len=s["max_len"],
                        n_lora_slots=s["n_lora_slots"], r_max=s["r_max"],
                        n_adapters=len(cat.weights),
                        page_size=s["page_size"], seed=seed,
                        **s.get("engine", {}))
    return build("chameleon", cfg, params, ecfg, catalog=cat)
