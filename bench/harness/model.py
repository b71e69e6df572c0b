"""A configuration file, the weights and adapters made from the seed,
and the engine built over them.

The benchmark makes every weight itself, on the device, in the type it
is served in, and hands it to the program; the reference
(``bench/references``) reads the same arrays. Parameter names are the
program's flat layout (``repro.models.base.param_shapes``): that is the
program's input format, not something the program made.

A config file holds ``registry``, ``reference``, the published ``config``
as run and ``serving`` (``engine`` settings need an ``engine_why``), and
may hold ``reference_options`` and ``program``: fields no other key
states, each with a reason in ``program_why``. A family's shapes and
counts live in ``bench/references/<reference>.py``, which imports nothing
of the program and provides ``logits_at(w, adapter, tokens, pos, *, dims,
dtype)``, ``weight_shapes(dims) -> {flat name: shape}`` and
``matmul_params(dims, head) -> int``, the weights a token multiplies by.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
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
    "num_experts": "n_experts", "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "d_ff_expert", "decoder_sparse_step": "moe_every",
}

#: The LoRA-adapted projections and their (d_in, d_out).
TARGETS = ("q", "k", "v", "o")

#: Where ``reference`` finds a family's module; a test may repoint it.
REFERENCES = Path(__file__).resolve().parents[1] / "references"


def _freeze(v):
    """A JSON value made hashable: objects as sorted pairs, lists as tuples."""
    v = sorted(v.items()) if isinstance(v, dict) else v
    return tuple(map(_freeze, v)) if isinstance(v, (list, tuple)) else v


@dataclasses.dataclass(frozen=True, init=False)
class Dims:
    """The sizes every GQA family shares, the reference's name and, read
    as attributes from ``extra``, every other key of ``config`` (under its
    ``CONFIG_KEYS`` name, if any) and of ``reference_options``."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab: int
    rope_theta: float
    norm_eps: float
    tied: bool
    reference: str
    extra: tuple

    def __init__(self, *, reference: str = "dense_gqa", **kw):
        for f in dataclasses.fields(self)[:-2]:  # not reference, extra
            object.__setattr__(self, f.name, kw.pop(f.name))
        object.__setattr__(self, "reference", reference)
        object.__setattr__(self, "extra", _freeze(kw))

    def __getattr__(self, name):
        for k, v in self.__dict__.get("extra", ()):
            if k == name:
                return v
        raise AttributeError(name)

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


@functools.cache
def load_module(path: Path, name: str):
    """The Python file ``path`` as a module, loaded once per process."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(d: Dims):
    """The reference module ``d`` names, from ``REFERENCES``."""
    return load_module(REFERENCES / f"{d.reference}.py",
                       f"bench_ref_{d.reference}")


class ConfigFile:
    """``bench/configs/<config>.json``: the configuration as it is run,
    its source, what was cut and why, and the engine's sizes."""

    def __init__(self, path: Path):
        self.path = Path(path)
        self.data = json.loads(self.path.read_text())
        opts = self.data.get("reference_options", {})
        prog, why = self.data.get("program", {}), self.data.get("program_why")
        stated = {*CONFIG_KEYS, *CONFIG_KEYS.values(), *self.data["config"],
                  *(f.name for f in dataclasses.fields(Dims))}
        for what, bad in (("reference_options restates", set(opts) & stated),
                          ("program restates", set(prog) & (stated | {*opts})),
                          ("no program_why for", set(prog) - set(why or ()))):
            if bad:
                raise ValueError(f"{self.path}: {what} {sorted(bad)}")
        c = dict(self.data["config"])
        D, H = c.pop("hidden_size"), c.pop("num_attention_heads")
        self.dims = Dims(
            n_layers=c.pop("num_hidden_layers"), d_model=D, n_heads=H,
            n_kv_heads=c.pop("num_key_value_heads"),
            head_dim=c.pop("head_dim", D // H), vocab=c.pop("vocab_size"),
            rope_theta=float(c.pop("rope_theta")),
            norm_eps=float(c.pop("rms_norm_eps")),
            tied=bool(c.pop("tie_word_embeddings", False)),
            reference=self.data["reference"],
            **{CONFIG_KEYS.get(k, k): v for k, v in c.items()}, **opts)
        self.serving = self.data["serving"]

    def model_config(self):
        """The program's ModelConfig: its registry entry with the keys
        ``CONFIG_KEYS`` maps, the reference options that are also program
        fields (one statement for both sides), and ``program``."""
        from repro.configs import get_config
        base = get_config(self.data["registry"])
        fields = {f.name for f in dataclasses.fields(base)}
        c = self.data["config"]
        kw = {f: c[k] for k, f in CONFIG_KEYS.items() if k in c}
        kw["head_dim"] = self.dims.head_dim
        kw.update((k, v) for k, v in self.data.get(
            "reference_options", {}).items() if k in fields)
        kw.update(self.data.get("program", {}))
        return dataclasses.replace(base, **kw)


def make_weights(d: Dims, key, dtype: str = "float32") -> dict:
    """Every weight the reference's ``weight_shapes`` names, from ``key``,
    in one jitted program on the device. Matrices are N(0, 1/fan_in); the
    embedding N(0, 1); norm gains 1 + N(0, 0.1^2), so a dropped gain shows."""
    import jax
    import jax.numpy as jnp

    shapes = reference(d).weight_shapes(d)
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
