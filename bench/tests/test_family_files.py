"""A family's shapes and counts come from the reference its
configuration file names. The dense numbers are pinned as they stood
before they moved out of the harness, and a second family, a tiny
Qwen3-MoE with a reference of its own in this directory, reaches the
harness through new files alone."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.harness import cell, flops, model
from bench.references import dense_gqa
from bench.tests import tiny

HERE = Path(__file__).resolve().parent
INTERNLM2 = HERE.parent / "configs" / "internlm2-1.8b.json"
MOE = HERE / "data" / "tiny-qwen3-moe.json"


def parent_make_weights(d, key, dtype="float32"):
    """The harness's ``weight_shapes`` and ``make_weights`` as they were
    before the shapes moved into the reference, kept to pin the weights
    bit for bit."""
    import jax
    import jax.numpy as jnp

    L, D, F = d.n_layers, d.d_model, d.d_ff
    shapes = {"embed/tok": (d.vocab, D), "final_norm": (D,),
              "layers/attn_norm": (L, D), "layers/q": (L, D, d.q_dim),
              "layers/k": (L, D, d.kv_dim), "layers/v": (L, D, d.kv_dim),
              "layers/o": (L, d.q_dim, D), "layers/mlp_norm": (L, D),
              "layers/gate": (L, D, F), "layers/up": (L, D, F),
              "layers/down": (L, F, D)}
    if not d.tied:
        shapes["lm_head"] = (D, d.vocab)
    if d.qk_norm:
        shapes["layers/q_norm"] = (L, d.head_dim)
        shapes["layers/k_norm"] = (L, d.head_dim)
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


def test_dense_weight_shapes_pinned():
    d = model.ConfigFile(INTERNLM2).dims
    L, D, F, V = 24, 2048, 8192, 92544
    assert dense_gqa.weight_shapes(d) == {
        "embed/tok": (V, D), "final_norm": (D,), "lm_head": (D, V),
        "layers/attn_norm": (L, D), "layers/q": (L, D, 2048),
        "layers/k": (L, D, 1024), "layers/v": (L, D, 1024),
        "layers/o": (L, 2048, D), "layers/mlp_norm": (L, D),
        "layers/gate": (L, D, F), "layers/up": (L, D, F),
        "layers/down": (L, F, D)}


def test_dense_matmul_params_pinned():
    d = model.ConfigFile(INTERNLM2).dims
    assert dense_gqa.matmul_params(d, True) == 1_699_479_552
    assert dense_gqa.matmul_params(d, False) == 1_509_949_440
    assert flops.dense_params(d) == 1_699_479_552
    assert flops.dense_params(d, head=False) == 1_509_949_440


@pytest.mark.parametrize("qk_norm", [False, True])
def test_dense_weights_bit_identical_to_parent(qk_norm, tmp_path):
    c = json.loads(json.dumps(tiny.CONFIG))
    c["reference_options"]["qk_norm"] = qk_norm
    (tmp_path / "c.json").write_text(json.dumps(c))
    d = model.ConfigFile(tmp_path / "c.json").dims
    key = cell.prng(2**33 + 7)
    got, want = model.make_weights(d, key), parent_make_weights(d, key)
    assert sorted(got) == sorted(want)
    for name in want:
        assert got[name].dtype == want[name].dtype
        assert np.asarray(got[name]).tobytes() == \
            np.asarray(want[name]).tobytes(), name


@pytest.fixture
def moe(monkeypatch):
    """The tiny MoE configuration, its reference found in this
    directory."""
    monkeypatch.setattr(model, "REFERENCES", HERE)
    return model.ConfigFile(MOE)


def test_moe_config_loads(moe):
    from repro.models.base import Family
    d, cfg = moe.dims, moe.model_config()
    assert cfg.family == Family.MOE and cfg.name == "qwen3-moe-235b-a22b"
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.vocab_size) == (2, 64, 4, 2, 16, 256)
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff_expert, cfg.moe_every) == (
        4, 2, 32, 1)
    assert cfg.capacity_factor == 2.0          # from "program"
    assert cfg.qk_norm and cfg.d_ff == 0       # the registry's own
    assert (d.n_experts, d.top_k, d.d_ff_expert, d.moe_every) == (4, 2, 32, 1)
    assert d.norm_topk_prob is True and d.mlp_only_layers == ()
    assert model.reference(d).__file__ == str(HERE / "moe_reference.py")
    with pytest.raises(AttributeError):
        d.d_ff
    hash(d)


@pytest.mark.parametrize("where, key, value, match", [
    ("program_why", "capacity_factor", None, "no program_why for"),
    # neither ``program`` nor ``reference_options`` may override what the
    # published config or the reference states: a cut goes through
    # ``reduced``, and the program and the reference read one value
    ("program", "n_experts", 8, "program restates"),  # a published width
    ("program", "d_model", 32, "program restates"),
    ("program", "num_hidden_layers", 1, "program restates"),  # its own key
    ("program", "d_ff", 128, "program restates"),  # mapped, not published
    ("program", "vocab", 64, "program restates"),  # a harness size
    ("program", "norm_topk_prob", False, "program restates"),
    ("program", "qk_norm", False, "program restates"),  # a reference option
    ("reference_options", "d_ff_expert", 16, "reference_options restates"),
    ("reference_options", "hidden_size", 32, "reference_options restates"),
])
def test_program_field_needs_its_reason(where, key, value, match, tmp_path):
    c = json.loads(MOE.read_text())
    c["reference_options"] = {"qk_norm": True}
    if value is None:
        del c[where][key]
    else:
        c[where][key] = value
        c["program_why"][key] = "a reason"
    (tmp_path / "c.json").write_text(json.dumps(c))
    with pytest.raises(ValueError, match=f"{match} .*'{key}'"):
        model.ConfigFile(tmp_path / "c.json")


def test_moe_weights_are_the_programs_input(moe):
    from repro.models.base import param_shapes
    w = model.make_weights(moe.dims, cell.prng(11))
    assert {k: tuple(v.shape) for k, v in w.items()} == param_shapes(
        moe.model_config())
    assert all(v.dtype == np.float32 for v in w.values())
    # the init rule follows the name: norm gains near 1, matrices 1/fan_in
    assert abs(float(w["moe/norm"].mean()) - 1) < 0.05
    assert abs(float(w["moe/w_gate"].std()) - 64 ** -0.5) < 0.02
    assert abs(float(w["moe/w_down"].std()) - 32 ** -0.5) < 0.02


def test_moe_flops_use_its_reference(moe):
    d = moe.dims
    # per layer: q 64x64, k 64x32, v 64x32, o 64x64; router 64x4; two of
    # the four experts, each 3 x 64x32; head 64x256
    per_layer = 4096 + 2048 + 2048 + 4096 + 256 + 2 * 3 * 2048
    assert flops.dense_params(d) == 2 * per_layer + 64 * 256
    assert flops.dense_params(d, head=False) == 2 * per_layer
    assert flops.decode_flops(d, 9, 4) == (
        2 * (2 * per_layer + 64 * 256) + flops.lora_flops_per_token(d, 4)
        + flops.attn_flops(d, 9))
    assert flops.prefill_flops(d, 4, 3, 2) == (
        2 * 2 * per_layer * 3 + flops.lora_flops_per_token(d, 2) * 3
        + flops.attn_flops(d, 1) * (5 + 6 + 7) + 2 * 64 * 256)
