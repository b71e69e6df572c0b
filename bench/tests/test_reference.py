"""The plain reference agrees with the program's own prefill at a tiny
size on the CPU (where both run in float32), with and without qk-norm,
with an adapter on q, k, v and o; and it notices a wrong adapter."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import cell, model
from bench.references import dense_gqa
from bench.tests.tiny import CONFIG


def setup(qk_norm, tmp_path):
    c = json.loads(json.dumps(CONFIG))
    c["reference_options"]["qk_norm"] = qk_norm
    p = tmp_path / "c.json"
    p.write_text(json.dumps(c))
    cf = model.ConfigFile(p)
    cfg = cf.model_config()
    w = model.make_weights(cf.dims, cell.prng(3))
    ads = model.make_adapters(cf.dims, [4, 16], 16, cell.prng(4))
    return cf, cfg, w, ads


def program_logits(cfg, w, ads, aid, toks, positions):
    """The program's prefill logits at each of ``positions``, one batch
    row per position, with adapter ``aid`` in slot 0 of an arena."""
    from repro.models import api
    from repro.models.lora_apply import init_lora_slots, write_adapter_to_slot
    arena = init_lora_slots(None, 2, cfg.n_layers, cfg.d_model, cfg.q_dim,
                            cfg.kv_dim, 16)
    arena = write_adapter_to_slot(arena, ads[aid], 0)
    B = len(positions)
    t = jnp.asarray(np.tile(toks, (B, 1)))
    out, _ = api.prefill(cfg, w, t, lora=arena,
                         adapter_idx=jnp.zeros(B, jnp.int32),
                         last_pos=jnp.asarray(positions, jnp.int32),
                         lora_backend="einsum")
    return np.asarray(out)


@pytest.mark.parametrize("qk_norm", [False, True])
def test_reference_matches_program(qk_norm, tmp_path):
    cf, cfg, w, ads = setup(qk_norm, tmp_path)
    assert ("layers/q_norm" in w) == qk_norm
    toks = np.random.default_rng(0).integers(0, cf.dims.vocab, 24)
    pos = [0, 5, 17, 23]
    want = program_logits(cfg, w, ads, 1, toks, pos)
    ad = {t: (jnp.asarray(a), jnp.asarray(b)) for t, (a, b) in ads[1].items()}
    got = np.asarray(dense_gqa.logits_at(w, ad, jnp.asarray(toks),
                                         jnp.asarray(pos), dims=cf.dims,
                                         dtype="float32"))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() < 1e-4 * scale
    other = {t: (jnp.asarray(a), jnp.asarray(b))
             for t, (a, b) in ads[0].items()}
    wrong = np.asarray(dense_gqa.logits_at(w, other, jnp.asarray(toks),
                                           jnp.asarray(pos), dims=cf.dims,
                                           dtype="float32"))
    assert np.abs(wrong - want).max() > 1e-2 * scale


def test_reference_is_causal(tmp_path):
    cf, _, w, ads = setup(False, tmp_path)
    ad = {t: (jnp.asarray(a), jnp.asarray(b)) for t, (a, b) in ads[0].items()}
    toks = np.arange(20) % cf.dims.vocab
    later = toks.copy()
    later[12:] = (later[12:] + 7) % cf.dims.vocab
    f = lambda t: np.asarray(dense_gqa.logits_at(  # noqa: E731
        w, ad, jnp.asarray(t), jnp.arange(12), dims=cf.dims,
        dtype="float32"))
    np.testing.assert_array_equal(f(toks), f(later))


def test_bfloat16_control_departs(tmp_path):
    cf, _, w, ads = setup(True, tmp_path)
    ad = {t: (jnp.asarray(a), jnp.asarray(b)) for t, (a, b) in ads[1].items()}
    toks = jnp.asarray(np.arange(24) % cf.dims.vocab)
    pos = jnp.arange(24)
    f32 = np.asarray(dense_gqa.logits_at(w, ad, toks, pos, dims=cf.dims,
                                         dtype="float32"))
    w16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), w)
    b16 = np.asarray(dense_gqa.logits_at(w16, ad, toks, pos, dims=cf.dims,
                                         dtype="bfloat16"))
    assert 1e-3 < np.abs(b16 - f32).max() / np.abs(f32).max() < 0.2
    assert dataclasses.is_dataclass(cf.dims)
