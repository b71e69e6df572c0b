"""Placing a fresh prefill's K/V into its pages (paged, prefix cache off).

One donated program per (B, S) prefill bucket writes every placed row's
pages (``lm.write_prompt_pages``). It must leave each prompt position
exactly as the page-by-page ``.at[].set`` loop it replaced did, touch no
page but the placed rows' and the trash page 0, update the pool in
place, compile once per bucket, and keep greedy tokens identical to a
dense-KV engine.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Request
from repro.models import api
from repro.serving.engine import ChameleonEngine, EngineConfig

PAGE = 16
L_, N_PAGES, KH, DH = 2, 24, 2, 4


def eager_pages(kv_pages, k_new, v_new, lengths, pages):
    """The page-by-page write the program replaced: each row's prompt,
    a page at a time, into the pages it was given."""
    kp, vp = kv_pages
    for i, (L, rows) in enumerate(zip(lengths, pages)):
        for j in range(0, L, PAGE):
            pid, n = rows[j // PAGE], min(PAGE, L - j)
            kp = kp.at[:, pid, :n].set(k_new[:, i, j:j + n])
            vp = vp.at[:, pid, :n].set(v_new[:, i, j:j + n])
    return kp, vp


def bucket(lengths, rows):
    """(B, S) of the engine's prefill bucket, and random K/V for it."""
    S = 1 << max(3, (max(lengths) - 1).bit_length())
    B = 1 << max(0, (rows - 1).bit_length())
    rng = np.random.default_rng(sum(lengths) + B)
    k, v = (jnp.asarray(rng.standard_normal((L_, B, S, KH, DH)),
                        jnp.float32) for _ in range(2))
    return B, S, k, v


def pool(seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(
        (L_, N_PAGES, PAGE, KH, DH)), jnp.float32) for _ in range(2))


def page_rows(B, S, pages):
    rows = np.zeros((B, -(-S // PAGE)), np.int32)
    for i, p in enumerate(pages):
        rows[i, :len(p)] = p
    return jnp.asarray(rows)


def allocate(lengths):
    """Each row's pages, numbered from 3 and handed out in reverse."""
    out, nxt = [], 3
    for L in lengths:
        n = -(-L // PAGE)
        out.append(list(range(nxt, nxt + n))[::-1])
        nxt += n
    return out


CASES = [
    pytest.param([1], 1, id="L1-S8"),
    pytest.param([15], 1, id="L15"),
    pytest.param([16], 1, id="L16"),
    pytest.param([17], 1, id="L17"),
    pytest.param([33], 1, id="L33"),
    pytest.param([100], 1, id="L100"),
    pytest.param([1, 7], 2, id="B2-S8"),
    pytest.param([17, 100], 2, id="B2"),
    pytest.param([33], 2, id="B2-pad-row"),
    pytest.param([15, 16, 1], 3, id="B4-pad-row"),
]


@pytest.mark.parametrize("lengths,rows", CASES)
def test_program_writes_what_the_page_loop_wrote(lengths, rows):
    B, S, k_new, v_new = bucket(lengths, rows)
    pages = allocate(lengths)
    want = eager_pages(pool(), k_new, v_new, lengths, pages)
    before = pool()
    got_in = pool()
    got = jax.jit(api.write_prompt_pages, donate_argnums=(0,))(
        got_in, k_new, v_new, page_rows(B, S, pages))
    placed = {0} | {p for ps in pages for p in ps}
    for g, w, b in zip(got, want, before):
        g, w, b = np.asarray(g), np.asarray(w), np.asarray(b)
        for L, ps in zip(lengths, pages):
            pos = np.arange(L)
            idx = np.asarray(ps)[pos // PAGE]
            np.testing.assert_array_equal(g[:, idx, pos % PAGE],
                                          w[:, idx, pos % PAGE])
        others = [p for p in range(N_PAGES) if p not in placed]
        np.testing.assert_array_equal(g[:, others], b[:, others])
    # The pool was updated in place: the donated input is gone.
    assert all(x.is_deleted() for x in got_in)


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("chameleon-llama-7b").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def make_engine(small_model, paged=True, **kw):
    cfg, params = small_model
    return ChameleonEngine(cfg, params, EngineConfig(**{
        **dict(max_slots=4, max_len=128, n_lora_slots=4, n_adapters=4,
               seed=0, paged=paged, page_size=PAGE, prefix_cache=False),
        **kw}))


def serve(eng, lengths, out_len=6):
    handles = [eng.submit(Request(input_len=L, output_len=out_len,
                                  adapter_id=i % 4))
               for i, L in enumerate(lengths)]
    eng.drain()
    return [h.tokens for h in handles]


LENGTHS = [1, 15, 16, 17, 33, 100, 7, 40]


def test_greedy_tokens_match_a_dense_engine(small_model):
    dense = serve(make_engine(small_model, paged=False), LENGTHS)
    paged = serve(make_engine(small_model), LENGTHS)
    assert paged == dense
    assert all(len(t) == 6 for t in paged)


def test_one_compile_per_bucket(small_model):
    eng = make_engine(small_model)
    serve(eng, [17])                  # the (1, 32) bucket
    n = eng._place_pages_jit._cache_size()
    serve(eng, [30])                  # another length, the same bucket
    assert eng._place_pages_jit._cache_size() == n
    serve(eng, [40])                  # the (1, 64) bucket
    assert eng._place_pages_jit._cache_size() == n + 1


@pytest.mark.parametrize("mesh_shape", [
    (1, 1), pytest.param((2, 2), marks=pytest.mark.skipif(
        len(jax.devices()) < 4, reason="needs 4 devices (set XLA_FLAGS="
        "--xla_force_host_platform_device_count=4)"))])
def test_mesh_placement_matches_one_device(small_model, mesh_shape):
    """Under a mesh the program keeps the pool's sharding (donated) and
    the tokens are the single-device engine's."""
    base = serve(make_engine(small_model), LENGTHS)
    eng = make_engine(small_model, mesh_shape=mesh_shape)
    assert serve(eng, LENGTHS) == base
    assert all(x.sharding == s for x, s in zip(eng.kv_pages, eng._kv_sh))
