"""The serving path's Pallas kernels compile for a TPU v5e chip.

Each case lowers one kernel through ``repro.kernels.ops`` at
internlm2-1.8b widths (d 2048, q 2048, kv 1024, head_dim 128, 16/8
heads so G = 2, r_max 32, page 16, 8 rows) and compiles it for one chip
of a *described* ``v5e:2x2`` topology — the TPU compiler runs here, no
chip is attached. Interpret mode cannot see what the chip's compiler
refuses (block shapes off the (8, 128) tiling, VMEM over budget), so
these are the guard for that. The dtypes are the engine's: float32
activations and KV, bfloat16 adapter-slot arenas.

The engine's prompt-page write is compiled at the benchmark cell's
shapes as well, to see that it updates the donated pool in place.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every test
worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import api

CFG = get_config("internlm2-1.8b")
R_MAX = 32
N_SLOTS = 8
ROWS = 8
PAGE = 16
MAX_LEN = 1024
N_PAGES = 1025


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip lands in the persistent cache but
    # cannot be read back without one: keep the cache off meanwhile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# (din, dout) of the adapted projections: q/o are d -> d, k/v d -> kv.
PROJ = {"q": (CFG.d_model, CFG.q_dim), "k": (CFG.d_model, CFG.kv_dim),
        "o": (CFG.q_dim, CFG.d_model)}


def _lora_text(one_chip, rows, S, proj):
    """The LoRA delta for ``rows`` runs of ``S`` tokens (S == 1 is the
    bgmv decode path, S > 1 the sgmv prefill path), compiled."""
    din, dout = PROJ[proj]
    args = (_spec((rows, S, din), jnp.float32, one_chip),
            _spec((N_SLOTS, din, R_MAX), jnp.bfloat16, one_chip),
            _spec((N_SLOTS, R_MAX, dout), jnp.bfloat16, one_chip),
            _spec((rows,), jnp.int32, one_chip))
    return _compiled_text(
        lambda x, a, b, i: ops.lora_delta_kernel(x, a, b, i,
                                                 interpret=False), *args)


def _paged_attention_text(one_chip):
    Kh, G, dh = CFG.n_kv_heads, CFG.n_heads // CFG.n_kv_heads, CFG.head_dim
    pool = (N_PAGES, PAGE, Kh, dh)
    args = (_spec((ROWS, Kh, G, dh), jnp.float32, one_chip),
            _spec(pool, jnp.float32, one_chip),
            _spec(pool, jnp.float32, one_chip),
            _spec((ROWS, MAX_LEN // PAGE), jnp.int32, one_chip),
            _spec((ROWS,), jnp.int32, one_chip))
    return _compiled_text(
        lambda q, k, v, pt, ln: ops.paged_attention(
            q, k, v, pt, ln, prefer_kernel=True, interpret=False), *args)


@pytest.mark.parametrize("proj", sorted(PROJ))
def test_bgmv_decode_compiles(one_chip, proj):
    assert "tpu_custom_call" in _lora_text(one_chip, ROWS, 1, proj)


@pytest.mark.parametrize("B,S", [(1, 512), (4, 128)])
def test_sgmv_prefill_compiles(one_chip, B, S):
    assert "tpu_custom_call" in _lora_text(one_chip, B, S, "q")


def test_paged_attention_compiles(one_chip):
    assert "tpu_custom_call" in _paged_attention_text(one_chip)


@pytest.mark.parametrize("kernel", ["bgmv", "sgmv", "paged_attention"])
def test_kernel_names_in_compile(one_chip, kernel):
    """Each kernel's ``pallas_call`` names its op, as the benchmark's
    trace readers key on it (``%bgmv``, ``%sgmv``, ``%paged_attention``)."""
    txt = (_paged_attention_text(one_chip) if kernel == "paged_attention"
           else _lora_text(one_chip, 4, 1 if kernel == "bgmv" else 128,
                           "q"))
    op = re.compile(rf"%{kernel}(\.\d+)? = \S+ custom-call\(.*"
                    r'custom_call_target="tpu_custom_call"')
    assert any(op.search(line) for line in txt.splitlines()), kernel


def test_prompt_page_write_updates_the_pool_in_place(one_chip):
    """The placement program at the benchmark cell's shapes: a pool of
    515 pages of 16 tokens (k and v) and a (2, 2048) prefill's K/V.
    Both pool outputs alias the donated inputs, and the temporaries stay
    under a tenth of one pool: no second pool, no reordered K/V copy."""
    pool = (CFG.n_layers, 515, PAGE, CFG.n_kv_heads, CFG.head_dim)
    new = (CFG.n_layers, 2, 2048, CFG.n_kv_heads, CFG.head_dim)
    args = ((_spec(pool, jnp.float32, one_chip),) * 2,
            _spec(new, jnp.float32, one_chip),
            _spec(new, jnp.float32, one_chip),
            _spec((2, 2048 // PAGE), jnp.int32, one_chip))
    compiled = jax.jit(api.write_prompt_pages, donate_argnums=(0,)).lower(
        *args).compile()
    alias = re.search(r"input_output_alias=\{ \{0\}: \(0, \{\}, [a-z-]+\), "
                      r"\{1\}: \(1, \{\}, [a-z-]+\) \}", compiled.as_text())
    assert alias, "the pool outputs do not alias the donated pool"
    pool_bytes = 4 * int(np.prod(pool))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 10, mem
