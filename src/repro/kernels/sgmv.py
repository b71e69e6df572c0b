"""sgmv — prefill-time segmented LoRA matmul (Pallas TPU).

Prefill batches contain contiguous token runs per request. The ops.py
wrapper sorts/pads tokens so every tile of ``tile`` tokens belongs to
exactly one adapter (``tile_slot[t]``); the kernel then runs, per tile,

    y_tile = (x_tile @ A[slot]) @ B[slot]

as two MXU matmuls with the adapter chosen by scalar-prefetch — the TPU
equivalent of S-LoRA's SGMV segment GEMMs (no warp-level machinery; the
segment → tile alignment plays the role of the CUDA segment offsets).

Grid: (n_tiles, dout_tiles). VMEM at tile=128, din=6144, r=128,
T_out=512: x 1.5 MB + A 1.5 MB + B .13 MB + y .13 MB ≈ 3.3 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sgmv_kernel(slot_ref, x_ref, a_ref, b_ref, o_ref):
    # x: (tile, din); a: (1, din, r); b: (1, r, T_out); o: (tile, T_out)
    t = jnp.dot(x_ref[...], a_ref[0],
                preferred_element_type=jnp.float32)       # (tile, r)
    o_ref[...] = jnp.dot(t.astype(b_ref.dtype), b_ref[0],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("tile", "out_tile", "interpret"))
def sgmv(x: jax.Array, A: jax.Array, B: jax.Array, tile_slot: jax.Array,
         tile: int = 128, out_tile: int = 512,
         interpret: bool = False) -> jax.Array:
    """x: (T, din), T % tile == 0; tile_slot: (T/tile,) adapter slots."""
    T, din = x.shape
    n, _, r = A.shape
    dout = B.shape[-1]
    out_tile = min(out_tile, dout)
    assert T % tile == 0 and dout % out_tile == 0
    n_tiles = T // tile
    grid = (n_tiles, dout // out_tile)

    return pl.pallas_call(
        _sgmv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tile, din), lambda t, j, s: (t, 0)),
                pl.BlockSpec((1, din, r), lambda t, j, s: (s[t], 0, 0)),
                pl.BlockSpec((1, r, out_tile), lambda t, j, s: (s[t], 0, j)),
            ],
            out_specs=pl.BlockSpec((tile, out_tile),
                                   lambda t, j, s: (t, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((T, dout), x.dtype),
        interpret=interpret,
        name="sgmv",
    )(tile_slot.astype(jnp.int32), x, A, B)


def pack_segments(seq_lens, adapter_slots, tile: int = 128):
    """Host-side packing: per-request segment → tile-aligned layout.

    Returns (perm, tile_slot, padded_T): ``perm[i]`` gives the source
    row of packed row i (or -1 for padding). Tokens of each request are
    padded up to a tile multiple so no tile spans two adapters.
    """
    import numpy as np
    perm, tile_slot = [], []
    src = 0
    for L, slot in zip(seq_lens, adapter_slots):
        pad = (-L) % tile
        perm.extend(range(src, src + L))
        perm.extend([-1] * pad)
        tile_slot.extend([slot] * ((L + pad) // tile))
        src += L
    return (np.asarray(perm, np.int32),
            np.asarray(tile_slot, np.int32),
            len(perm))
