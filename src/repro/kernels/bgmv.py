"""bgmv — decode-time batched-gather LoRA (Pallas TPU).

One token per request; request b applies adapter ``idx[b]``:

    y[b] = (x[b] @ A[idx[b]]) @ B[idx[b]]

TPU adaptation of Punica/S-LoRA's CUDA BGMV (DESIGN §2): the per-token
adapter gather becomes a *scalar-prefetch* index — ``idx`` is carried in
SMEM and the A/B BlockSpec index_maps select the adapter slot per grid
step, so the weights stream HBM→VMEM for exactly the adapters used, no
materialised (B, din, r) gather. The shrink and expand matmuls fuse in
one kernel invocation (the rank-r intermediate never leaves VMEM).

Grid: (B, dout_tiles). x and y carry a unit middle axis, (B, 1, din)
and (B, 1, dout), so a one-token block (1, 1, din) keeps its last two
dims equal to the array's — Mosaic accepts a block whose trailing dims
are (8, 128) multiples or the full array dims, and a (1, din) row block
of a (B, din) array is neither. Blocks: x (1, 1, din), A (din, r),
B (r, T_out), y (1, 1, T_out). VMEM at din=6144, r=128, T_out=512:
~3.3 MB — comfortably under the ~16 MB/core budget; din and dout tiles
are multiples of 128 for MXU alignment (pad at the ops.py wrapper if
needed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bgmv_kernel(idx_ref, x_ref, a_ref, b_ref, o_ref):
    # x: (1, 1, din); a: (1, din, r); b: (1, r, T_out); o: (1, 1, T_out)
    t = jnp.dot(x_ref[0], a_ref[0],
                preferred_element_type=jnp.float32)      # (1, r)
    o_ref[0] = jnp.dot(t.astype(b_ref.dtype), b_ref[0],
                       preferred_element_type=jnp.float32
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_tile", "interpret"))
def bgmv(x: jax.Array, A: jax.Array, B: jax.Array, idx: jax.Array,
         out_tile: int = 512, interpret: bool = False) -> jax.Array:
    """x: (Bt, din); A: (n, din, r); B: (n, r, dout); idx: (Bt,) int32."""
    Bt, din = x.shape
    n, _, r = A.shape
    dout = B.shape[-1]
    out_tile = min(out_tile, dout)
    assert dout % out_tile == 0, (dout, out_tile)
    grid = (Bt, dout // out_tile)

    def x_map(b, j, idx_ref):
        return b, 0, 0

    def a_map(b, j, idx_ref):
        return idx_ref[b], 0, 0

    def b_map(b, j, idx_ref):
        return idx_ref[b], 0, j

    def o_map(b, j, idx_ref):
        return b, 0, j

    return pl.pallas_call(
        _bgmv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, din), x_map),
                pl.BlockSpec((1, din, r), a_map),
                pl.BlockSpec((1, r, out_tile), b_map),
            ],
            out_specs=pl.BlockSpec((1, 1, out_tile), o_map),
        ),
        out_shape=jax.ShapeDtypeStruct((Bt, 1, dout), x.dtype),
        interpret=interpret,
        name="bgmv",
    )(idx.astype(jnp.int32), x[:, None, :], A, B)[:, 0, :]
