"""paged_attention — decode attention over a paged KV pool (Pallas TPU).

The serving engine stores KV in fixed-size pages; request b's pages are
listed in ``page_table[b]``. The kernel computes, per (batch, kv-head),
flash-style online softmax over that request's pages:

    out[b,h] = softmax(q[b,h] · K[pages(b)]) · V[pages(b)]

TPU adaptation of vLLM's PagedAttention CUDA kernel: the page
indirection is a scalar-prefetch index_map (pages stream HBM→VMEM in
page-table order), and the online-softmax accumulator lives in VMEM
scratch, carried across the sequential last grid axis — TPU grids
iterate in order, which replaces the CUDA block reduction.

Grid: (B, Kh, n_page_steps). Blocks: q (1,1,G,dh) resident; k/v page
(1, page, dh). Scratch: acc (G, dh) f32 + m/l (G,) f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                       acc_ref, m_ref, l_ref, *, page: int, dh: int):
    b = pl.program_id(0)
    p = pl.program_id(2)
    n_steps = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * (dh ** -0.5)   # (G, dh)
    k = k_ref[0, 0].astype(jnp.float32)                  # (page, dh)
    v = v_ref[0, 0].astype(jnp.float32)

    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (G,page)
    pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = pos < len_ref[b]
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev = m_ref[...]                                   # (G, 1)
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    probs = jnp.exp(scores - m_new)                       # (G, page)
    l_ref[...] = l_ref[...] * alpha + probs.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        probs, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(p == n_steps - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_table: jax.Array, lengths: jax.Array,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Kh, G, dh); k/v_pages: (n_pages, page, Kh, dh);
    page_table: (B, P) int32; lengths: (B,) int32 -> (B, Kh, G, dh)."""
    B, Kh, G, dh = q.shape
    n_pages, page, _, _ = k_pages.shape
    P = page_table.shape[1]

    # Layout: bring Kh forward so a (page, dh) block slices cleanly.
    kp = k_pages.transpose(2, 0, 1, 3)     # (Kh, n_pages, page, dh)
    vp = v_pages.transpose(2, 0, 1, 3)

    grid = (B, Kh, P)

    def q_map(b, h, p, pt, ln):
        return b, h, 0, 0

    def kv_map(b, h, p, pt, ln):
        return h, pt[b, p], 0, 0

    def o_map(b, h, p, pt, ln):
        return b, h, 0, 0

    kernel = functools.partial(_paged_attn_kernel, page=page, dh=dh)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, dh), q_map),
                pl.BlockSpec((1, 1, page, dh), kv_map),
                pl.BlockSpec((1, 1, page, dh), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, G, dh), o_map),
            scratch_shapes=[
                pltpu.VMEM((G, dh), jnp.float32),   # acc
                pltpu.VMEM((G, 1), jnp.float32),    # running max
                pltpu.VMEM((G, 1), jnp.float32),    # running denom
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Kh, G, dh), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), q, kp, vp)
