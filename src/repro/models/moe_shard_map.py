"""shard_map MoE: explicit all-to-all expert parallelism (§Perf cell B).

The einsum-dispatch MoE (moe.py) leaves GSPMD to discover the expert
all-to-all; measured on qwen3-moe prefill it instead emits ~13 GB/layer
of gathers+permutes. This layer takes explicit control:

  per data-shard (shard_map over the expert axis):
    1. route local tokens (global expert ids);
    2. bucket per (group, expert) with group-local capacity
       C = Tg·top_k·cf/E — one-hots stay (G_l, Tg, E, C), ~1 GB;
    3. `jax.lax.all_to_all` sends each expert's buckets to its home
       shard; 4. local expert FFN (E/n_shards experts resident);
    5. inverse all_to_all; local weighted combine.

Wire per layer = 2 × bucket bytes ≈ 2·T_l·k·cf·D·2B — the information-
theoretic minimum for einsum-style expert dispatch.

Used for inference (prefill/decode-prefill paths) when a mesh context
is active and E divides the expert axis; training keeps the einsum path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .moe import MOE_GROUP, _capacity, route


def _group_buckets(xg, idx, weights, E, cap, dtype):
    """xg: (G,Tg,D); idx/weights: (G,Tg,K). -> (send (G,E,C,D), comb)."""
    G, Tg, K = idx.shape
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)         # (G,Tg,K,E)
    pos = (jnp.cumsum(onehot.reshape(G, Tg * K, E), axis=1)
           .reshape(G, Tg, K, E) - 1)
    pos = jnp.sum(pos * onehot, axis=-1)                     # (G,Tg,K)
    keep = pos < cap
    w = weights * keep
    pos_c = jnp.minimum(pos, cap - 1)
    disp = (jax.nn.one_hot(idx, E, dtype=dtype)[..., None]
            * jax.nn.one_hot(pos_c, cap, dtype=dtype)[..., None, :])
    disp = disp * keep[..., None, None].astype(dtype)
    comb = jnp.sum(disp * w[..., None, None].astype(dtype), axis=2)
    disp = jnp.sum(disp, axis=2)                             # (G,Tg,E,C)
    send = jnp.einsum("gtd,gtec->gecd", xg, disp)
    return send, comb


def moe_block_a2a(x: jax.Array, router_w: jax.Array,
                  w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                  top_k: int, capacity_factor: float,
                  mesh, expert_axis: str = "data",
                  group_size: int = MOE_GROUP,
                  ) -> tuple[jax.Array, jax.Array]:
    """x: (B,S,D) batch-sharded on ``expert_axis``; weights E-sharded.

    Requires B % n_shards == 0 and E % n_shards == 0.
    Returns (out (B,S,D), aux scalar)."""
    E = router_w.shape[-1]
    n_shards = mesh.shape[expert_axis]
    assert E % n_shards == 0, (E, n_shards)
    E_l = E // n_shards

    def shard_fn(xs, rw, wg, wu, wd):
        Bl, S, D = xs.shape
        weights, idx, aux = route(xs, rw, top_k)
        T = Bl * S
        Tg = min(group_size, T)
        G = T // Tg
        cap = _capacity(Tg, E, top_k, capacity_factor)
        send, comb = _group_buckets(
            xs.reshape(G, Tg, D), idx.reshape(G, Tg, top_k),
            weights.reshape(G, Tg, top_k), E, cap, xs.dtype)
        # (G,E,C,D) -> a2a over experts' home shards. Global expert
        # e = (s, e_l) with s = e // E_l.
        send = send.reshape(G, n_shards, E_l, cap, D)
        recv = jax.lax.all_to_all(send, expert_axis, split_axis=1,
                                  concat_axis=0, tiled=False)
        # recv: (n_shards, G, E_l, C, D) — every shard's buckets for my
        # E_l experts; treat source shards as extra groups.
        h_in = recv.reshape(n_shards * G, E_l, cap, D)
        g = jnp.einsum("gecd,edf->gecf", h_in, wg)
        u = jnp.einsum("gecd,edf->gecf", h_in, wu)
        out = jnp.einsum("gecf,efd->gecd", jax.nn.silu(g) * u, wd)
        out = out.reshape(n_shards, G, E_l, cap, D)
        back = jax.lax.all_to_all(out, expert_axis, split_axis=0,
                                  concat_axis=1, tiled=False)
        # back: (G, n_shards, E_l, C, D) -> (G, E, C, D).
        expert_out = back.reshape(G, E, cap, D)
        y = jnp.einsum("gecd,gtec->gtd", expert_out, comb)
        return (y.reshape(Bl, S, D),
                jax.lax.pmean(aux, expert_axis))

    out, aux = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(expert_axis, None, None), P(None, None),
                  P(expert_axis, None, None),
                  P(expert_axis, None, None),
                  P(expert_axis, None, None)),
        out_specs=(P(expert_axis, None, None), P()),
        check_vma=False,
    )(x, router_w, w_gate, w_up, w_down)
    return out, aux
