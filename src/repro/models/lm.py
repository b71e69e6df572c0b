"""Decoder-only LM assembly: dense / MoE / VLM families.

Parameters are a *flat* dict {path: array} (see base.param_shapes) with
per-layer tensors stacked on axis 0 — the layer loop is a single
``lax.scan`` whose xs are the stacked stacks, keeping the HLO small
(one layer body regardless of depth) and making remat policy uniform.
MoE-interleaved models (llama4: moe_every=2) scan over supersteps of
(dense layer, MoE layer) pairs.

Three entry points per model, all pure functions of (cfg, params, ...):

- ``forward``      full-sequence logits (training / evaluation)
- ``prefill``      logits for the last position + per-layer KV caches
- ``decode_step``  one token against padded KV caches (+ optional
                   multi-adapter LoRA via per-request adapter indices —
                   the paper's serving data plane)
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .base import Family, ModelConfig, param_shapes
from .layers import (apply_rope, cross_entropy, decode_attention, embed,
                     gqa_attention, head_rms_norm, mrope_cos_sin,
                     gelu_mlp, rms_norm, rope_cos_sin, suffix_attention,
                     swiglu, unembed)
from .lora_apply import lora_delta
from repro.core.sampling import (SPEC_ACCEPT_FOLD, SPEC_DRAFT_FOLD,
                                 SPEC_RESIDUAL_FOLD)
from repro.distributed.act_sharding import (constrain_attn_merged,
                                            constrain_btd,
                                            constrain_boundary,
                                            constrain_logits,
                                            constrain_residual,
                                            constrain_expert_ecd)
from .moe import moe_block, moe_block_gather


# ----------------------------------------------------------------- init
def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=jnp.bfloat16) -> dict[str, jax.Array]:
    shapes = param_shapes(cfg)
    params: dict[str, jax.Array] = {}
    keys = jax.random.split(key, len(shapes))
    for (path, shape), k in zip(sorted(shapes.items()), keys):
        leaf = path.split("/")[-1]
        if "norm" in leaf:
            params[path] = jnp.ones(shape, dtype)
        elif leaf in ("A_log",):
            # S4D-real init: A in [1, d_state] (mamba1) / [1, 16] (mamba2).
            hi = 16.0
            params[path] = jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, hi))
        elif leaf in ("ssm_D",):
            params[path] = jnp.ones(shape, jnp.float32)
        elif leaf in ("dt_bias",):
            # Bias such that softplus(dt_bias) spans [1e-3, 1e-1].
            u = jax.random.uniform(k, shape, jnp.float32, 1e-3, 1e-1)
            params[path] = jnp.log(jnp.expm1(u))
        elif leaf.endswith("_bias") or leaf in ("conv_b",):
            params[path] = jnp.zeros(shape, dtype)
        elif leaf in ("enc_pos", "dec_pos"):
            params[path] = (0.02 * jax.random.normal(k, shape)).astype(dtype)
        else:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            std = fan_in ** -0.5
            params[path] = (std * jax.random.normal(k, shape)).astype(dtype)
    return params


# ------------------------------------------------------------ attention
def _qkv_proj(cfg: ModelConfig, x: jax.Array, p: dict, cos, sin,
              lora=None, adapter_idx=None, prefix: str = "",
              lora_backend: str = "einsum"):
    """Normed q/k/v projections (+bias, +LoRA, +qk-norm, +RoPE).

    Shared by the dense and paged attention blocks so the two data
    planes are numerically the same computation up to the KV layout.
    Returns (h, q, k, v) with q (B,S,H,Dh), k/v (B,S,Kh,Dh); ``h`` is the
    post-norm hidden the o-projection's residual pairs with.
    """
    B, S, _ = x.shape
    h = rms_norm(x, p[prefix + "attn_norm"], cfg.norm_eps)

    def proj(name):
        y = jnp.einsum("bsd,de->bse", h, p[prefix + name])
        if cfg.qkv_bias and prefix + name + "_bias" in p:
            y = y + p[prefix + name + "_bias"]
        if lora is not None and name in lora:
            y = y + lora_delta(h, lora[name], adapter_idx,
                               backend=lora_backend)
        return y

    q = proj("q").reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = proj("k").reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = proj("v").reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = head_rms_norm(q, p[prefix + "q_norm"], cfg.norm_eps)
        k = head_rms_norm(k, p[prefix + "k_norm"], cfg.norm_eps)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return h, q, k, v


def _o_proj(cfg: ModelConfig, x: jax.Array, out: jax.Array, p: dict,
            lora=None, adapter_idx=None, prefix: str = "",
            lora_backend: str = "einsum") -> jax.Array:
    """Output projection + LoRA + residual. out: (B, S, q_dim)."""
    out = constrain_attn_merged(out)
    o = jnp.einsum("bse,ed->bsd", out, p[prefix + "o"])
    if lora is not None and "o" in lora:
        o = o + lora_delta(out, lora["o"], adapter_idx,
                           backend=lora_backend)
    return constrain_residual(x + o)


def _attn(cfg: ModelConfig, x: jax.Array, p: dict, cos, sin,
          kv_cache=None, cache_len=None, lora=None, adapter_idx=None,
          prefix: str = "", lora_backend: str = "einsum"):
    """Shared attention block.

    Returns (out, new_kv): new_kv is (k, v) for prefill or the updated
    (k_cache, v_cache, ) slices for decode.
    """
    B, S, _ = x.shape
    _, q, k, v = _qkv_proj(cfg, x, p, cos, sin, lora, adapter_idx, prefix,
                           lora_backend)

    if kv_cache is None:
        out = gqa_attention(q, k, v, causal=True)
        new_kv = (k, v)
    else:
        k_cache, v_cache = kv_cache
        # Scatter the new entries at cache_len (decode: S == 1).
        idx = jnp.reshape(cache_len, (B, 1)) + jnp.arange(S)[None]
        bidx = jnp.arange(B)[:, None] + jnp.zeros_like(idx)
        k_cache = k_cache.at[bidx, idx].set(k)
        v_cache = v_cache.at[bidx, idx].set(v)
        out = decode_attention(q, k_cache, v_cache,
                               cache_len + S)
        new_kv = (k_cache, v_cache)

    out = out.reshape(B, S, cfg.q_dim)
    return _o_proj(cfg, x, out, p, lora, adapter_idx, prefix,
                   lora_backend), new_kv


def _attn_paged(cfg: ModelConfig, x: jax.Array, p: dict, cos, sin,
                k_pages: jax.Array, v_pages: jax.Array,
                page_table: jax.Array, cache_len: jax.Array,
                page_idx: jax.Array, page_off: jax.Array,
                lora=None, adapter_idx=None,
                lora_backend: str = "einsum"):
    """Decode attention over paged KV (one layer; S == 1).

    k/v_pages: (n_pages, page, Kh, Dh); page_table: (B, P) physical page
    ids per request; page_idx/page_off: (B,) precomputed write position
    of the new token (page_table[b, cache_len[b]//page], cache_len[b] %
    page). The fresh K/V is scattered into the pages, then attention
    runs over the request's page list via ``kernels.ops.paged_attention``
    (Pallas on TPU, the jnp reference on CPU). Requests never share
    pages, so the batched scatter cannot collide (inactive slots all
    write the reserved trash page 0, which active tables never map).
    """
    from repro.kernels.ops import paged_attention

    B, S, _ = x.shape
    _, q, k, v = _qkv_proj(cfg, x, p, cos, sin, lora, adapter_idx,
                           lora_backend=lora_backend)
    k_pages = k_pages.at[page_idx, page_off].set(k[:, 0])
    v_pages = v_pages.at[page_idx, page_off].set(v[:, 0])
    Kh, G = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    qh = q[:, 0].reshape(B, Kh, G, cfg.head_dim)
    out = paged_attention(qh, k_pages, v_pages, page_table,
                          cache_len + 1)
    out = out.reshape(B, S, cfg.q_dim)
    return _o_proj(cfg, x, out, p, lora, adapter_idx,
                   lora_backend=lora_backend), (k_pages, v_pages)


def _mlp(cfg, x, p, prefix=""):
    h = rms_norm(x, p[prefix + "mlp_norm"], cfg.norm_eps)
    if not cfg.gated_mlp:
        return x + gelu_mlp(h, p[prefix + "up"], p[prefix + "down"])
    return x + swiglu(h, p[prefix + "gate"], p[prefix + "up"],
                      p[prefix + "down"])


def _moe(cfg, x, p):
    from repro.distributed.act_sharding import moe_a2a_mesh
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    a2a = moe_a2a_mesh()
    B, S, _ = x.shape
    # a2a MoE for distributed inference: cuts MoE decode collective
    # 235-560x vs the expert-gather path (whose jnp.take of expert
    # weights is itself the HBM bill); both paths carry heavy per-layer
    # transient buffers on MoE decode (§Perf cell B, iter 4 caveat).
    if a2a is not None:
        mesh, axis = a2a
        ns = mesh.shape[axis]
        if cfg.n_experts % ns == 0 and B % ns == 0:
            from .moe_shard_map import moe_block_a2a
            # Decode: dropless capacity (cf = E/k makes cap == Tg).
            cf = (cfg.n_experts / cfg.top_k if S == 1
                  else cfg.capacity_factor)
            y, aux = moe_block_a2a(h, p["router"], p["w_gate"],
                                   p["w_up"], p["w_down"], cfg.top_k,
                                   cf, mesh, expert_axis=axis)
            if cfg.shared_expert_ff:
                y = y + swiglu(h, p["shared_gate"], p["shared_up"],
                               p["shared_down"])
            return x + y, aux
    if S == 1:
        # Decode: dropless expert-gather (see moe.moe_block_gather).
        y, aux = moe_block_gather(h, p["router"], p["w_gate"],
                                  p["w_up"], p["w_down"], cfg.top_k)
    else:
        y, aux = moe_block(h, p["router"], p["w_gate"], p["w_up"],
                           p["w_down"], cfg.top_k, cfg.capacity_factor)
    if cfg.shared_expert_ff:
        y = y + swiglu(h, p["shared_gate"], p["shared_up"],
                       p["shared_down"])
    return x + y, aux


# ----------------------------------------------------- stacked param views
def _slice_group(params: dict, prefix: str) -> dict:
    n = len(prefix)
    return {k[n:]: v for k, v in params.items() if k.startswith(prefix)}


def _positions(cfg: ModelConfig, tokens_shape, offset, mrope_pos):
    B, S = tokens_shape
    if cfg.mrope:
        assert mrope_pos is not None, "VLM needs (3,B,S) M-RoPE positions"
        return mrope_cos_sin(mrope_pos, cfg.head_dim, cfg.rope_theta,
                             cfg.mrope_sections)
    pos = jnp.arange(S)[None, :] + jnp.reshape(offset, (-1, 1))
    return rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta)


# ------------------------------------------------------------- backbone
def _backbone(cfg: ModelConfig, params: dict, x: jax.Array, cos, sin,
              kv_caches=None, cache_len=None, lora=None, adapter_idx=None,
              collect_kv=False, lora_backend: str = "einsum"):
    """Scan over layers. Returns (hidden, new_kv_stack, aux_loss).

    ``collect_kv`` stacks per-layer fresh K/V (prefill). Training leaves
    it False so the scan carries no dead 100-GB KV output to rely on
    DCE for.
    """
    attn_stack = _slice_group(params, "layers/")
    lora_stack = lora  # {proj: (L, slots, din, r) & (L, slots, r, dout)}

    if cfg.family == Family.MOE:
        return _backbone_moe(cfg, params, x, cos, sin, kv_caches,
                             cache_len, lora, adapter_idx, collect_kv,
                             lora_backend)

    def body(carry, xs):
        h = constrain_boundary(carry)
        p = xs["p"]
        kv = (xs["k"], xs["v"]) if kv_caches is not None else None
        lr = xs.get("lora")
        h, new_kv = _attn(cfg, h, p, cos, sin, kv, cache_len, lr,
                          adapter_idx, lora_backend=lora_backend)
        h = constrain_boundary(_mlp(cfg, h, p))
        if kv_caches is None and not collect_kv:
            new_kv = None
        return h, new_kv

    if cfg.remat:
        body = jax.checkpoint(body)
    xs = {"p": attn_stack}
    if kv_caches is not None:
        xs["k"], xs["v"] = kv_caches
    if lora_stack is not None:
        xs["lora"] = lora_stack
    h, kv_out = jax.lax.scan(body, x, xs)
    return h, kv_out, jnp.float32(0)


def _backbone_moe(cfg, params, x, cos, sin, kv_caches, cache_len,
                  lora, adapter_idx, collect_kv=False,
                  lora_backend: str = "einsum"):
    """MoE scan; supersteps of (moe_every) layers, last one MoE."""
    E = cfg.moe_every
    L = cfg.n_layers
    n_super = L // E
    attn_stack = _slice_group(params, "layers/")
    attn_stack = {k: v.reshape((n_super, E) + v.shape[1:])
                  for k, v in attn_stack.items()}
    moe_stack = _slice_group(params, "moe/")
    dense_stack = _slice_group(params, "dense_mlp/")
    if dense_stack:
        n_dense_per = E - 1
        dense_stack = {k: v.reshape((n_super, n_dense_per) + v.shape[1:])
                       for k, v in dense_stack.items()}

    def body(carry, xs):
        h, aux = carry
        h = constrain_boundary(h)
        new_kv = []
        for e in range(E):
            p_attn = {k: v[e] for k, v in xs["attn"].items()}
            kv = ((xs["k"][e], xs["v"][e])
                  if kv_caches is not None else None)
            lr = ({proj: (ab[0][e], ab[1][e])
                   for proj, ab in xs["lora"].items()}
                  if lora is not None else None)
            h, kv_e = _attn(cfg, h, p_attn, cos, sin, kv, cache_len,
                            lr, adapter_idx, lora_backend=lora_backend)
            new_kv.append(kv_e)
            if e == E - 1:
                h, a = _moe(cfg, h, xs["moe"])
                h = constrain_btd(h)
                aux = aux + a
            else:
                p_d = {k: v[e] for k, v in xs["dense"].items()}
                h = _mlp(cfg, h, p_d)
        want_kv = kv_caches is not None or collect_kv
        k_out = (jnp.stack([kv[0] for kv in new_kv]) if want_kv
                 else jnp.float32(0))
        v_out = (jnp.stack([kv[1] for kv in new_kv]) if want_kv
                 else jnp.float32(0))
        return (h, aux), (k_out, v_out)

    if cfg.remat:
        body = jax.checkpoint(body)
    xs = {"attn": attn_stack, "moe": moe_stack}
    if dense_stack:
        xs["dense"] = dense_stack
    if kv_caches is not None:
        k, v = kv_caches
        xs["k"] = k.reshape((n_super, E) + k.shape[1:])
        xs["v"] = v.reshape((n_super, E) + v.shape[1:])
    if lora is not None:
        xs["lora"] = {proj: (a.reshape((n_super, E) + a.shape[1:]),
                             b.reshape((n_super, E) + b.shape[1:]))
                      for proj, (a, b) in lora.items()}
    (h, aux), kv_out = jax.lax.scan(body, (x, jnp.float32(0)), xs)
    if kv_caches is not None or collect_kv:
        k_out, v_out = kv_out
        k_out = k_out.reshape((L,) + k_out.shape[2:])
        v_out = v_out.reshape((L,) + v_out.shape[2:])
        kv_out = (k_out, v_out)
    return h, kv_out, aux


# ----------------------------------------------------------- entry points
def forward(cfg: ModelConfig, params: dict, tokens: jax.Array,
            mrope_pos: jax.Array | None = None) -> jax.Array:
    """Full-sequence logits (B, S, V)."""
    x = constrain_btd(embed(tokens, params["embed/tok"]))
    cos, sin = _positions(cfg, tokens.shape, 0, mrope_pos)
    h, _, _aux = _backbone(cfg, params, x, cos, sin)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    table = (params["embed/tok"].T if cfg.tie_embeddings
             else params["lm_head"])
    return constrain_logits(unembed(h, table))


def train_loss(cfg: ModelConfig, params: dict, tokens: jax.Array,
               labels: jax.Array, mrope_pos=None,
               aux_weight: float = 0.01) -> jax.Array:
    x = constrain_btd(embed(tokens, params["embed/tok"]))
    cos, sin = _positions(cfg, tokens.shape, 0, mrope_pos)
    h, _, aux = _backbone(cfg, params, x, cos, sin)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    table = (params["embed/tok"].T if cfg.tie_embeddings
             else params["lm_head"])
    logits = constrain_logits(unembed(h, table))
    return cross_entropy(logits, labels) + aux_weight * aux


def make_kv_caches(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def make_paged_kv(cfg: ModelConfig, n_pages: int, page_size: int,
                  dtype=jnp.bfloat16):
    """Paged KV pool: (L, n_pages, page, Kh, Dh) ×2.

    Page 0 is reserved as the trash page inactive batch slots write
    into; allocators hand out pages 1..n_pages-1 (engine convention).
    """
    shape = (cfg.n_layers, n_pages, page_size, cfg.n_kv_heads,
             cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def write_prompt_pages(kv_pages, k_new: jax.Array, v_new: jax.Array,
                       page_rows: jax.Array):
    """Write a prefill's K/V into whole pages of the pool, in one program.

    kv_pages: (k_pages, v_pages) each (L, n_pages, page, Kh, Dh);
    k_new/v_new: (L, B, S, Kh, Dh) from ``prefill``; page_rows: (B, P)
    with P = ceil(S / page), row i's j-th page at [i, j]. Entries past a
    row's prompt pages, and every entry of a pad row, are the trash page
    0. A row's positions past its prompt length land in its last page as
    K/V of padding tokens: attention masks at ``cache_len`` and decode
    overwrites them before they are read.

    Donate ``kv_pages``: each page is one in-place dynamic-update-slice
    inside a loop. A single scatter of all pages would have XLA on TPU
    first copy the (L, B*P, ...) updates into page-major order, a second
    copy of the prefill's K/V in HBM.
    """
    k_pages, v_pages = kv_pages
    page = k_pages.shape[2]
    L, B, S = k_new.shape[:3]
    P = page_rows.shape[1]
    if P * page != S:               # an S = 8 bucket under 16-token pages
        pad = ((0, 0), (0, 0), (0, P * page - S), (0, 0), (0, 0))
        k_new, v_new = jnp.pad(k_new, pad), jnp.pad(v_new, pad)
    size = (L, 1, page) + k_new.shape[3:]

    def body(j, kv):
        i, q = j // P, j % P
        src, dst = (0, i, q * page, 0, 0), (0, page_rows[i, q], 0, 0, 0)
        return tuple(jax.lax.dynamic_update_slice(
            pool, jax.lax.dynamic_slice(new, src, size), dst)
            for pool, new in zip(kv, (k_new, v_new)))

    return jax.lax.fori_loop(0, B * P, body, (k_pages, v_pages))


# ------------------------------------------------------------- sampling
def sample_tokens(logits: jax.Array, temperature: jax.Array,
                  top_k: jax.Array, top_p: jax.Array, seeds: jax.Array,
                  positions: jax.Array) -> jax.Array:
    """Batched next-token sampler, one row per batch slot (jit-safe).

    logits (B, V) float; temperature/top_p (B,) float; top_k (B,) int
    (0 = disabled); seeds (B,) uint32-ish int; positions (B,) int token
    index being sampled. Rows with ``temperature <= 0`` are greedy
    argmax — bit-identical to the pre-SamplingParams engine. Stochastic
    rows draw Gumbel noise from ``fold_in(PRNGKey(seed), position)``,
    so a token's randomness depends only on (seed, position): the same
    request resamples identically across batch compositions, dense vs
    paged KV, einsum vs kernel LoRA backends, and squash re-execution.

    top-k keeps the k best logits; top-p keeps the smallest sorted
    prefix whose cumulative probability reaches top_p (the best token
    always survives both masks).
    """
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    # Rank every vocab entry within its row (0 = best).
    order = jnp.argsort(scaled, axis=-1)[:, ::-1]
    ranks = jnp.argsort(order, axis=-1)
    keep_k = ranks < jnp.where(top_k > 0, top_k, V)[:, None]
    # Nucleus: keep entries whose *preceding* cumulative mass < top_p.
    probs = jax.nn.softmax(scaled, axis=-1)
    sorted_p = jnp.take_along_axis(probs, order, axis=-1)
    cum = jnp.cumsum(sorted_p, axis=-1)
    keep_p_sorted = (cum - sorted_p) < top_p[:, None]
    keep_p = jnp.take_along_axis(keep_p_sorted, ranks, axis=-1)
    masked = jnp.where(keep_k & keep_p, scaled, -jnp.inf)
    keys = jax.vmap(lambda s, p: jax.random.fold_in(
        jax.random.PRNGKey(s), p))(seeds.astype(jnp.uint32),
                                   positions.astype(jnp.uint32))
    gumbel = jax.vmap(lambda k: jax.random.gumbel(k, (V,)))(keys)
    sampled = jnp.argmax(masked + gumbel, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


# ------------------------------------------------- fused decode hot loop
def _fused_decode_scan(decode_one, tokens, cache_len, active, positions,
                       kv, budget, stop_ids, temperature, top_k, top_p,
                       seeds, max_ctx: int, n_steps: int,
                       all_greedy: bool):
    """Run ``n_steps`` decode+sample+advance iterations on device.

    One ``lax.scan`` whose body is: decode one token for every batch
    slot, pick the next token (argmax when the whole batch is greedy —
    bit-identical to the two-dispatch engine loop — else the seeded
    batched sampler), advance ``cache_len``/``positions`` for active
    rows, and fold the per-row finish conditions into an on-device
    done-mask so a row that hits its budget / a stop token / the
    context bound stops emitting *inside* the horizon. The host syncs
    one ``(n_steps, B)`` token block + emit-mask instead of one (B, V)
    logits round-trip per token.

    Semantics mirror the single-step engine loop exactly (the parity
    suite asserts token-identity): ``tokens`` is overwritten for every
    row including inactive ones, ``cache_len`` advances by the *pre*-
    done-check active mask, and rows past their end keep decoding
    masked garbage whose emissions are dropped via the emit mask.

    decode_one: (tokens (B,1), kv, cache_len) -> (logits (B,V), kv').
    Returns ((tokens', kv', cache_len', active', positions'),
             toks (n_steps, B) int32, emits (n_steps, B) bool).
    """

    def body(carry, _):
        tokens, kv, cache_len, active, positions = carry
        logits, kv = decode_one(tokens, kv, cache_len)
        if all_greedy:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            nxt = sample_tokens(logits, temperature, top_k, top_p,
                                seeds, positions)
        emit = active
        step = active.astype(jnp.int32)
        new_len = cache_len + step
        new_pos = positions + step
        # Finish conditions, verbatim from the engine bookkeeping:
        # budget exhausted (req.done), a SamplingParams stop id, or the
        # context bound generated + input_len >= max_len - 1 — with
        # cache_len == input_len + generated - 1, that is new_len + 1.
        hit_stop = (nxt[:, None] == stop_ids).any(axis=-1)
        done_now = emit & ((new_pos >= budget) | hit_stop
                           | (new_len + 1 >= max_ctx - 1))
        carry = (nxt[:, None], kv, new_len, emit & ~done_now, new_pos)
        return carry, (nxt, emit)

    init = (tokens, kv, cache_len, active, positions)
    carry, (toks, emits) = jax.lax.scan(body, init, None, length=n_steps)
    return carry, toks, emits


def decode_fused(cfg: ModelConfig, params: dict, tokens: jax.Array,
                 kv_caches, cache_len: jax.Array, active: jax.Array,
                 positions: jax.Array, budget: jax.Array,
                 stop_ids: jax.Array, temperature: jax.Array,
                 top_k: jax.Array, top_p: jax.Array, seeds: jax.Array,
                 *, n_steps: int, all_greedy: bool, max_ctx: int,
                 lora=None, adapter_idx=None,
                 lora_backend: str = "einsum"):
    """Fused multi-step decode over the dense KV slab (see
    ``_fused_decode_scan``). active (B,) bool; positions (B,) the
    output index each row samples next; budget (B,) max output tokens;
    stop_ids (B, n_stop) int32 padded with -1 (n_stop may be 0)."""

    def decode_one(tok, kv, clen):
        return decode_step(cfg, params, tok, kv, clen, lora=lora,
                           adapter_idx=adapter_idx,
                           lora_backend=lora_backend)

    return _fused_decode_scan(decode_one, tokens, cache_len, active,
                              positions, kv_caches, budget, stop_ids,
                              temperature, top_k, top_p, seeds, max_ctx,
                              n_steps, all_greedy)


def decode_fused_paged(cfg: ModelConfig, params: dict, tokens: jax.Array,
                       kv_pages, page_table: jax.Array,
                       cache_len: jax.Array, active: jax.Array,
                       positions: jax.Array, budget: jax.Array,
                       stop_ids: jax.Array, temperature: jax.Array,
                       top_k: jax.Array, top_p: jax.Array,
                       seeds: jax.Array, *, n_steps: int,
                       all_greedy: bool, max_ctx: int, lora=None,
                       adapter_idx=None, lora_backend: str = "einsum"):
    """Fused multi-step decode over the paged KV pool. The page table
    is read-only across the horizon: the engine pre-allocates pages
    covering every write the scan can make, so ``cache_len // page``
    always lands on a mapped page (done rows keep overwriting the slot
    one past their final token, which attention masks by length)."""

    def decode_one(tok, kv, clen):
        return decode_step_paged(cfg, params, tok, kv, page_table, clen,
                                 lora=lora, adapter_idx=adapter_idx,
                                 lora_backend=lora_backend)

    return _fused_decode_scan(decode_one, tokens, cache_len, active,
                              positions, kv_pages, budget, stop_ids,
                              temperature, top_k, top_p, seeds, max_ctx,
                              n_steps, all_greedy)


def prefill(cfg: ModelConfig, params: dict, tokens: jax.Array,
            mrope_pos=None, lora=None, adapter_idx=None, last_pos=None,
            lora_backend: str = "einsum"):
    """Returns (last-position logits (B, V), (k_stack, v_stack)).

    ``last_pos`` (B,) selects the position whose logits are returned —
    needed for right-padded prefill batches (defaults to S-1).
    ``lora_backend="kernel"`` routes the LoRA deltas through the Pallas
    sgmv kernel (each request's row is one contiguous token run).
    """
    x = constrain_btd(embed(tokens, params["embed/tok"]))
    cos, sin = _positions(cfg, tokens.shape, 0, mrope_pos)
    h, kv, _ = _backbone(cfg, params, x, cos, sin, lora=lora,
                         adapter_idx=adapter_idx, collect_kv=True,
                         lora_backend=lora_backend)
    if last_pos is None:
        h_last = h[:, -1:]
    else:
        h_last = jnp.take_along_axis(
            h, jnp.reshape(last_pos, (-1, 1, 1)).astype(jnp.int32), axis=1)
    h_last = rms_norm(h_last, params["final_norm"], cfg.norm_eps)
    table = (params["embed/tok"].T if cfg.tie_embeddings
             else params["lm_head"])
    return constrain_logits(unembed(h_last, table)[:, 0]), kv


def decode_step(cfg: ModelConfig, params: dict, tokens: jax.Array,
                kv_caches, cache_len: jax.Array, mrope_pos=None,
                lora=None, adapter_idx=None,
                lora_backend: str = "einsum"):
    """One decode step.

    tokens: (B, 1); kv_caches: (k, v) each (L, B, Smax, Kh, Dh);
    cache_len: (B,) valid lengths. Returns (logits (B,V), new caches).
    ``lora_backend="kernel"`` routes the per-token LoRA deltas through
    the Pallas bgmv kernel.
    """
    x = constrain_btd(embed(tokens, params["embed/tok"]))
    if cfg.mrope:
        cos, sin = _positions(cfg, tokens.shape, cache_len, mrope_pos)
    else:
        cos, sin = _positions(cfg, tokens.shape, cache_len, None)
    h, kv, _ = _backbone(cfg, params, x, cos, sin, kv_caches=kv_caches,
                         cache_len=cache_len, lora=lora,
                         adapter_idx=adapter_idx,
                         lora_backend=lora_backend)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    table = (params["embed/tok"].T if cfg.tie_embeddings
             else params["lm_head"])
    return constrain_logits(unembed(h, table)[:, 0]), kv


def decode_step_paged(cfg: ModelConfig, params: dict, tokens: jax.Array,
                      kv_pages, page_table: jax.Array,
                      cache_len: jax.Array, lora=None, adapter_idx=None,
                      lora_backend: str = "einsum"):
    """One decode step over a paged KV pool (dense-family scan).

    tokens: (B, 1); kv_pages: (k_pages, v_pages) each (L, n_pages,
    page, Kh, Dh); page_table: (B, P) physical page ids per request;
    cache_len: (B,) valid lengths. Returns (logits (B, V), new kv_pages).

    The same ``lax.scan`` layer loop as ``decode_step``; only the KV
    residency differs — fixed-size pages indirected through the page
    table instead of a dense (B, max_len) slab, so HBM holds exactly the
    pages requests allocated (DESIGN §2).
    """
    x = constrain_btd(embed(tokens, params["embed/tok"]))
    cos, sin = _positions(cfg, tokens.shape, cache_len, None)
    k_pages, v_pages = kv_pages
    page = k_pages.shape[2]
    B = tokens.shape[0]
    # Write position of the new token, shared across layers.
    page_idx = page_table[jnp.arange(B), cache_len // page]
    page_off = cache_len % page
    attn_stack = _slice_group(params, "layers/")

    def body(carry, xs):
        h = constrain_boundary(carry)
        p = xs["p"]
        lr = xs.get("lora")
        h, (kp, vp) = _attn_paged(cfg, h, p, cos, sin, xs["kp"],
                                  xs["vp"], page_table, cache_len,
                                  page_idx, page_off, lr, adapter_idx,
                                  lora_backend)
        h = constrain_boundary(_mlp(cfg, h, p))
        return h, (kp, vp)

    xs = {"p": attn_stack, "kp": k_pages, "vp": v_pages}
    if lora is not None:
        xs["lora"] = lora
    h, (k_out, v_out) = jax.lax.scan(body, x, xs)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    table = (params["embed/tok"].T if cfg.tie_embeddings
             else params["lm_head"])
    return constrain_logits(unembed(h, table)[:, 0]), (k_out, v_out)


def prefill_paged(cfg: ModelConfig, params: dict, tokens: jax.Array,
                  kv_pages, page_table: jax.Array, start: jax.Array,
                  seq_len: jax.Array, lora=None, adapter_idx=None,
                  lora_backend: str = "einsum"):
    """Suffix prefill straight into paged KV (prefix-cache data plane).

    tokens: (B, S) right-padded *suffix* token ids — the part of each
    prompt not covered by cached prefix pages; start: (B,) absolute
    position of tokens[:, 0] (== the cached prefix length, 0 on a cache
    miss); seq_len: (B,) valid suffix lengths (>= 1 — the engine caps
    prefix matches at L-1 so the last prompt position always prefills);
    page_table: (B, P) physical pages covering positions 0..start+S-1,
    with cached-prefix pages mapped read-only by convention (suffix
    positions land in the request's private pages, so the scatter never
    writes a shared page).

    Per layer: project the suffix q/k/v (RoPE at the absolute offset),
    scatter K/V into the pages at positions start..start+seq_len-1
    (padding redirected to trash page 0), gather the request's whole
    page list back to (B, P*page, Kh, Dh), and run offset-causal
    attention over it — the cached prefix participates as keys without
    being recomputed. Returns (last-valid-position logits (B, V),
    kv_pages'). On a miss row (start == 0) this computes exactly what
    ``prefill`` + ``write_prompt_pages`` produce, so one code path
    serves hits and misses.
    """
    B, S = tokens.shape
    x = constrain_btd(embed(tokens, params["embed/tok"]))
    cos, sin = _positions(cfg, tokens.shape, start, None)
    k_pages, v_pages = kv_pages
    page = k_pages.shape[2]
    P = page_table.shape[1]
    pos = start[:, None] + jnp.arange(S)[None, :]            # (B, S) abs
    valid = jnp.arange(S)[None, :] < seq_len[:, None]        # (B, S)
    page_idx = jnp.take_along_axis(page_table, pos // page, axis=1)
    page_idx = jnp.where(valid, page_idx, 0)                 # pad → trash
    page_off = pos % page
    attn_stack = _slice_group(params, "layers/")

    def body(carry, xs):
        h0 = constrain_boundary(carry)
        p = xs["p"]
        lr = xs.get("lora")
        _, q, k, v = _qkv_proj(cfg, h0, p, cos, sin, lr, adapter_idx,
                               lora_backend=lora_backend)
        kp = xs["kp"].at[page_idx, page_off].set(k)
        vp = xs["vp"].at[page_idx, page_off].set(v)
        kf = kp[page_table].reshape(B, P * page, cfg.n_kv_heads,
                                    cfg.head_dim)
        vf = vp[page_table].reshape(B, P * page, cfg.n_kv_heads,
                                    cfg.head_dim)
        out = suffix_attention(q, kf, vf, pos)
        out = out.reshape(B, S, cfg.q_dim)
        h0 = _o_proj(cfg, h0, out, p, lr, adapter_idx,
                     lora_backend=lora_backend)
        h0 = constrain_boundary(_mlp(cfg, h0, p))
        return h0, (kp, vp)

    xs = {"p": attn_stack, "kp": k_pages, "vp": v_pages}
    if lora is not None:
        xs["lora"] = lora
    h, (k_out, v_out) = jax.lax.scan(body, x, xs)
    h_last = jnp.take_along_axis(
        h, jnp.reshape(seq_len - 1, (-1, 1, 1)).astype(jnp.int32), axis=1)
    h_last = rms_norm(h_last, params["final_norm"], cfg.norm_eps)
    table = (params["embed/tok"].T if cfg.tie_embeddings
             else params["lm_head"])
    return constrain_logits(unembed(h_last, table)[:, 0]), (k_out, v_out)


# ---------------------------------------- speculative decoding (draft–verify)
def verify(cfg: ModelConfig, params: dict, tokens: jax.Array,
           kv_caches, cache_len: jax.Array, seq_len: jax.Array | None = None,
           lora=None, adapter_idx=None, lora_backend: str = "einsum"):
    """Multi-token target forward over the dense KV slab.

    The verify half of speculative decoding: score ``S`` already-chosen
    tokens per row in one dispatch, returning logits for *every*
    position (``prefill``/``prefill_paged`` keep only the last). Row
    ``b``'s tokens sit at absolute positions ``cache_len[b] ..
    cache_len[b]+S-1``; their K/V is scattered into the slab at those
    positions (the same per-row-offset scatter ``_attn`` does for S==1)
    and attention runs offset-causal via ``suffix_attention``, so
    position j attends exactly the keys the single-step decode path
    would see — numerics match ``decode_step`` per position.

    tokens: (B, S); kv_caches: (k, v) each (L, B, Smax, Kh, Dh);
    cache_len: (B,) valid lengths; seq_len: optional (B,) valid token
    counts (< S positions are right-padding: their K/V writes are
    dropped and their logits are garbage the caller ignores — used for
    the draft-KV catch-up path). Returns (logits (B, S, V), kv').
    """
    B, S = tokens.shape
    x = constrain_btd(embed(tokens, params["embed/tok"]))
    cos, sin = _positions(cfg, tokens.shape, cache_len, None)
    pos = cache_len[:, None] + jnp.arange(S)[None, :]        # (B, S) abs
    Smax = kv_caches[0].shape[2]
    valid = pos < Smax
    if seq_len is not None:
        valid = valid & (jnp.arange(S)[None, :] < seq_len[:, None])
    idx = jnp.where(valid, pos, Smax)                        # OOB → dropped
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, S))
    attn_stack = _slice_group(params, "layers/")

    def body(carry, xs):
        h0 = constrain_boundary(carry)
        p = xs["p"]
        lr = xs.get("lora")
        _, q, k, v = _qkv_proj(cfg, h0, p, cos, sin, lr, adapter_idx,
                               lora_backend=lora_backend)
        kc = xs["k"].at[bidx, idx].set(k, mode="drop")
        vc = xs["v"].at[bidx, idx].set(v, mode="drop")
        out = suffix_attention(q, kc, vc, pos)
        out = out.reshape(B, S, cfg.q_dim)
        h0 = _o_proj(cfg, h0, out, p, lr, adapter_idx,
                     lora_backend=lora_backend)
        h0 = constrain_boundary(_mlp(cfg, h0, p))
        return h0, (kc, vc)

    xs = {"p": attn_stack, "k": kv_caches[0], "v": kv_caches[1]}
    if lora is not None:
        xs["lora"] = lora
    h, (k_out, v_out) = jax.lax.scan(body, x, xs)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    table = (params["embed/tok"].T if cfg.tie_embeddings
             else params["lm_head"])
    return constrain_logits(unembed(h, table)), (k_out, v_out)


def verify_paged(cfg: ModelConfig, params: dict, tokens: jax.Array,
                 kv_pages, page_table: jax.Array, cache_len: jax.Array,
                 seq_len: jax.Array | None = None, lora=None,
                 adapter_idx=None, lora_backend: str = "einsum"):
    """Multi-token target forward over paged KV — ``verify`` with the
    ``prefill_paged`` page-table scatter/gather: K/V lands in the
    request's private pages at positions ``cache_len..cache_len+S-1``
    (invalid/overflow positions redirect to trash page 0), the whole
    page list is gathered back and ``suffix_attention`` applies the
    per-row offset-causal mask. Returns all-position logits
    (B, S, V) + kv_pages'."""
    B, S = tokens.shape
    x = constrain_btd(embed(tokens, params["embed/tok"]))
    cos, sin = _positions(cfg, tokens.shape, cache_len, None)
    k_pages, v_pages = kv_pages
    page = k_pages.shape[2]
    P = page_table.shape[1]
    pos = cache_len[:, None] + jnp.arange(S)[None, :]        # (B, S) abs
    valid = pos < P * page
    if seq_len is not None:
        valid = valid & (jnp.arange(S)[None, :] < seq_len[:, None])
    page_idx = jnp.take_along_axis(page_table,
                                   jnp.minimum(pos // page, P - 1), axis=1)
    page_idx = jnp.where(valid, page_idx, 0)                 # pad → trash
    page_off = pos % page
    attn_stack = _slice_group(params, "layers/")

    def body(carry, xs):
        h0 = constrain_boundary(carry)
        p = xs["p"]
        lr = xs.get("lora")
        _, q, k, v = _qkv_proj(cfg, h0, p, cos, sin, lr, adapter_idx,
                               lora_backend=lora_backend)
        kp = xs["kp"].at[page_idx, page_off].set(k)
        vp = xs["vp"].at[page_idx, page_off].set(v)
        kf = kp[page_table].reshape(B, P * page, cfg.n_kv_heads,
                                    cfg.head_dim)
        vf = vp[page_table].reshape(B, P * page, cfg.n_kv_heads,
                                    cfg.head_dim)
        out = suffix_attention(q, kf, vf, pos)
        out = out.reshape(B, S, cfg.q_dim)
        h0 = _o_proj(cfg, h0, out, p, lr, adapter_idx,
                     lora_backend=lora_backend)
        h0 = constrain_boundary(_mlp(cfg, h0, p))
        return h0, (kp, vp)

    xs = {"p": attn_stack, "kp": k_pages, "vp": v_pages}
    if lora is not None:
        xs["lora"] = lora
    h, (k_out, v_out) = jax.lax.scan(body, x, xs)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    table = (params["embed/tok"].T if cfg.tie_embeddings
             else params["lm_head"])
    return constrain_logits(unembed(h, table)), (k_out, v_out)


def _spec_keys(seeds, positions, fold: int):
    """(seed, position, stream) keys — ``sample_tokens``' base key with
    the spec stream tag folded in. positions (B,) or (B, S)."""
    def one(s, p):
        k = jax.random.fold_in(jax.random.PRNGKey(s), p)
        return jax.random.fold_in(k, fold)
    if positions.ndim == 1:
        return jax.vmap(one)(seeds.astype(jnp.uint32),
                             positions.astype(jnp.uint32))
    return jax.vmap(jax.vmap(one, in_axes=(None, 0)))(
        seeds.astype(jnp.uint32), positions.astype(jnp.uint32))


def _spec_filtered(logits, temperature, top_k, top_p):
    """temperature/top-k/top-p masking identical to ``sample_tokens``,
    plus the renormalized probabilities of the kept set (what the
    rejection rule needs). logits (..., V); params (...) leading-shaped.
    Returns (masked scaled logits, filtered probs)."""
    V = logits.shape[-1]
    scaled = logits / jnp.maximum(temperature, 1e-6)[..., None]
    order = jnp.argsort(scaled, axis=-1)[..., ::-1]
    ranks = jnp.argsort(order, axis=-1)
    keep_k = ranks < jnp.where(top_k > 0, top_k, V)[..., None]
    probs = jax.nn.softmax(scaled, axis=-1)
    sorted_p = jnp.take_along_axis(probs, order, axis=-1)
    cum = jnp.cumsum(sorted_p, axis=-1)
    keep_p_sorted = (cum - sorted_p) < top_p[..., None]
    keep_p = jnp.take_along_axis(keep_p_sorted, ranks, axis=-1)
    masked = jnp.where(keep_k & keep_p, scaled, -jnp.inf)
    return masked, jax.nn.softmax(masked, axis=-1)


def _draft_propose(logits, temperature, top_k, top_p, seeds, positions):
    """Draft proposal for one chained draft step: greedy rows take the
    draft argmax, stochastic rows Gumbel-sample the filtered draft
    distribution from the SPEC_DRAFT stream. Returns (tokens (B,),
    filtered draft probs (B, V) — the ``q`` of the rejection rule)."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    masked, qprobs = _spec_filtered(logits, temperature, top_k, top_p)
    keys = _spec_keys(seeds, positions, SPEC_DRAFT_FOLD)
    gumbel = jax.vmap(lambda k: jax.random.gumbel(k, (V,)))(keys)
    sampled = jnp.argmax(masked + gumbel, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy), qprobs


def _spec_decode_scan(draft_one, verify_multi, tokens, cache_len, active,
                      positions, kv, draft_kv, budget, stop_ids,
                      temperature, top_k, top_p, seeds, max_ctx: int,
                      n_rounds: int, spec_k: int, all_greedy: bool):
    """Fused draft–verify decode: ``n_rounds`` speculative rounds on
    device, emitting up to ``spec_k + 1`` tokens per row per round.

    Each round, per row: (1) the draft model runs ``spec_k + 1`` chained
    single-token steps on its own KV (one past the last proposal so the
    draft cache holds every accepted token's entry when all drafts
    land), proposing ``d_1..d_spec_k``; (2) the target scores
    ``[t0, d_1..d_spec_k]`` in ONE multi-token ``verify`` dispatch,
    writing target KV for all spec_k+1 positions; (3) the accept mask,
    correction/bonus token, and per-row cache_len rollback are computed
    on device — logits never leave the device. Greedy rows accept
    ``d_j`` iff it equals the target argmax given the accepted prefix,
    and every emitted token *is* a target argmax, so greedy output is
    bit-identical to ``_fused_decode_scan``; stochastic rows use
    rejection sampling (accept w.p. ``min(1, p/q)``, resample the
    residual ``max(p-q, 0)`` on reject) with every draw keyed on
    (seed, position) spec streams, so replay/squash re-execution is
    deterministic and each emitted token is exactly target-distributed.

    Rollback: both caches advance by the per-row emitted count only —
    entries written past it (rejected drafts) are garbage that the next
    round's writes at the same positions overwrite before attention can
    see them (the same argument the non-spec loop makes for done rows).
    Per-token finish semantics (budget / stop id / context bound) are
    replayed emission-by-emission inside the round, verbatim from
    ``_fused_decode_scan``, so a row that finishes mid-round stops
    emitting at the identical token.

    draft_one: (tokens (B,1), draft_kv, clen) -> (logits (B,V), kv').
    verify_multi: (tokens (B,S), kv, clen) -> (logits (B,S,V), kv').
    Returns ((tokens', kv', draft_kv', cache_len', active', positions'),
             toks (n_rounds*(spec_k+1), B), emits (same), n_acc
             (n_rounds, B) accepted-draft counts for the meter).
    """
    K = spec_k
    B = tokens.shape[0]

    def round_body(carry, _):
        tokens, kv, dkv, cache_len, active, positions = carry

        def dstep(dc, j):
            tok, dkv = dc
            dlogits, dkv = draft_one(tok, dkv, cache_len + j)
            if all_greedy:
                nxt = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
                out = (nxt,)
            else:
                nxt, qp = _draft_propose(dlogits, temperature, top_k,
                                         top_p, seeds, positions + j)
                out = (nxt, qp)
            return (nxt[:, None], dkv), out

        (_, dkv), douts = jax.lax.scan(dstep, (tokens, dkv),
                                       jnp.arange(K + 1))
        d_check = douts[0][:K].T                 # (B, K) = d_1..d_K
        vt = jnp.concatenate([tokens, d_check], axis=1)      # (B, K+1)
        vlogits, kv = verify_multi(vt, kv, cache_len)        # (B, K+1, V)

        tgt = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)
        acc = d_check == tgt[:, :K]              # greedy accept (B, K)
        if not all_greedy:
            qprobs = jnp.swapaxes(douts[1][:K], 0, 1)        # (B, K, V)
            _, pprobs = _spec_filtered(
                vlogits[:, :K],
                jnp.broadcast_to(temperature[:, None], (B, K)),
                jnp.broadcast_to(top_k[:, None], (B, K)),
                jnp.broadcast_to(top_p[:, None], (B, K)))
            p_d = jnp.take_along_axis(pprobs, d_check[..., None],
                                      axis=-1)[..., 0]
            q_d = jnp.take_along_axis(qprobs, d_check[..., None],
                                      axis=-1)[..., 0]
            pos_mat = positions[:, None] + jnp.arange(K)[None, :]
            u = jax.vmap(jax.vmap(jax.random.uniform))(
                _spec_keys(seeds, pos_mat, SPEC_ACCEPT_FOLD))
            # u < min(1, p/q)  ⇔  u*q < p (q > 0 on the proposal support)
            acc_s = u * jnp.maximum(q_d, 1e-30) < p_d
            acc = jnp.where((temperature > 0.0)[:, None], acc_s, acc)
        n_acc = jnp.cumprod(acc.astype(jnp.int32), axis=1).sum(axis=1)

        # Correction (first reject) / bonus (all accepted) token.
        corr = tgt[jnp.arange(B), n_acc]         # argmax(L_n)
        if not all_greedy:
            V = vlogits.shape[-1]
            resid = jnp.maximum(pprobs - qprobs, 0.0)
            rsum = resid.sum(axis=-1, keepdims=True)
            resid = jnp.where(rsum > 0, resid / jnp.maximum(rsum, 1e-30),
                              pprobs)
            rg = jax.vmap(jax.vmap(lambda k: jax.random.gumbel(k, (V,))))(
                _spec_keys(seeds, pos_mat, SPEC_RESIDUAL_FOLD))
            resid_tok = jnp.argmax(
                jnp.log(jnp.maximum(resid, 1e-38)) + rg,
                axis=-1).astype(jnp.int32)       # (B, K)
            bonus = sample_tokens(vlogits[:, K], temperature, top_k,
                                  top_p, seeds, positions + K)
            corr_s = jnp.where(
                n_acc < K,
                resid_tok[jnp.arange(B), jnp.minimum(n_acc, K - 1)], bonus)
            corr = jnp.where(temperature > 0.0, corr_s, corr)

        # Emission slot j holds d_{j+1} while accepted, else the
        # correction/bonus at slot n_acc (slots past it are masked).
        jj = jnp.arange(K + 1)[None, :]
        e = jnp.where(jj < n_acc[:, None],
                      jnp.pad(d_check, ((0, 0), (0, 1))), corr[:, None])

        # Per-emission finish conditions, verbatim from
        # _fused_decode_scan's done-mask (budget / stop / context).
        m = active
        toks_list, emits_list = [], []
        for j in range(K + 1):
            ej = e[:, j]
            emit_j = m & (jj[0, j] <= n_acc)
            new_pos = positions + j + 1
            new_len = cache_len + j + 1
            hit_stop = (ej[:, None] == stop_ids).any(axis=-1)
            done_j = emit_j & ((new_pos >= budget) | hit_stop
                               | (new_len + 1 >= max_ctx - 1))
            m = m & ~done_j
            toks_list.append(ej)
            emits_list.append(emit_j)
        toks_r = jnp.stack(toks_list)            # (K+1, B)
        emits_r = jnp.stack(emits_list)          # (K+1, B)
        cnt = emits_r.astype(jnp.int32).sum(axis=0)
        new_tok = e[jnp.arange(B), jnp.maximum(cnt - 1, 0)]
        tokens = jnp.where(cnt > 0, new_tok, tokens[:, 0])[:, None]
        carry = (tokens, kv, dkv, cache_len + cnt, m, positions + cnt)
        return carry, (toks_r, emits_r, jnp.where(active, n_acc, 0))

    init = (tokens, kv, draft_kv, cache_len, active, positions)
    carry, (toks, emits, accs) = jax.lax.scan(round_body, init, None,
                                              length=n_rounds)
    # Flatten rounds × emission slots to the step-major (n, B) block
    # the engine drain walks, like the non-spec scan's output.
    toks = toks.reshape(n_rounds * (K + 1), B)
    emits = emits.reshape(n_rounds * (K + 1), B)
    return carry, toks, emits, accs


def decode_spec_fused(cfg: ModelConfig, params: dict,
                      draft_cfg: ModelConfig, draft_params: dict,
                      tokens: jax.Array, kv_caches, draft_kv,
                      cache_len: jax.Array, active: jax.Array,
                      positions: jax.Array, budget: jax.Array,
                      stop_ids: jax.Array, temperature: jax.Array,
                      top_k: jax.Array, top_p: jax.Array,
                      seeds: jax.Array, *, spec_k: int, n_rounds: int,
                      all_greedy: bool, max_ctx: int, lora=None,
                      adapter_idx=None, lora_backend: str = "einsum"):
    """Speculative fused decode over the dense KV slab: base-weights
    draft (no LoRA — the adapters ride along at verify time only),
    multi-token target ``verify``, on-device accept/rollback."""

    def draft_one(tok, dkv, clen):
        return decode_step(draft_cfg, draft_params, tok, dkv, clen)

    def verify_multi(toks, kv, clen):
        return verify(cfg, params, toks, kv, clen, lora=lora,
                      adapter_idx=adapter_idx, lora_backend=lora_backend)

    return _spec_decode_scan(draft_one, verify_multi, tokens, cache_len,
                             active, positions, kv_caches, draft_kv,
                             budget, stop_ids, temperature, top_k, top_p,
                             seeds, max_ctx, n_rounds, spec_k, all_greedy)


def decode_spec_fused_paged(cfg: ModelConfig, params: dict,
                            draft_cfg: ModelConfig, draft_params: dict,
                            tokens: jax.Array, kv_pages,
                            page_table: jax.Array, draft_kv,
                            cache_len: jax.Array, active: jax.Array,
                            positions: jax.Array, budget: jax.Array,
                            stop_ids: jax.Array, temperature: jax.Array,
                            top_k: jax.Array, top_p: jax.Array,
                            seeds: jax.Array, *, spec_k: int,
                            n_rounds: int, all_greedy: bool, max_ctx: int,
                            lora=None, adapter_idx=None,
                            lora_backend: str = "einsum"):
    """Speculative fused decode with the target on paged KV (the draft
    keeps a dense slab — it is small and adapter-free). The engine
    pre-allocates pages covering every write a round can make
    (``cache_len + spec_k + 1``) and shrinks back after readback."""

    def draft_one(tok, dkv, clen):
        return decode_step(draft_cfg, draft_params, tok, dkv, clen)

    def verify_multi(toks, kv, clen):
        return verify_paged(cfg, params, toks, kv, page_table, clen,
                            lora=lora, adapter_idx=adapter_idx,
                            lora_backend=lora_backend)

    return _spec_decode_scan(draft_one, verify_multi, tokens, cache_len,
                             active, positions, kv_pages, draft_kv,
                             budget, stop_ids, temperature, top_k, top_p,
                             seeds, max_ctx, n_rounds, spec_k, all_greedy)
