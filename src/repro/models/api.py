"""Family dispatcher: one uniform entry surface over all model families.

    api.init_params(cfg, key)
    api.train_loss(cfg, params, **batch)          # batch from input specs
    api.prefill(cfg, params, **inputs)
    api.decode_step(cfg, params, **inputs)

The launcher / dry-run / engine talk to this module only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import encdec, hybrid, lm, ssm_lm
from .base import Family, ModelConfig
from .lm import init_params  # shared: param_shapes covers every family
from .lm import sample_tokens  # family-agnostic: operates on logits
from .lm import write_prompt_pages  # the paged pool's layout


def _mod(cfg: ModelConfig):
    if cfg.family == Family.SSM:
        return ssm_lm
    if cfg.family == Family.HYBRID:
        return hybrid
    if cfg.family == Family.ENCDEC:
        return encdec
    return lm


def forward(cfg, params, tokens, **kw):
    return _mod(cfg).forward(cfg, params, tokens, **kw)


def train_loss(cfg, params, tokens, labels, **kw):
    return _mod(cfg).train_loss(cfg, params, tokens, labels, **kw)


def prefill(cfg, params, tokens, **kw):
    return _mod(cfg).prefill(cfg, params, tokens, **kw)


def decode_step(cfg, params, tokens, state, cache_len=None, **kw):
    m = _mod(cfg)
    if cfg.family in (Family.DENSE, Family.MOE, Family.VLM):
        return m.decode_step(cfg, params, tokens, state, cache_len, **kw)
    if cfg.family == Family.SSM:
        return m.decode_step(cfg, params, tokens, state, **kw)
    return m.decode_step(cfg, params, tokens, state, cache_len, **kw)


# ---------------------------------------------- fused decode hot loop
def supports_fused(cfg: ModelConfig) -> bool:
    """Families servable by the fused decode+sample horizon loop.

    Anything whose ``decode_step`` is the (tokens, kv, cache_len)
    dense-KV shape: dense decoder LMs and MoE (same KV path, superstep
    scan). VLM needs per-step M-RoPE positions the engine does not
    thread; SSM/hybrid/enc-dec carry non-KV state shapes. Engines fall
    back to the two-dispatch step loop for unsupported families.
    """
    return cfg.family in (Family.DENSE, Family.MOE)


def decode_fused(cfg, params, tokens, kv_caches, cache_len, active,
                 positions, budget, stop_ids, temperature, top_k, top_p,
                 seeds, **kw):
    """K fused decode+sample steps over dense KV in one dispatch
    (``lm.decode_fused``); see ``_fused_decode_scan`` for semantics."""
    if not supports_fused(cfg):
        raise NotImplementedError(
            f"fused decode unsupported for family {cfg.family}")
    return lm.decode_fused(cfg, params, tokens, kv_caches, cache_len,
                           active, positions, budget, stop_ids,
                           temperature, top_k, top_p, seeds, **kw)


def decode_fused_paged(cfg, params, tokens, kv_pages, page_table,
                       cache_len, active, positions, budget, stop_ids,
                       temperature, top_k, top_p, seeds, **kw):
    """K fused decode+sample steps over paged KV in one dispatch."""
    if not (supports_fused(cfg) and supports_paged(cfg)):
        raise NotImplementedError(
            f"fused paged decode unsupported for family {cfg.family}")
    return lm.decode_fused_paged(cfg, params, tokens, kv_pages,
                                 page_table, cache_len, active,
                                 positions, budget, stop_ids,
                                 temperature, top_k, top_p, seeds, **kw)


# --------------------------------------------- speculative draft–verify
def supports_spec_draft(cfg: ModelConfig) -> bool:
    """Families usable as the *draft* model of speculative decoding.

    The draft runs chained single-token ``lm.decode_step``s on a dense
    KV slab inside the speculative scan, so only dense decoder LMs
    qualify for now (MoE drafting is pointless — the draft should be
    cheap; SSM/hybrid carry non-KV state the scan does not thread).
    The *target* additionally needs ``supports_fused``.
    """
    return cfg.family == Family.DENSE


def verify(cfg, params, tokens, kv_caches, cache_len, **kw):
    """Multi-token target forward over dense KV returning all-position
    logits (B, S, V) — the verify half of speculative decoding."""
    if not supports_fused(cfg):
        raise NotImplementedError(
            f"verify forward unsupported for family {cfg.family}")
    return lm.verify(cfg, params, tokens, kv_caches, cache_len, **kw)


def verify_paged(cfg, params, tokens, kv_pages, page_table, cache_len,
                 **kw):
    """Multi-token target forward over paged KV (all-position logits)."""
    if not supports_paged(cfg):
        raise NotImplementedError(
            f"paged verify unsupported for family {cfg.family}")
    return lm.verify_paged(cfg, params, tokens, kv_pages, page_table,
                           cache_len, **kw)


def decode_spec_fused(cfg, params, draft_cfg, draft_params, tokens,
                      kv_caches, draft_kv, cache_len, active, positions,
                      budget, stop_ids, temperature, top_k, top_p, seeds,
                      **kw):
    """Fused speculative decode over dense KV: draft–verify rounds with
    on-device acceptance (``lm._spec_decode_scan``)."""
    if not supports_fused(cfg):
        raise NotImplementedError(
            f"speculative decode unsupported for target family "
            f"{cfg.family}")
    if not supports_spec_draft(draft_cfg):
        raise NotImplementedError(
            f"speculative draft unsupported for family {draft_cfg.family}")
    return lm.decode_spec_fused(cfg, params, draft_cfg, draft_params,
                                tokens, kv_caches, draft_kv, cache_len,
                                active, positions, budget, stop_ids,
                                temperature, top_k, top_p, seeds, **kw)


def decode_spec_fused_paged(cfg, params, draft_cfg, draft_params, tokens,
                            kv_pages, page_table, draft_kv, cache_len,
                            active, positions, budget, stop_ids,
                            temperature, top_k, top_p, seeds, **kw):
    """Fused speculative decode with the target on paged KV."""
    if not (supports_fused(cfg) and supports_paged(cfg)):
        raise NotImplementedError(
            f"speculative paged decode unsupported for target family "
            f"{cfg.family}")
    if not supports_spec_draft(draft_cfg):
        raise NotImplementedError(
            f"speculative draft unsupported for family {draft_cfg.family}")
    return lm.decode_spec_fused_paged(cfg, params, draft_cfg,
                                      draft_params, tokens, kv_pages,
                                      page_table, draft_kv, cache_len,
                                      active, positions, budget, stop_ids,
                                      temperature, top_k, top_p, seeds,
                                      **kw)


# ------------------------------------------------------- paged serving
def supports_paged(cfg: ModelConfig) -> bool:
    """Families whose decode can run over a paged KV pool.

    Dense decoder LMs only for now: MoE decode shares the dense KV path
    but scans supersteps (paged xs plumbing not wired), VLM needs M-RoPE
    positions, SSM/hybrid/enc-dec carry non-KV state. Engines fall back
    to the dense slab for unsupported families.
    """
    return cfg.family == Family.DENSE


def init_paged_serve_state(cfg: ModelConfig, n_pages: int, page_size: int,
                           dtype=jnp.bfloat16):
    if not supports_paged(cfg):
        raise NotImplementedError(
            f"paged KV unsupported for family {cfg.family}")
    return lm.make_paged_kv(cfg, n_pages, page_size, dtype)


def decode_step_paged(cfg, params, tokens, kv_pages, page_table,
                      cache_len, **kw):
    if not supports_paged(cfg):
        raise NotImplementedError(
            f"paged KV unsupported for family {cfg.family}")
    return lm.decode_step_paged(cfg, params, tokens, kv_pages,
                                page_table, cache_len, **kw)


def prefill_paged(cfg, params, tokens, kv_pages, page_table, start,
                  seq_len, **kw):
    """Suffix prefill into paged KV (prefix-cache hits skip the cached
    prefix; see lm.prefill_paged). Paged families only."""
    if not supports_paged(cfg):
        raise NotImplementedError(
            f"paged KV unsupported for family {cfg.family}")
    return lm.prefill_paged(cfg, params, tokens, kv_pages, page_table,
                            start, seq_len, **kw)


def init_serve_state(cfg: ModelConfig, batch: int, max_len: int,
                     dtype=jnp.bfloat16):
    if cfg.family == Family.SSM:
        return ssm_lm.init_serve_state(cfg, batch, dtype)
    if cfg.family == Family.HYBRID:
        return hybrid.init_serve_state(cfg, batch, max_len, dtype)
    if cfg.family == Family.ENCDEC:
        k = jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads,
                       cfg.head_dim), dtype)
        kx = jnp.zeros((cfg.n_layers, batch, cfg.enc_ctx, cfg.n_kv_heads,
                        cfg.head_dim), dtype)
        return ((k, k), (kx, kx))
    return lm.make_kv_caches(cfg, batch, max_len, dtype)
