"""Operations and bytes the algorithm needs, from a configuration's
sizes and the lengths actually served. Used by the per-layer readers;
never measured, only counted.

Conventions: a multiply-add is 2 operations; attention counts QK^T and
PV over the keys each query may see (causal); LoRA counts each
adapter's true rank, not the arena's padded ``r_max``; KV is float32
(4 bytes) and adapter slots bfloat16 (2 bytes), as the engine holds them.
"""
from __future__ import annotations

from .model import TARGETS, Dims, reference

KV_BYTES = 4
ADAPTER_BYTES = 2
ACT_BYTES = 4


def dense_params(d: Dims, head: bool = True) -> int:
    """``matmul_params`` of ``d``'s reference, by the name tests pin."""
    return reference(d).matmul_params(d, head)


def lora_flops_per_token(d: Dims, rank: int) -> int:
    return 2 * d.n_layers * sum(rank * sum(d.proj(t)) for t in TARGETS)


def attn_flops(d: Dims, n_keys: int) -> int:
    """QK^T and PV of one query over ``n_keys`` keys, all layers."""
    return 4 * d.n_layers * d.n_heads * d.head_dim * n_keys


def prefill_flops(d: Dims, start: int, n: int, rank: int) -> int:
    """A suffix of ``n`` prompt tokens at positions start..start+n-1:
    matmuls and LoRA per token, causal attention, and the head at the
    last position only (the engine needs only its logits)."""
    keys = n * start + n * (n + 1) // 2
    return (2 * reference(d).matmul_params(d, False) * n
            + lora_flops_per_token(d, rank) * n
            + attn_flops(d, 1) * keys + 2 * d.d_model * d.vocab)


def decode_flops(d: Dims, n_keys: int, rank: int) -> int:
    """One decoded token that attends over ``n_keys`` keys."""
    return (2 * reference(d).matmul_params(d, True)
            + lora_flops_per_token(d, rank) + attn_flops(d, n_keys))


def paged_attention_cost(d: Dims, n_keys: int) -> tuple[int, int]:
    """(operations, bytes) of one row's decode attention, all layers:
    the K and V pages up to the row's actual length, its query and
    its output."""
    flops = attn_flops(d, n_keys)
    kv = 2 * d.n_layers * n_keys * d.kv_dim * KV_BYTES
    qo = 2 * d.n_layers * d.q_dim * ACT_BYTES
    return flops, kv + qo


def bgmv_cost(d: Dims, rank: int) -> tuple[int, int]:
    """(operations, bytes) of one row's decode LoRA, all layers and
    targets: A and B at the adapter's true rank, its input and output."""
    flops = bytes_ = 0
    for t in TARGETS:
        din, dout = d.proj(t)
        flops += 2 * rank * (din + dout)
        bytes_ += rank * (din + dout) * ADAPTER_BYTES \
            + (din + dout) * ACT_BYTES
    return d.n_layers * flops, d.n_layers * bytes_


def roofline_seconds(flops: float, bytes_: float, peak: dict
                     ) -> tuple[float, str]:
    """Least time on the chip, and which bound sets it."""
    tf = flops / peak["flops_per_s"]
    tb = bytes_ / peak["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "bandwidth")
