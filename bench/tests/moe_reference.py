"""Shapes and counts of a Qwen3-MoE decoder, for the harness's tests:
every layer MoE (``decoder_sparse_step`` 1), no shared expert, per-head
q/k RMSNorm, untied head. It shows a second family reaching the harness
through files of its own; a served MoE cell needs ``logits_at`` beside
these, in ``bench/references``."""


def weight_shapes(d) -> dict[str, tuple]:
    """The weights the harness makes, under the program's flat names."""
    L, D, E, F = d.n_layers, d.d_model, d.n_experts, d.d_ff_expert
    return {"embed/tok": (d.vocab, D), "final_norm": (D,),
            "lm_head": (D, d.vocab),
            "layers/attn_norm": (L, D), "layers/q": (L, D, d.q_dim),
            "layers/k": (L, D, d.kv_dim), "layers/v": (L, D, d.kv_dim),
            "layers/o": (L, d.q_dim, D), "layers/q_norm": (L, d.head_dim),
            "layers/k_norm": (L, d.head_dim), "moe/norm": (L, D),
            "moe/router": (L, D, E), "moe/w_gate": (L, E, D, F),
            "moe/w_up": (L, E, D, F), "moe/w_down": (L, E, F, D)}


def matmul_params(d, head: bool) -> int:
    """Weights a token multiplies through: q, k, v, o, the router over
    every expert, the ``top_k`` experts it is sent to, and the head."""
    if d.moe_every != 1 or d.tied:
        raise ValueError("every layer MoE and an untied head only")
    attn = d.d_model * (d.q_dim + 2 * d.kv_dim) + d.q_dim * d.d_model
    experts = d.top_k * 3 * d.d_model * d.d_ff_expert
    per_layer = attn + d.d_model * d.n_experts + experts
    return d.n_layers * per_layer + (d.d_model * d.vocab if head else 0)
