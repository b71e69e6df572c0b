"""Activation sharding constraints (GSPMD propagation anchors).

Sharding propagation through scan-over-layers + remat reliably loses
the batch sharding of activations (the recompute path resolves to
replicated), which silently turns a 16-way batch-parallel program into
a replicated one. The launcher/dry-run installs the mesh's batch axes
here; the model code calls ``constrain_*`` at layer boundaries, which
is a no-op when nothing is installed (tests, single-device engine).
"""
from __future__ import annotations

import contextlib

import jax
from jax.sharding import PartitionSpec as P

_BATCH_AXES: tuple | None = None
_MODEL_AXIS: str | None = None
_EXPERT_AXIS: str | None = None
_MODEL_SIZE: int = 1
_SEQ_SHARD: bool = False
_MOE_TOKEN_PARALLEL: bool = False
_MESH = None
_EXACT: bool = False


@contextlib.contextmanager
def activation_sharding(batch_axes: tuple, model_axis: str = "model",
                        expert_axis: str = "data", model_size: int = 1,
                        seq_shard_boundary: bool = False,
                        moe_token_parallel: bool = False,
                        mesh=None, exact_reductions: bool = False):
    """``seq_shard_boundary``: shard the inter-layer residual stream's
    sequence dim over the model axis (Megatron-style sequence
    parallelism). This is what bounds remat memory: the saved per-layer
    carries shrink by the TP degree (25 GB -> 1.6 GB per chip for a
    14B model at 64k tokens/chip); XLA re-gathers S where attention/MLP
    need it.

    ``exact_reductions`` (the serving engine's mode): constrain
    activations so no einsum ever contracts over a sharded dim —
    FFN hidden and merged attention heads are gathered *before* their
    down/out projections instead of row-parallel psum'd after. Every
    FP reduction then keeps the single-device order, making sharded
    inference token-identical to mesh=1 (DESIGN §4)."""
    global _BATCH_AXES, _MODEL_AXIS, _EXPERT_AXIS, _MODEL_SIZE, \
        _SEQ_SHARD, _MOE_TOKEN_PARALLEL, _MESH, _EXACT
    prev = (_BATCH_AXES, _MODEL_AXIS, _EXPERT_AXIS, _MODEL_SIZE,
            _SEQ_SHARD, _MOE_TOKEN_PARALLEL, _MESH, _EXACT)
    _BATCH_AXES, _MODEL_AXIS, _EXPERT_AXIS = (batch_axes, model_axis,
                                              expert_axis)
    _MODEL_SIZE, _SEQ_SHARD = model_size, seq_shard_boundary
    _MOE_TOKEN_PARALLEL = moe_token_parallel
    _MESH = mesh
    _EXACT = exact_reductions
    try:
        yield
    finally:
        (_BATCH_AXES, _MODEL_AXIS, _EXPERT_AXIS, _MODEL_SIZE,
         _SEQ_SHARD, _MOE_TOKEN_PARALLEL, _MESH, _EXACT) = prev


def installed_mesh():
    """The mesh a serving engine installed for this trace, or None.
    Pallas kernels read it: GSPMD cannot partition a Mosaic call, so
    under a mesh they run inside ``shard_map`` (see kernels/ops.py)."""
    return _MESH


def moe_a2a_mesh():
    """(mesh, expert_axis) when the shard_map a2a MoE should be used
    (inference under an installed mesh), else None."""
    if _MOE_TOKEN_PARALLEL and _MESH is not None:
        return _MESH, _EXPERT_AXIS
    return None


def _wsc(x, spec):
    if _BATCH_AXES is None:
        return x
    if _MESH is not None:
        # Mesh installed explicitly (the serving engine does not run
        # its jits under a ``with mesh:`` scope): resolve the raw spec
        # to a NamedSharding, fitted to this value's shape so uneven
        # dims (a B=1 prefill bucket on a 2-way data axis) degrade to
        # replicated instead of erroring.
        from jax.sharding import NamedSharding
        from repro.distributed.sharding import fit_spec
        fitted = fit_spec(x.shape, spec, _MESH)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(_MESH, fitted))
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_btd(x):
    """(B, S, D) activations: batch over data axes."""
    return _wsc(x, P(_BATCH_AXES, None, None))


def constrain_boundary(x):
    """Inter-layer residual (B, S, D): batch over data; sequence over
    the model axis when sequence-parallel boundaries are enabled."""
    if (_SEQ_SHARD and x.ndim == 3 and _MODEL_SIZE > 1
            and x.shape[1] % _MODEL_SIZE == 0):
        return _wsc(x, P(_BATCH_AXES, _MODEL_AXIS, None))
    return _wsc(x, P(_BATCH_AXES, None, None))


def constrain_bd(x):
    """(B, D) decode activations."""
    return _wsc(x, P(_BATCH_AXES, None))


def constrain_logits(x):
    """(B, S, V) or (B, V) logits: batch over data, vocab over model.

    Exact mode gathers the vocab dim instead: sampling consumes these
    (sort / cumsum / top-k over V), and a model-sharded vocab turns
    those into distributed scans with a different FP order than
    single-device — the unembed einsum still runs column-parallel, the
    all-gather after it is elementwise."""
    v = None if _EXACT else _MODEL_AXIS
    if x.ndim == 2:
        return _wsc(x, P(_BATCH_AXES, v))
    return _wsc(x, P(_BATCH_AXES, None, v))


def constrain_heads(x):
    """Attention head tensors — (B, S, H, D) q/k/v or (B, S, Kh, G, D)
    grouped query: heads over the model axis (the serving fused scan
    otherwise replicates the per-head compute, DESIGN §4)."""
    if x.ndim == 4:
        return _wsc(x, P(_BATCH_AXES, None, _MODEL_AXIS, None))
    if x.ndim == 5:
        return _wsc(x, P(_BATCH_AXES, None, _MODEL_AXIS, None, None))
    return x


def constrain_ffn_hidden(x):
    """(B, S, F) MLP hidden: F over model — matches the gate/up column
    sharding so SwiGLU runs fully sharded until the down projection.
    Exact mode gathers F here instead, so the down projection contracts
    an unsharded dim in single-device FP order (no psum of partials)."""
    if _EXACT:
        return _wsc(x, P(_BATCH_AXES, None, None))
    return _wsc(x, P(_BATCH_AXES, None, _MODEL_AXIS))


def constrain_attn_merged(x):
    """(B, S, q_dim) attention output after heads merge, feeding the o
    projection. Exact mode only: gather the head shards so the o-proj
    contraction runs unsharded (see ``exact_reductions``); otherwise a
    no-op — training relies on GSPMD propagation (row-parallel o)."""
    if _EXACT:
        return _wsc(x, P(_BATCH_AXES, None, None))
    return x


def constrain_residual(x):
    """(B, S, D) residual stream *mid-layer* (between the attention
    residual and the MLP norm). Exact mode only: the column-parallel
    o projection leaves D model-sharded, and the next rms_norm would
    psum its mean-square over the shards — a different FP reduction
    order per mesh shape. Gathering here keeps every norm reduction in
    single-device order; outside exact mode propagation stands."""
    if _EXACT:
        return _wsc(x, P(_BATCH_AXES, None, None))
    return x


def constrain_ssm_channels(x):
    """(B, S, C) SSM activations: channels over model, S *full* — the
    time recurrence is sequential in S, so sequence sharding inside the
    mixer forces pathological resharding (observed: 48 GB/layer of
    collectives on falcon train before this anchor)."""
    return _wsc(x, P(_BATCH_AXES, None, _MODEL_AXIS))


def constrain_ssm_bthp(x):
    """SSM activations (B, T, H, P): heads over the model axis."""
    return _wsc(x, P(_BATCH_AXES, None, _MODEL_AXIS, None))


def constrain_ssm_bth(x):
    """(B, T, H) per-head scalars: heads over the model axis."""
    return _wsc(x, P(_BATCH_AXES, None, _MODEL_AXIS))


def constrain_moe_groups(x):
    """Group-major MoE tensors (G, ...): groups follow batch sharding
    (the (B,S)->(G,Tg) reshape is layout-compatible, but GSPMD tends to
    replicate through it without an anchor). Skipped under sequence-
    parallel boundaries: there S carries the model-axis sharding and a
    batch-only anchor would force an S all-gather per layer."""
    if _SEQ_SHARD:
        return x
    return _wsc(x, P(_BATCH_AXES, *([None] * (x.ndim - 1))))


def constrain_moe_local(x):
    """Pre-all-to-all bucket tensor (G, E, C, D): still group-sharded
    (local to each data shard). Forcing this anchor *before* the
    expert-sharded anchor turns the reshard into a clean all-to-all —
    fused into the dispatch einsum, GSPMD falls back to all-gathering
    operands (measured 14.9 GB/layer vs ~0.5 GB for the a2a)."""
    return _wsc(x, P(_BATCH_AXES, None, None, None))


def constrain_expert_ecd(x):
    """MoE dispatch buckets (G, E, C, D): experts over the expert axis
    (the group dim gives up its batch sharding here — this reshard is
    the MoE all-to-all). In token-parallel mode (inference) the bucket
    dim C also shards over the model axis — each chip runs its experts
    on 1/TP of their tokens with *unsharded* expert FFN weights, so no
    down-projection psum exists at all (it was 10 GB wire/layer on
    qwen3-moe prefill)."""
    if _MOE_TOKEN_PARALLEL:
        return _wsc(x, P(None, _EXPERT_AXIS, _MODEL_AXIS, None))
    return _wsc(x, P(None, _EXPERT_AXIS, None, None))


def constrain_expert_ecf(x):
    """MoE hidden (G, E, C, F): experts over data; hidden over model
    (TP mode) or tokens over model (token-parallel inference)."""
    if _MOE_TOKEN_PARALLEL:
        return _wsc(x, P(None, _EXPERT_AXIS, _MODEL_AXIS, None))
    return _wsc(x, P(None, _EXPERT_AXIS, None, _MODEL_AXIS))
