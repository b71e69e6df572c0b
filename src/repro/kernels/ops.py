"""jit'd public wrappers for the Pallas kernels + the LoRA dispatch layer.

``use_kernel`` resolution: on TPU backends the Pallas path runs
natively; elsewhere (this CPU container) it runs in interpret mode when
``interpret_ok`` — tests force that; the serving engine on CPU prefers
the jnp reference path for speed. Wrappers also handle padding to the
kernels' tile-alignment requirements so callers stay shape-agnostic.

The LoRA *dispatch layer* (``resolve_lora_backend`` /
``lora_delta_kernel``) is what the model data plane calls
(models/lora_apply.py): decode steps (S == 1) route per-token LoRA
through the bgmv kernel, batched prefill (S > 1, one contiguous run of
S tokens per request) through the sgmv kernel with tile-aligned
segments, and the pure-jnp einsum stays available as the CPU/oracle
fallback. Backend choice is a static Python string resolved once per
engine, so jit caches stay coherent. Under a mesh-sharded engine every
Pallas call runs inside ``shard_map`` (``_on_mesh``): GSPMD cannot
partition a Mosaic kernel.
"""
from __future__ import annotations

import contextlib
import functools
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.act_sharding import installed_mesh

from . import ref
from .bgmv import bgmv as _bgmv_pallas
from .paged_attention import paged_attention as _paged_pallas
from .sgmv import pack_segments, sgmv as _sgmv_pallas

LORA_BACKENDS = ("auto", "einsum", "kernel")


@functools.lru_cache(maxsize=1)
def on_tpu() -> bool:
    # Memoised: the serving hot loop asks per dispatch and the backend
    # cannot change after the first device op anyway.
    return jax.default_backend() == "tpu"


class DispatchMeter:
    """Hot-loop observability: jit dispatches and host-sync wall time.

    The serving engine ticks the meter once per device dispatch it
    launches on the decode path and wraps its device→host token reads
    in ``sync()`` (the same reads are its ``engine.readback`` spans
    while a profiler capture runs, ``serving/spans.py``). ``benchmarks/decode_hotloop.py`` reads the meter to
    report dispatches/token and the host-sync fraction — the two
    numbers the fused device-resident loop exists to shrink. A plain
    counter + accumulator: the per-step cost is one int add, so the
    meter stays on in production paths.

    Speculative decoding splits device work into two phases the meter
    counts separately on top of ``dispatches``: ``draft_dispatches``
    (draft-model forward passes — the chained proposal steps plus the
    draft prefill/catch-up calls) and ``verify_dispatches`` (multi-token
    target verify passes). ``dispatches`` still counts *jit dispatches
    launched*, so one fused speculative round ticks ``tick(1)`` plus
    the per-phase counts of the forwards folded inside it.
    """

    def __init__(self) -> None:
        self.dispatches = 0
        self.draft_dispatches = 0
        self.verify_dispatches = 0
        self.sync_seconds = 0.0

    def reset(self) -> None:
        self.dispatches = 0
        self.draft_dispatches = 0
        self.verify_dispatches = 0
        self.sync_seconds = 0.0

    def tick(self, n: int = 1) -> None:
        self.dispatches += n

    def tick_draft(self, n: int = 1) -> None:
        self.draft_dispatches += n

    def tick_verify(self, n: int = 1) -> None:
        self.verify_dispatches += n

    @contextlib.contextmanager
    def sync(self):
        """Time a blocking device→host readback (e.g. ``np.asarray`` on
        a decode result)."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.sync_seconds += time.perf_counter() - t0


#: Process-wide meter the engine step loops tick (reset by benchmarks).
DISPATCH_METER = DispatchMeter()


def resolve_lora_backend(backend: str | None) -> str:
    """Resolve a ``EngineConfig.lora_backend`` knob to a concrete path.

    ``auto`` (or None) picks the Pallas kernels on TPU and the einsum
    reference elsewhere; ``kernel`` forces the Pallas path (interpret
    mode off-TPU — what the CI parity jobs run); ``einsum`` forces the
    reference path.
    """
    if backend in (None, "auto"):
        return "kernel" if on_tpu() else "einsum"
    if backend not in ("einsum", "kernel"):
        raise ValueError(
            f"lora_backend must be one of {LORA_BACKENDS}, got {backend!r}")
    return backend


def _pad_axis(a, axis, mult):
    size = a.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return a, size
    width = [(0, 0)] * a.ndim
    width[axis] = (0, pad)
    return jnp.pad(a, width), size


def lora_bgmv(x, A, B, idx, *, prefer_kernel: bool | None = None,
              interpret: bool | None = None):
    """Decode LoRA delta. x: (Bt, din) -> (Bt, dout)."""
    use_kernel = on_tpu() if prefer_kernel is None else prefer_kernel
    if not use_kernel:
        return ref.bgmv_ref(x, A, B, idx)
    interpret = (not on_tpu()) if interpret is None else interpret
    Bp, dout0 = B, B.shape[-1]
    Bp, _ = _pad_axis(B, 2, 128)
    y = _bgmv_pallas(x, A, Bp, idx, interpret=interpret)
    return y[:, :dout0]


def lora_sgmv(x, A, B, seq_lens, adapter_slots, *, tile: int = 128,
              prefer_kernel: bool | None = None,
              interpret: bool | None = None):
    """Prefill LoRA delta over concatenated sequences.

    x: (T, din) tokens concatenated per request (seq_lens[i] each),
    adapter_slots[i] the adapter of request i. Returns (T, dout).
    """
    use_kernel = on_tpu() if prefer_kernel is None else prefer_kernel
    perm, tile_slot, padded_T = pack_segments(seq_lens, adapter_slots,
                                              tile)
    perm_j = jnp.asarray(perm)
    gathered = jnp.where(perm_j[:, None] >= 0,
                         x[jnp.maximum(perm_j, 0)], 0).astype(x.dtype)
    if not use_kernel:
        y = ref.sgmv_ref(gathered, A, B, jnp.asarray(tile_slot), tile)
    else:
        interpret = (not on_tpu()) if interpret is None else interpret
        Bp, dout0 = _pad_axis(B, 2, 128)
        y = _sgmv_pallas(gathered, A, Bp, jnp.asarray(tile_slot),
                         tile=tile, interpret=interpret)[:, :dout0]
    # Scatter back to original token order.
    out = jnp.zeros((x.shape[0], y.shape[-1]), y.dtype)
    valid = perm_j >= 0
    return out.at[jnp.maximum(perm_j, 0)].add(
        jnp.where(valid[:, None], y, 0))


def _lora_bgmv_tokens(x, A, B, idx, interpret):
    """(T, din) tokens, one adapter index per token, via the bgmv kernel."""
    x, din0 = _pad_axis(x, 1, 128)
    A, _ = _pad_axis(A, 1, 128)
    Bp, dout0 = _pad_axis(B, 2, 128)
    y = _bgmv_pallas(x, A, Bp, idx, out_tile=128, interpret=interpret)
    return y[:, :dout0]


def _lora_sgmv_uniform(x, A, B, idx, tile, interpret):
    """Prefill LoRA via sgmv for *uniform* segments (jit-traceable).

    x: (Bt, S, din) — request b's tokens are the contiguous run x[b],
    all runs the same (static) length S, adapter idx[b] per run. This is
    the batched-prefill layout the engine produces (right-padded (B, S)
    buckets), so no host-side ``pack_segments`` permutation is needed:
    S is padded up to a tile multiple and ``tile_slot`` is idx repeated
    per tile. The ragged path (`lora_sgmv`) keeps pack_segments for
    host-driven concatenated layouts.
    """
    Bt, S, din = x.shape
    tile = min(tile, -(-S // 8) * 8)          # small-S: shrink the tile
    S_pad = -(-S // tile) * tile
    if S_pad != S:
        x = jnp.pad(x, ((0, 0), (0, S_pad - S), (0, 0)))
    xt = x.reshape(Bt * S_pad, din)
    xt, _ = _pad_axis(xt, 1, 128)
    A, _ = _pad_axis(A, 1, 128)
    Bp, dout0 = _pad_axis(B, 2, 128)
    tile_slot = jnp.repeat(idx.astype(jnp.int32), S_pad // tile)
    y = _sgmv_pallas(xt, A, Bp, tile_slot, tile=tile, out_tile=128,
                     interpret=interpret)
    return y.reshape(Bt, S_pad, -1)[:, :S, :dout0]


def _on_mesh(kernel):
    """Run a Pallas call under the serving mesh the engine installed.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so under a mesh the call runs inside
    ``shard_map`` with every operand replicated: each device gathers
    its inputs and computes the whole result in single-device order,
    which keeps the mesh engine's exact-reductions contract. Without a
    mesh the kernel is called as is."""
    mesh = installed_mesh()
    if mesh is None:
        return kernel

    def run(*args):
        return jax.shard_map(kernel, mesh=mesh,
                             in_specs=tuple(P() for _ in args),
                             out_specs=P(), check_vma=False)(*args)
    return run


def lora_delta_kernel(x, A, B, idx, *, scale: float = 1.0,
                      tile: int = 128, interpret: bool | None = None):
    """Multi-adapter LoRA delta through the Pallas kernels.

    x: (Bt, S, din); A: (n_slots, din, r); B: (n_slots, r, dout);
    idx: (Bt,). Decode (S == 1) routes through bgmv (one gathered
    adapter per token); prefill (S > 1) routes each request's
    contiguous token run through sgmv tiles. Returns (Bt, S, dout).
    """
    interpret = (not on_tpu()) if interpret is None else interpret
    if x.shape[1] == 1:
        y = _on_mesh(lambda x, A, B, idx: _lora_bgmv_tokens(
            x[:, 0], A, B, idx, interpret)[:, None])(x, A, B, idx)
    else:
        y = _on_mesh(lambda x, A, B, idx: _lora_sgmv_uniform(
            x, A, B, idx, tile, interpret))(x, A, B, idx)
    return (scale * y).astype(x.dtype)


def paged_attention(q, k_pages, v_pages, page_table, lengths, *,
                    prefer_kernel: bool | None = None,
                    interpret: bool | None = None):
    """Decode attention over paged KV; see paged_attention.py."""
    use_kernel = on_tpu() if prefer_kernel is None else prefer_kernel
    if not use_kernel:
        return ref.paged_attention_ref(q, k_pages, v_pages, page_table,
                                       lengths)
    interpret = (not on_tpu()) if interpret is None else interpret
    return _on_mesh(functools.partial(_paged_pallas, interpret=interpret))(
        q, k_pages, v_pages, page_table, lengths)
