#!/usr/bin/env python3
"""Chip smoke: serve internlm2-1.8b at published width on a TPU.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # four chips of one host

One chip runs four phases in one process:

0. device and compile cache (``repro.launch.device.init_device``): the
   run fails unless JAX's first device is a TPU;
1. the bgmv (decode LoRA), sgmv (prefill LoRA) and paged-attention
   Pallas kernels, called through ``repro.kernels.ops`` at internlm2
   widths, against the plain references in ``repro.kernels.ref``;
2. the serving path a user calls — ``build_system(tier="engine")``,
   ``submit``, ``step``, ``drain`` — on internlm2-1.8b with random
   float32 weights from ``--seed``: paged KV, the fused decode loop,
   the prefix cache, async adapter loads and both prefetchers, with
   more adapters than LoRA slots so the cache loads and evicts;
3. the greedy requests again on an engine with the einsum LoRA path,
   reporting token agreement and the first-token logit difference.

``--four-chips`` runs only the multi-chip paths, each against its
comparison: (a) four one-chip replicas behind ``adapter_affinity``
routing against one engine on chip 0, and (b) an engine on a (2, 2)
mesh against the single-device engine.

Every time printed is a wall-clock smoke count, not a benchmark. Any
failed check exits non-zero before the last line, which is one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MODEL = "internlm2-1.8b"
R_MAX = 32          # EngineConfig default: the slot arena's rank
PAGE = 16           # EngineConfig default page size
ROWS = 8            # decode rows (EngineConfig default max_slots)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileMeter:
    """Seconds JAX spent tracing, lowering and compiling (its own
    monitoring events), so serving time can be told apart from
    compile time in one process."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += secs
        if event == self.EVENTS[-1]:
            self.backend_compiles += 1


# ------------------------------------------------------------ phase 1
def phase_kernels(cfg, seed: int) -> None:
    """Kernels vs references at internlm2 widths, compiled for the chip.

    Tolerances, each relative to the reference's largest magnitude:
    - bgmv / sgmv 1e-2: the kernels round the rank-32 intermediate
      x @ A to bfloat16 (relative error 2**-8 per element) before the
      expand matmul against the bfloat16 B, while the reference keeps
      it in float32 at "highest" precision; 32 such terms stay well
      under 1e-2 of the output scale, and a wrong adapter or tile is
      off by O(1).
    - paged attention 1e-2: the kernel's float32 dots (q.k and p.v)
      run as single bfloat16 MXU passes at default precision, rounding
      q, k, the probabilities and v to bfloat16 (relative error 2**-9
      each), and the online softmax sums pages in another order than
      the one-shot reference; a wrong page or length mask is off by
      O(1). (A first bound of 1e-3 assumed float32 passes; the chip
      measured 2.3e-3.)
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    check(ops.on_tpu(), "kernels.ops does not see a TPU backend")
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    n_slots = 8

    def normal(shape, scale=1.0, dtype=jnp.float32):
        return (scale * jax.random.normal(next(keys), shape)).astype(dtype)

    def compiled(fn, *args):
        jf = jax.jit(fn)
        txt = jf.lower(*args).compile().as_text()
        check("tpu_custom_call" in txt,
              f"{fn.__name__}: compiled program has no tpu_custom_call")
        return jf

    def rel_err(y, y_ref):
        y, y_ref = np.asarray(y, np.float64), np.asarray(y_ref, np.float64)
        check(np.isfinite(y).all(), "kernel output is not finite")
        return float(np.abs(y - y_ref).max() / np.abs(y_ref).max())

    def lora(x, A, B, idx):
        return ops.lora_delta_kernel(x, A, B, idx, interpret=False)

    def attn(q, kp, vp, pt, ln):
        return ops.paged_attention(q, kp, vp, pt, ln, prefer_kernel=True,
                                   interpret=False)

    d, q_dim, kv_dim = cfg.d_model, cfg.q_dim, cfg.kv_dim
    for name, din, dout in (("q", d, q_dim), ("k", d, kv_dim)):
        A = normal((n_slots, din, R_MAX), din ** -0.5, jnp.bfloat16)
        B = normal((n_slots, R_MAX, dout), R_MAX ** -0.5, jnp.bfloat16)
        idx = jax.random.randint(next(keys), (ROWS,), 0, n_slots)
        # bgmv: one token per row.
        x = normal((ROWS, 1, din))
        y = compiled(lora, x, A, B, idx)(x, A, B, idx)
        with jax.default_matmul_precision("highest"):
            y_ref = ref.bgmv_ref(x[:, 0], A.astype(jnp.float32),
                                 B.astype(jnp.float32), idx)
        err = rel_err(y[:, 0], y_ref)
        log(f"phase1 bgmv[{name}] {din}->{dout} rows={ROWS}: "
            f"rel_err={err:.3e} (tol 1e-2)")
        check(err <= 1e-2, f"bgmv[{name}] rel_err {err} > 1e-2")
        # sgmv: 4 requests x 128 prompt tokens (one tile each).
        S = 128
        xs = normal((4, S, din))
        ys = compiled(lora, xs, A, B, idx[:4])(xs, A, B, idx[:4])
        with jax.default_matmul_precision("highest"):
            ys_ref = ref.sgmv_ref(xs.reshape(4 * S, din),
                                  A.astype(jnp.float32),
                                  B.astype(jnp.float32), idx[:4], S)
        err = rel_err(ys.reshape(4 * S, dout), ys_ref)
        log(f"phase1 sgmv[{name}] {din}->{dout} 4x{S} tokens: "
            f"rel_err={err:.3e} (tol 1e-2)")
        check(err <= 1e-2, f"sgmv[{name}] rel_err {err} > 1e-2")

    Kh, G, dh = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim
    n_pages, per_seq = 1025, 40
    qh = normal((ROWS, Kh, G, dh))
    kp = normal((n_pages, PAGE, Kh, dh))
    vp = normal((n_pages, PAGE, Kh, dh))
    pt = jax.random.permutation(next(keys), n_pages - 1)[:ROWS * per_seq]
    pt = (pt + 1).reshape(ROWS, per_seq).astype(jnp.int32)
    lens = jnp.asarray([1, 15, 16, 17, 200, 333, 511, per_seq * PAGE],
                       jnp.int32)
    out = compiled(attn, qh, kp, vp, pt, lens)(qh, kp, vp, pt, lens)
    with jax.default_matmul_precision("highest"):
        out_ref = ref.paged_attention_ref(qh, kp, vp, pt, lens)
    err = rel_err(out, out_ref)
    log(f"phase1 paged_attention B={ROWS} Kh={Kh} G={G} dh={dh} "
        f"pages={n_pages}x{PAGE}: rel_err={err:.3e} (tol 1e-2)")
    check(err <= 1e-2, f"paged_attention rel_err {err} > 1e-2")


# ------------------------------------------------------------ requests
def make_requests(cfg, seed: int):
    """16 requests over 12 adapters: 8 share a 256-token prefix (two per
    adapter, so the second of each pair can hit the prefix tree), 8
    more have their own 64- or 512-token prompts. Prompt lengths come
    from {64, 320, 512} so the prefill buckets stay few. Request 9 is
    sampled with a seed; request 15 is the one cancelled.

    Returns (wave1, wave2): lists of (Request, SamplingParams | None);
    wave 2 holds the second request of every prefix pair."""
    import numpy as np

    from repro.core import Request, SamplingParams

    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, 256).tolist()

    def toks(n):
        return rng.integers(0, cfg.vocab_size, n).tolist()

    outs = (32, 48, 64)
    wave1, wave2 = [], []
    for i in range(8):                       # prefix pairs, adapters 0..3
        req = Request(input_len=320, output_len=outs[i % 3],
                      adapter_id=i // 2, prompt=prefix + toks(64))
        (wave1 if i % 2 == 0 else wave2).append((req, None))
    for i in range(8):                       # own prompts, adapters 4..11
        n = 64 if i % 2 == 0 else 512
        sp = None
        if i == 1:
            sp = SamplingParams(temperature=0.8, top_k=64, seed=seed + 7)
        wave1.append((Request(input_len=n, output_len=outs[i % 3],
                              adapter_id=4 + i, prompt=toks(n)), sp))
    return wave1, wave2


def clone(req):
    from repro.core import Request
    return Request(input_len=req.input_len, output_len=req.output_len,
                   adapter_id=req.adapter_id, prompt=list(req.prompt))


# ------------------------------------------------------------ phase 2
def engine_config(**kw):
    from repro.serving.engine import EngineConfig
    # max_len covers the longest prompt (512) plus 64 outputs; more
    # adapters than LoRA slots so the cache has to evict.
    return EngineConfig(max_slots=ROWS, max_len=640, n_lora_slots=4,
                        n_adapters=16, **kw)


def phase_serve(cfg, params, seed: int, meter: CompileMeter):
    import jax

    from repro.core import RequestState
    from repro.serving import build_system

    c0, t0 = meter.seconds, time.perf_counter()
    eng = build_system("chameleon", tier="engine", model_cfg=cfg,
                       params=params, ecfg=engine_config(seed=seed),
                       seed=seed)
    check(eng._lora_backend == "kernel",
          f"engine resolved lora_backend {eng._lora_backend!r}")
    check(eng.paged and eng.fused, "engine is not paged + fused")
    check(eng.prefix is not None, "engine has no prefix cache")

    wave1, wave2 = make_requests(cfg, seed)
    handles = [eng.submit(r, sampling=sp) for r, sp in wave1]
    victim = handles[-1]
    cancelled = False
    steps = 0
    pending2 = list(wave2)
    while eng.busy() or pending2:
        eng.step()
        steps += 1
        if not cancelled and victim.tokens:
            check(eng.cancel(victim), "cancel of a running request "
                  "returned False")
            cancelled = True
        # Wave 2 (the second of each prefix pair) arrives once every
        # pair's first request has been prefilled and its prompt pages
        # adopted by the prefix tree.
        if pending2 and all(h.tokens for h in handles[:4]):
            handles += [eng.submit(r, sampling=sp) for r, sp in pending2]
            pending2 = []
        check(steps < 20_000, "engine did not drain")
    eng.drain()
    jax.block_until_ready(eng.kv_pages)
    wall = time.perf_counter() - t0
    compile_s = meter.seconds - c0

    check(cancelled and victim.state == RequestState.CANCELLED,
          f"cancelled request ended {victim.state}")
    n_tok = 0
    for h in handles:
        if h is victim:
            continue
        check(h.state == RequestState.FINISHED,
              f"request {h.req_id} ended {h.state}")
        check(len(h.tokens) == h.req.output_len,
              f"request {h.req_id}: {len(h.tokens)} tokens, "
              f"want {h.req.output_len}")
        check(all(0 <= t < cfg.vocab_size for t in h.tokens),
              f"request {h.req_id}: token out of vocabulary")
        n_tok += len(h.tokens)
    st = eng.stats()
    cache = st["cache"]
    check(cache["misses"] >= 1 and cache["evictions"] >= 1,
          f"adapter cache saw no miss/eviction: {cache}")
    check(st["prefix_hits"] >= 1, f"no prefix hit: {st}")
    check(len({h.adapter_id for h in handles}) >= 12, "fewer adapters")

    # The served decode program itself holds the Pallas kernels.
    d = eng._dev
    txt = eng._fused_paged_jit.lower(
        eng.params, eng.lora, eng.tokens, eng.kv_pages,
        eng._page_table_dev, eng.cache_len, d["active"], d["positions"],
        eng.adapter_slot, d["budget"], d["stop"], d["temp"], d["topk"],
        d["topp"], d["seeds"], K=1, all_greedy=True).as_text()
    n_kernels = txt.count("tpu_custom_call")
    check(n_kernels >= 2, "served decode program has no Pallas kernel")

    serve_s = wall - compile_s
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"phase2 served {len(handles)} requests over "
        f"{len({h.adapter_id for h in handles})} adapters in {steps} "
        f"steps: {n_tok} tokens, 1 cancelled")
    log(f"phase2 lora_backend={eng._lora_backend} paged={eng.paged} "
        f"fused={eng.fused} tpu_custom_calls_in_decode={n_kernels}")
    log(f"phase2 cache misses={cache['misses']} hits={cache['hits']} "
        f"evictions={cache['evictions']} async_loads={st['async_loads']}"
        f" prefix_hits={st['prefix_hits']} "
        f"prefix_hit_tokens={st['prefix_hit_tokens']}")
    log(f"phase2 smoke count (not a benchmark): wall={wall:.1f}s "
        f"compile={compile_s:.1f}s ({meter.backend_compiles} XLA "
        f"compiles so far) serving={serve_s:.1f}s "
        f"tokens/s={n_tok / max(serve_s, 1e-9):.1f} "
        f"peak_bytes_in_use={peak}")
    greedy = [(clone(h.req), list(h.tokens)) for h in handles
              if h is not victim and h.req.sampling is None]
    catalog = eng.catalog
    return eng, greedy, catalog


# ------------------------------------------------------------ phase 3
def first_token_logits(cfg, params, catalog, reqs, backend: str):
    """Prefill logits of ``reqs`` (equal prompt lengths) with their
    adapters in a fresh slot arena, through one LoRA backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import api
    from repro.models.lora_apply import (init_lora_slots,
                                         write_adapter_to_slot)

    arena = init_lora_slots(None, len(reqs), cfg.n_layers, cfg.d_model,
                            cfg.q_dim, cfg.kv_dim, catalog.r_max)
    for slot, r in enumerate(reqs):
        arena = write_adapter_to_slot(arena, catalog.weights[r.adapter_id],
                                      slot)
    toks = jnp.asarray(np.asarray([r.prompt for r in reqs], np.int32))
    idx = jnp.arange(len(reqs), dtype=jnp.int32)

    @jax.jit
    def run(params, arena, toks, idx):
        logits, _ = api.prefill(cfg, params, toks, lora=arena,
                                adapter_idx=idx, lora_backend=backend)
        return logits

    return np.asarray(run(params, arena, toks, idx), np.float64)


def phase_crosscheck(cfg, params, seed, greedy, catalog):
    from repro.serving import build_system

    eng = build_system("chameleon", tier="engine", model_cfg=cfg,
                       params=params,
                       ecfg=engine_config(seed=seed, lora_backend="einsum"),
                       seed=seed)
    check(eng._lora_backend == "einsum", "cross-check engine backend")
    handles = [eng.submit(r) for r, _ in greedy]
    eng.drain()
    same = total = 0
    for h, (_, toks) in zip(handles, greedy):
        check(len(h.tokens) == len(toks), "cross-check token count")
        same += sum(a == b for a, b in zip(h.tokens, toks))
        total += len(toks)
    del eng, handles
    gc.collect()
    reqs = [r for r, _ in greedy if r.input_len == 64][:4]
    lk = first_token_logits(cfg, params, catalog, reqs, "kernel")
    le = first_token_logits(cfg, params, catalog, reqs, "einsum")
    diff = float(abs(lk - le).max())
    log(f"phase3 kernel vs einsum engine: {same}/{total} greedy tokens "
        f"agree; first-token logits max|diff|={diff:.3e} over "
        f"{len(reqs)} prompts (logit scale {abs(le).max():.2f}); "
        f"random weights make argmax flips legitimate, not gated")


# ------------------------------------------------------------ 4 chips
def first_diff(a: list, b: list) -> int:
    """First position where two token lists differ (or the shorter
    list's length when one is a prefix of the other)."""
    return next((j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                min(len(a), len(b)))


def serve_serial(eng, reqs):
    """Each request alone: submitted, then drained. Returns tokens."""
    out = []
    for r in reqs:
        h = eng.submit(clone(r))
        eng.drain()
        out.append(list(h.tokens))
    return out


def four_chip_requests(cfg, seed: int):
    """Two waves of four greedy requests, four distinct adapters per
    wave; wave 2 repeats wave 1's adapters and prefix, so affinity
    routing sends each back to the replica that holds it."""
    import numpy as np

    from repro.core import Request

    rng = np.random.default_rng(seed + 1)
    prefix = rng.integers(0, cfg.vocab_size, 256).tolist()
    waves = []
    for _ in range(2):
        waves.append([Request(
            input_len=320, output_len=32, adapter_id=a,
            prompt=prefix + rng.integers(0, cfg.vocab_size, 64).tolist())
            for a in range(4)])
    return waves


def phase_four_chips(cfg, box: list, seed: int) -> None:
    """``box`` holds the only reference to the float32 weights on chip
    0; it is emptied once the mesh engine has its own sharded copy, so
    chip 0 does not carry both (7.56 GB + its mesh share) while the
    mesh engine runs.

    (b) gates on greedy tokens; only if they differ does it fall back to
    first-token logits within 1e-2 of the logit scale. The mesh engine
    runs in exact-reductions mode (activations are gathered before every
    contraction), but XLA still tiles the per-device shapes differently:
    on 4 CPU devices that left 1e-5 logit differences with the einsum
    LoRA path, and 3.4e-3 (1e-3 of the scale, 4 layers) with the Pallas
    path, whose bfloat16 rounding of the rank-r intermediate turns a
    float32 difference into a whole bfloat16 step. 1e-2 leaves room
    for 24 layers; a wrong shard or gather is off by O(1)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving import build_system

    params = box[0]
    waves = four_chip_requests(cfg, seed)
    flat = [r for w in waves for r in w]
    single = build_system("chameleon", tier="engine", model_cfg=cfg,
                          params=params, ecfg=engine_config(seed=seed),
                          seed=seed)
    ref_toks = serve_serial(single, flat)
    ref_logits = first_token_logits(cfg, params, single.catalog, waves[0],
                                    single._lora_backend)
    del single
    gc.collect()
    log(f"four(a) reference: one engine on chip 0 served {len(flat)} "
        f"requests one at a time")

    # (a) four one-chip replicas behind adapter_affinity routing.
    cl = build_system("chameleon", tier="cluster", n_nodes=4,
                      policy="adapter_affinity", model_cfg=cfg,
                      params=params, ecfg=engine_config(seed=seed),
                      seed=seed)
    homes = []
    for e in cl.engines:
        devs = (set(e.params["embed/tok"].devices())
                | set(e.kv_pages[0].devices())
                | set(jax.tree.leaves(e.lora)[0].devices()))
        check(len(devs) == 1, f"a replica spans {devs}")
        homes.append(devs.pop())
    check(len(set(homes)) == 4, f"replicas share devices: {homes}")
    got, nodes = [], []
    for w in waves:
        hs = [cl.submit(clone(r)) for r in w]
        cl.drain()
        got += [list(h.tokens) for h in hs]
        nodes += [h.node for h in hs]
    check(sorted(set(nodes[:4])) == [0, 1, 2, 3],
          f"wave 1 did not spread over the replicas: {nodes[:4]}")
    check(nodes[4:] == nodes[:4],
          f"wave 2 left its adapters' replicas: {nodes}")
    for i, (a, b) in enumerate(zip(got, ref_toks)):
        check(a == b, f"replica tokens differ from chip 0 at request {i} "
              f"(node {nodes[i]}), first position {first_diff(a, b)} "
              f"({len(a)} vs {len(b)} tokens)")
    log(f"four(a) cluster: replicas on {[str(d) for d in homes]}; "
        f"routed {nodes}; all {len(got)} requests' greedy tokens equal "
        f"the single engine's")
    del cl, e, hs
    gc.collect()

    # (b) one engine on a (2, 2) mesh vs the single-device engine.
    mesh_eng = build_system("chameleon", tier="engine", model_cfg=cfg,
                            params=params, ecfg=engine_config(seed=seed),
                            mesh_shape=(2, 2), seed=seed)
    del params
    box.clear()
    gc.collect()
    mesh_toks = serve_serial(mesh_eng, flat)
    diffs = [(i, first_diff(a, b))
             for i, (a, b) in enumerate(zip(mesh_toks, ref_toks)) if a != b]
    # First-token logits of wave 1 through the mesh engine's own
    # prefill, with the adapters its cache holds after serving.
    mesh_eng.flush_loads()
    reqs = waves[0]
    S = reqs[0].input_len
    toks = jnp.asarray(np.asarray([r.prompt for r in reqs], np.int32))
    lslots = jnp.asarray([mesh_eng.slot_of[r.adapter_id] for r in reqs],
                         jnp.int32)
    last = jnp.full((len(reqs),), S - 1, jnp.int32)
    with mesh_eng._act_scope():
        logits, _ = mesh_eng._get_prefill_jit(len(reqs), S)(
            mesh_eng.params, mesh_eng.lora, toks, lslots, last, S)
    ldiff = float(np.abs(np.asarray(logits, np.float64) - ref_logits).max())
    scale = float(np.abs(ref_logits).max())
    log(f"four(b) mesh (2,2) vs single device: "
        f"{len(flat) - len(diffs)}/{len(flat)} requests token-identical; "
        f"first differences (request, position): {diffs[:4]}; "
        f"first-token logits max|diff|={ldiff:.3e} (scale {scale:.2f})")
    if diffs:
        check(ldiff <= 1e-2 * scale,
              f"mesh (2,2) tokens differ at {diffs} and first-token "
              f"logits differ by {ldiff} > 1e-2 x {scale}")


# ------------------------------------------------------------ main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip replica and mesh paths")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.device import init_device
    stamp = init_device(require="tpu")
    log(f"device: platform={stamp['platform']} kind={stamp['kind']} "
        f"count={stamp['count']}")
    want = 4 if args.four_chips else 1
    check(stamp["count"] >= want,
          f"needs {want} chips, JAX sees {stamp['count']}")

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import api

    meter = CompileMeter()
    cfg = get_config(MODEL)
    t0 = time.perf_counter()
    # One jitted program: eager init compiles and dispatches per leaf.
    params = jax.jit(lambda k: api.init_params(cfg, k, jnp.float32))(
        jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"model {MODEL}: {cfg.n_layers} layers d={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size}; {n_params} float32 params from seed "
        f"{args.seed} in {time.perf_counter() - t0:.1f}s")

    if args.four_chips:
        box = [params]
        del params
        phase_four_chips(cfg, box, args.seed)
    else:
        phase_kernels(cfg, args.seed)
        eng, greedy, catalog = phase_serve(cfg, params, args.seed, meter)
        del eng
        gc.collect()
        phase_crosscheck(cfg, params, args.seed, greedy, catalog)
    log(f"total compile (trace+lower+XLA) {meter.seconds:.1f}s over "
        f"{meter.backend_compiles} XLA compiles")
    print(json.dumps({"ok": True, "device": stamp}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
