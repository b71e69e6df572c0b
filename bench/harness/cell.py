"""One run of one cell: set up, measure, check, print.

    set-up    device check, weights and adapters from the seed, the
              engine, every shape this cell's traffic uses compiled
              and run once, hot adapters resident;
    window    the open-loop client for ``--seconds`` (traced when
              ``--trace 1``), then a drain of up to a minute in which
              nothing more is sent;
    check     the engine freed, a sample of finished requests drawn
              from the seed (the longest among them) run through the
              plain reference, and each served token's logit compared
              with the reference's best;
    print     end-to-end metrics (``--trace 0``) or per-layer metrics,
              device busy time and a breakdown (``--trace 1``), with the
              numbers compared beside their limits, last.
"""
from __future__ import annotations

import gc
import json
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from . import model
from .client import Client
from .traffic import Mix, adapter_ranks, hot_adapters

GRACE_S = 60.0            # a request due in the window may end this late
CHECK_MAX_REQUESTS = 32   # finished requests the reference compares
NO_READING = 1e30         # the gap where no request finished to compare


class SetupError(Exception):
    """The run cannot be made here (no chip, missing file): no result."""


def prng(seed: int):
    """A key from any whole seed (``PRNGKey`` keeps only 32 bits)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


class CompileMeter:
    """Counts JAX's compile work from its own monitoring events (copied
    from ``chip_smoke.py``), so set-up and the window can each say how
    much they compiled."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.compiles = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs
        if event == self.EVENT:
            self.compiles += 1


def load_cell(root: Path, workload: str):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SetupError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    paths = {"config": root / conf["file"],
             "traffic": root / "bench" / "traffic" / f"{cell['traffic']}.json",
             "check": root / "bench" / "checks" / f"{workload}.json"}
    for what, p in paths.items():
        if not p.is_file():
            raise SetupError(f"missing {what} file {p}")
    return bench, cell, paths


def device_stamp(require_tpu: bool, chips: int) -> dict:
    import jax
    if require_tpu:
        from repro.launch.device import init_device
        stamp = init_device(require="tpu")
    else:
        d = jax.devices()
        stamp = {"platform": d[0].platform, "kind": d[0].device_kind,
                 "count": len(d)}
    if stamp["count"] < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX sees "
                         f"{stamp['count']}")
    # Every program this run compiles goes to the persistent cache, the
    # sub-second ones too, so the next run of the cell compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return stamp


def _bucket(n: int) -> int:
    """The engine's prefill bucket for a suffix of ``n`` tokens."""
    return 1 << max(3, (n - 1).bit_length())


def suffix_range(mix: Mix, traffic) -> tuple[int, int]:
    """Shortest and longest prompt part this run will prefill: the
    prompts themselves for independent requests, the mix's bounds on a
    turn's new tokens for sessions."""
    if mix.kind == "requests":
        lens = [len(it.prompt) for it in traffic]
        return min(lens), max(lens)
    return mix.min_suffix(), mix.max_suffix()


def warm_up(eng, mix: Mix, serving: dict, vocab: int, seed: int,
            lo: int, hi: int) -> None:
    """Compile and run once every shape this run's traffic uses: each
    prefill bucket (batch rows x suffix length) from its shortest to its
    longest suffix, and each decode horizon; then make the mix's most
    popular adapters resident, as a server that has been up a while
    holds them."""
    from repro.core import Request

    rng = np.random.default_rng(seed ^ 0xA11CE)
    hot = hot_adapters(mix.adapters, min(serving["n_lora_slots"],
                                         mix.adapters["count"]))

    def serve(reqs):
        for r in reqs:
            eng.submit(r)
        eng.drain()

    def req(n, out, aid):
        return Request(input_len=n, output_len=out, adapter_id=aid,
                       prompt=rng.integers(0, vocab, n).tolist())

    serve([req(8, 1, hot[0])])              # the adapter, then shapes
    rows = [1 << i for i in range(
        (serving["max_slots"] - 1).bit_length() + 1)]
    s = _bucket(lo)
    while s <= _bucket(hi):
        for b in rows:
            serve([req(s, 2, hot[0]) for _ in range(b)])
        s *= 2
    serve([req(8, 2 * serving.get("max_horizon", 8), hot[0])])
    serve([req(8, 1, a) for a in hot])


def check_limits(path: Path) -> dict:
    return json.loads(path.read_text())["limits"]


def gap_numbers(gaps: np.ndarray) -> dict:
    """The numbers a check may compare, from the gaps at every compared
    position: their mean, the widest, and the share of positions whose
    served token is not the reference's first (gap above 0)."""
    if gaps.size == 0:
        return {"mean_logit_gap": NO_READING, "max_logit_gap": NO_READING,
                "flip_share": NO_READING}
    return {"mean_logit_gap": float(gaps.mean()),
            "max_logit_gap": float(gaps.max()),
            "flip_share": float(np.mean(gaps > 0))}


def reference_gaps(cf, w, adapters, logs, rng, max_out: int,
                   control: str | None = None) -> dict:
    """Compare finished requests with the plain reference.

    Up to ``CHECK_MAX_REQUESTS`` finished requests, drawn from ``rng``
    with the longest (prompt and answer) first. At each served position
    the gap is the reference's best logit minus its logit for the token
    the engine served: 0 where the engine chose the reference's token.
    Returns the gaps (``gaps``), the numbers ``gap_numbers`` makes of
    them, and how many requests and tokens were compared. ``control``
    ("bfloat16") puts the same equations, in that precision, in the
    engine's place: the token it puts first at each of those positions
    is read the same way (``control_gaps``, ``control``); the
    benchmark's own runs never do this."""
    import jax
    import jax.numpy as jnp

    ref = model.reference(cf.dims)
    done = [g for g in logs if g.finished and g.tokens]
    longest = max(range(len(done)), default=None, key=lambda i: (
        len(done[i].item.prompt) + len(done[i].tokens), len(done[i].tokens)))
    order = [] if longest is None else [longest] + [
        int(i) for i in rng.permutation(len(done)) if i != longest]
    order = order[:CHECK_MAX_REQUESTS]
    T = cf.serving["max_len"]
    gap_fn = jax.jit(lambda lg, tok: jnp.max(lg, -1) - jnp.take_along_axis(
        lg, tok[:, None], -1)[:, 0])
    low = (lambda t: jax.tree.map(lambda a: a.astype(control), t)
           ) if control else None
    w_low = low(w) if control else None
    gaps, cgaps = [np.zeros(0)], [np.zeros(0)]
    for i in order:
        g = done[i]
        prompt, served = g.item.prompt, g.tokens
        n = len(served)
        seq = np.zeros(T, np.int32)
        seq[:len(prompt) + n - 1] = prompt + served[:-1]
        pos = np.zeros(max_out, np.int32)
        pos[:n] = len(prompt) - 1 + np.arange(n)
        tok = np.zeros(max_out, np.int32)
        tok[:n] = served
        ad = {t: (jnp.asarray(a), jnp.asarray(b))
              for t, (a, b) in adapters[g.item.adapter].items()}
        args = (jnp.asarray(seq), jnp.asarray(pos))
        lg = ref.logits_at(w, ad, *args, dims=cf.dims, dtype="float32")
        gaps.append(np.asarray(gap_fn(lg, jnp.asarray(tok)))[:n])
        if control:
            lg_low = ref.logits_at(w_low, low(ad), *args, dims=cf.dims,
                                   dtype=control)
            top = jnp.argmax(lg_low, -1).astype(jnp.int32)
            cgaps.append(np.asarray(gap_fn(lg, top))[:n])
    gaps = np.concatenate(gaps).astype(np.float64)
    out = {"gaps": gaps, "requests": len(order), "tokens": int(gaps.size),
           **gap_numbers(gaps)}
    if control:
        cg = np.concatenate(cgaps).astype(np.float64)
        out.update(control_gaps=cg, control=gap_numbers(cg))
    return out


def _pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


class RunData:
    """What a per-layer reader may read: the client's logs, the engine's
    counters at the window's start and end, the program's own request
    records, the trace, the sizes and the chip's peaks."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def delta(self, key: str) -> float:
        return self.counters_end[key] - self.counters_start[key]


def counters(eng) -> dict:
    from repro.kernels.ops import DISPATCH_METER
    st = eng.cache.stats
    return {"hits": st.hits, "misses": st.misses,
            "prefix_hit_tokens": eng.prefix_hit_tokens,
            "prefix_lookup_tokens": eng.prefix_lookup_tokens,
            "dispatches": DISPATCH_METER.dispatches}


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_tpu: bool = True,
             keep_trace: Path | None = None,
             rate: float | None = None, control: str | None = None,
             log_requests: Path | None = None) -> dict:
    """One run; returns the result line (a dict). Raises SetupError
    where no result may be printed."""
    bench, cell, paths = load_cell(root, workload)
    cf = model.ConfigFile(paths["config"])
    mix = Mix.load(paths["traffic"])
    if rate is not None:              # a knee sweep's offered load
        key = "rate_per_s" if mix.kind == "requests" else "session_rate_per_s"
        mix.spec[key] = rate
    limits = check_limits(paths["check"])
    stamp = device_stamp(require_tpu, cell["chips"])
    peak_table = _peaks(root, stamp["kind"]) if trace else None

    import jax

    meter = CompileMeter()
    dims, serving = cf.dims, cf.serving
    cfg = cf.model_config()
    key = prng(seed)
    w = model.make_weights(dims, key)
    jax.block_until_ready(w)
    ranks = adapter_ranks(mix.adapters)
    adapters = model.make_adapters(dims, ranks, serving["r_max"],
                                   jax.random.fold_in(key, 1))
    cat = model.catalog(cfg, ranks, serving["r_max"], adapters)
    eng = model.build_engine(cf, cfg, w, cat, seed & 0x7FFFFFFF)
    traffic = mix.build(seed, seconds, dims.vocab)
    warm_up(eng, mix, serving, dims.vocab, seed,
            *suffix_range(mix, traffic))
    eng.reset_stats()
    from repro.kernels.ops import DISPATCH_METER
    DISPATCH_METER.reset()
    c_setup = meter.compiles
    client = Client(eng, mix, traffic, serving["max_len"], spans=trace)
    start = counters(eng)
    ends = {}
    tdir = Path(tempfile.mkdtemp(prefix="bench_trace_")) if trace else None

    def on_close():
        ends["backlog"] = sum(1 for g in client.logs if not g.times)
        ends["counters"] = counters(eng)
        ends["compiles"] = meter.compiles - c_setup

    if trace:
        # The benchmark's own spans and the device, not every Python call.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    drain_s = client.run(seconds, GRACE_S, on_close=on_close)
    if trace:      # after the drain: writing the trace stalls the host
        jax.profiler.stop_trace()
    mem = jax.devices()[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    qwait = {r.req_id: r.queue_wait for r in eng.records}
    stats = eng.stats()
    del eng, client.eng
    gc.collect()

    logs = client.logs
    attempted = len(logs)
    unanswered = sum(1 for g in logs if not g.finished)
    wrong = sum(1 for g in logs if g.finished and (
        len(g.tokens) != g.item.output_len
        or any(not 0 <= t < dims.vocab for t in g.tokens)))
    rng = np.random.default_rng(seed ^ 0xC4EC)
    ref = reference_gaps(cf, w, adapters, logs, rng,
                         mix.spec["output"]["max"], control=control)
    # A control run is judged by the control's reading in the engine's
    # place; the engine's own stays among the diagnostics.
    judged = ref["control"] if control else ref
    checks = {name: {"value": judged[name], "limit": lim}
              for name, lim in limits.items()}
    checks["unanswered"] = {"value": unanswered, "limit": 0}
    checks["wrong_length_or_id"] = {"value": wrong, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = dict(stamp, memory_peak_bytes=peak)
    data = RunData(
        dims=dims, logs=logs, window_s=seconds, ranks=ranks,
        counters_start=start, counters_end=ends["counters"],
        queue_wait=qwait, trace=None,
        peak=peak_table)
    late = [g.late for g in logs]
    diag = {"requests_due": attempted,
            "ttft_samples": sum(1 for g in logs if g.times),
            "late_p90_s": _pct(late, 90) if late else 0.0,
            "late_max_s": max(late) if late else 0.0,
            "compiles_in_window": ends["compiles"],
            "backlog_at_close": ends["backlog"],
            "compile_s_total": meter.seconds, "drain_s": drain_s,
            "program": {k: ref[k] for k in (
                "mean_logit_gap", "max_logit_gap", "flip_share")},
            "checked_requests": ref["requests"],
            "checked_tokens": ref["tokens"],
            "prefix_hit_tokens": data.delta("prefix_hit_tokens"),
            "adapter_misses": data.delta("misses"),
            "engine": {k: stats.get(k) for k in (
                "bypassed", "squashed", "deferred", "async_loads",
                "prefix_evictions", "kv_pages_total", "preempted")}}
    result = {"correct": correct, "attempted": attempted,
              "failed": unanswered + wrong}
    if trace:
        from . import trace as tracemod
        files = sorted(tdir.rglob("*.xplane.pb"))
        data.trace = tracemod.load(files[-1])
        if keep_trace is not None:
            keep_trace.mkdir(parents=True, exist_ok=True)
            shutil.copy(files[-1], keep_trace / files[-1].name)
        shutil.rmtree(tdir, ignore_errors=True)
        metrics = per_layer(root, bench, workload, data)
        device["busy_s"] = data.trace.mean_busy_s()
        device["window_s"] = data.trace.window_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": data.trace.top_ops(10),
                               "idle_gaps": data.trace.idle_gaps(10)}
    else:
        result["metrics"] = end_to_end(bench, workload, logs, seconds,
                                       setup_s)
        result["device"] = device
    if control:
        diag["control"] = ref["control"]
        result["gaps"] = {"program": ref["gaps"].tolist(),
                          "control": ref["control_gaps"].tolist()}
    if log_requests is not None:      # per request, for knee sweeps
        log_requests.write_text(json.dumps([
            {"due": g.item.due, "submit": g.submit, "times": g.times,
             "prompt": len(g.item.prompt), "output": g.item.output_len,
             "adapter": g.item.adapter, "finished": g.finished}
            for g in logs]))
    result["diagnostics"] = diag
    result["checks"] = checks
    return result


def _peaks(root: Path, kind: str) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SetupError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def end_to_end(bench: dict, workload: str, logs, window_s: float,
               setup_s: float) -> dict:
    """The cell's end-to-end metrics, over every request due in the
    window: TTFT from each request's due time (a request never answered
    counts as waiting until the drain ended); each request's time per
    output token after the first (its last token's time minus its
    first's, over the tokens between); and the tokens that arrived in
    the window over its length."""
    end = max((t for g in logs for t in g.times), default=window_s)
    ttft = [g.ttft if g.times else end - g.item.due for g in logs]
    tpot = [(g.times[-1] - g.times[0]) / (len(g.times) - 1)
            for g in logs if len(g.times) > 1]
    toks = sum(1 for g in logs for t in g.times if t <= window_s)
    vals = {"ttft_p50_s": (_pct(ttft, 50), "s"),
            "ttft_p90_s": (_pct(ttft, 90), "s"),
            "tpot_p90_ms": (1e3 * _pct(tpot, 90) if tpot else 0.0, "ms"),
            "output_tokens_per_s": (toks / window_s, "tokens/s"),
            "setup_s": (setup_s, "s")}
    out = {}
    for m in bench["end_to_end"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        v, unit = vals[m["name"]]
        if unit != m["unit"]:
            raise SetupError(f"{m['name']} is measured in {unit}, "
                             f"BENCHMARK.json says {m['unit']}")
        out[m["name"]] = {"value": v, "unit": unit}
    return out


def per_layer(root: Path, bench: dict, workload: str, data: RunData) -> dict:
    """Each per-layer metric of this cell, from its own reader
    ``bench/metrics/<name>.py``; a reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        mod = model.load_module(root / "bench" / "metrics" / f"{m['name']}.py",
                                f"bench_metric_{m['name']}")
        v = mod.read(data)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
