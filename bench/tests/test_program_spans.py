"""The program's spans on the trace's clock (``bench/harness/
program_spans.py``) and the four readers that use them: in a traced run
on the CPU the moved spans agree with their own mirrored annotations;
on hand-made spans and events each reader reads what it should; and
each reads nothing where the program holds no span."""
import json
import sys
import time
from types import SimpleNamespace

import pytest

import bench.harness.cell as cell
from bench.harness import model, program_spans, trace
from bench.harness.trace import Event, Trace
from bench.tests import tiny
from repro.serving.spans import SPANS, Span

READERS = ("adapter_wait_p90_s", "adapter_stall_idle_share",
           "prefill_ms_p50", "kv_place_ms_p50")


def reader(name):
    return model.load_module(tiny.BENCH / "metrics" / f"{name}.py",
                             f"test_metric_{name}")


def test_spans_meet_their_annotations_in_a_traced_cpu_run(tmp_path,
                                                          monkeypatch):
    from jax.profiler import ProfileData

    monkeypatch.setattr(cell, "_peaks", lambda root, kind: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    root = tiny.make_root(tmp_path / "b", {"req": tiny.REQUESTS})
    conf = json.loads(json.dumps(tiny.CONFIG))
    conf["serving"]["engine"] = {"prefix_cache": False}   # as the cells
    (root / "bench/configs/tiny.json").write_text(json.dumps(conf))
    kept = tmp_path / "kept"
    res = cell.run_cell(root, "tiny.req", 2**33 + 9, 2.0, True,
                        time.perf_counter(), require_tpu=False,
                        keep_trace=kept)
    m = res["metrics"]
    assert {"adapter_wait_p90_s", "prefill_ms_p50",
            "kv_place_ms_p50"} <= set(m)
    assert "adapter_stall_idle_share" not in m     # no device plane here
    assert m["prefill_ms_p50"]["value"] > 0

    path = next(kept.glob("*.xplane.pb"))
    moved = program_spans.on_trace_clock(
        SimpleNamespace(trace=trace.load(path)))
    assert moved is not None
    mirrored = {}
    for p in ProfileData.from_file(str(path)).planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                for e in line.events:
                    if e.name.startswith("engine."):
                        mirrored.setdefault(e.name, []).append(
                            (e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9))
    assert {"engine.step", "engine.prefill", "engine.kv_place",
            "engine.decode", "engine.readback"} <= set(mirrored)
    for name, marks in mirrored.items():
        mine = sorted((s.start, s.end) for s in moved if s.name == name)
        assert len(mine) == len(marks), name
        for (s, e), (ms, me) in zip(mine, sorted(marks)):
            assert abs(s - ms) < 50e-6 and abs(e - me) < 50e-6, name


def hand_made(monkeypatch, steps_in_trace=2):
    """Recorder spans on a clock 100 s ahead of a 10-s traced window;
    the device busy in 0-1 s and 5-6 s."""
    spans = [
        Span("engine.step", 100.0, 102.0, 1),
        Span("engine.prefill", 100.1, 100.4, 2, parent=1,
             attrs={"req_ids": [7]}),
        Span("engine.kv_place", 100.4, 100.6, 3, parent=1),
        Span("engine.step", 105.0, 108.0, 4),
        Span("engine.prefill", 105.1, 105.3, 5, parent=4),
        Span("engine.kv_place", 105.3, 105.4, 6, parent=4),
        Span("engine.idle_wait", 106.0, 108.0, 7, parent=4),
        Span("engine.step", 111.0, 112.0, 8),        # after the window
        Span("engine.prefill", 111.1, 112.0, 9, parent=8),
        Span("request.queued", 99.0, 100.1, 10, req_id=7,
             attrs={"adapter_wait": 0.5}),
        Span("request.queued", 99.0, 105.1, 11, req_id=8,
             attrs={"adapter_wait": 0.0}),
    ]
    monkeypatch.setattr(SPANS, "spans", spans)
    host = [Event("bench_window", 0.0, 10.0), Event("step", 0.0, 2.0),
            Event("step", 5.0, 8.0), Event("step", 11.0, 12.0)]
    tr = Trace(ops={"/device:TPU:0": [Event("%a", 0.0, 1.0),
                                      Event("%b", 5.0, 6.0)]},
               modules={}, host=host[:1 + steps_in_trace + 1],
               window=(0.0, 10.0))
    logs = [SimpleNamespace(req_id=7, times=[0.6, 0.9]),
            SimpleNamespace(req_id=8, times=[5.4]),
            SimpleNamespace(req_id=9, times=[6.0])]    # no span: skipped
    return SimpleNamespace(trace=tr, logs=logs, window_s=10.0)


def test_readers_on_hand_made_spans(monkeypatch):
    run = hand_made(monkeypatch)
    assert program_spans.idle_by_span(run) == pytest.approx(
        {"other": 4.0, "engine.idle_wait": 4.0})
    assert reader("adapter_stall_idle_share").read(run) == \
        pytest.approx(40.0)
    assert reader("prefill_ms_p50").read(run) == pytest.approx(250.0)
    assert reader("kv_place_ms_p50").read(run) == pytest.approx(150.0)
    assert reader("adapter_wait_p90_s").read(run) == pytest.approx(0.45)


def test_nothing_to_read(monkeypatch):
    run = hand_made(monkeypatch, steps_in_trace=1)   # counts differ
    assert program_spans.on_trace_clock(run) is None
    for name in READERS[1:]:
        assert reader(name).read(run) is None
    monkeypatch.setattr(SPANS, "spans", [])
    for name in READERS:
        assert reader(name).read(run) is None
    # A program without the recorder.
    run = hand_made(monkeypatch)
    monkeypatch.setitem(sys.modules, "repro.serving.spans", None)
    for name in READERS:
        assert reader(name).read(run) is None
