"""The trace reduction: on hand-made events, and on a short trace
recorded on a TPU v5e chip by ``bench/run.py --trace 1 --keep-trace``
(internlm2-1.8b.code-many-adapters, 2 s window), kept gzipped here."""
import gzip
import shutil
from pathlib import Path

import pytest

from bench.harness import trace
from bench.harness.trace import Event, Trace

RECORDED = Path(__file__).resolve().parent / "data" / "v5e_window.xplane.pb.gz"


def hand_made():
    ops = [Event("%a.1 = f32[2] add(x)", 1.0, 2.0),
           Event("%b.7 = f32[2] mul(x)", 1.5, 2.5),      # overlaps a
           Event("%paged_attention.3 = custom-call()", 4.0, 5.0),
           Event("%a.2 = f32[2] add(x)", 9.0, 12.0)]    # runs past the end
    mods = [Event("jit__fused_paged_fn(123)", 1.0, 5.0)]
    host = [Event("bench_window", 0.0, 10.0), Event("step", 2.5, 3.5),
            Event("wait_arrival", 5.0, 9.0)]
    return Trace(ops={"/device:TPU:0": ops},
                 modules={"/device:TPU:0": mods}, host=host,
                 window=(0.0, 10.0))


def test_busy_is_the_union_inside_the_window():
    t = hand_made()
    assert t.busy_s("/device:TPU:0") == pytest.approx(1.5 + 1.0 + 1.0)
    assert t.mean_busy_s() == pytest.approx(3.5)


def test_names_match_without_instance_numbers():
    t = hand_made()
    assert Event("%paged_attention.3 = x", 0, 1).short == "%paged_attention"
    assert Event("jit__fused_paged_fn(99)", 0, 1).short == \
        "jit__fused_paged_fn"
    assert t.seconds(r"%paged_attention$") == pytest.approx(1.0)
    assert t.seconds(r"%a$") == pytest.approx(4.0)   # clipped events count whole
    assert t.seconds(r"jit__fused(_paged)?_fn$", "modules") == 4.0
    assert t.top_ops(1) == [["%a", 4.0]]


def test_idle_gaps_are_labelled_by_host_spans():
    gaps = dict(hand_made().idle_gaps())
    # idle: 0-1 (other), 2.5-4 (step covers its middle), 5-9 (wait)
    assert gaps == pytest.approx({"other": 1.0, "step": 1.5,
                                  "wait_arrival": 4.0})


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    if not RECORDED.exists():
        pytest.skip("no recorded trace")
    dst = tmp_path_factory.mktemp("tr") / "w.xplane.pb"
    with gzip.open(RECORDED) as src, open(dst, "wb") as out:
        shutil.copyfileobj(src, out)
    return trace.load(dst)


def test_recorded_trace(recorded):
    t = recorded
    assert t.planes == ["/device:TPU:0"]
    assert 1.5 < t.window_s < 3.0
    busy = t.mean_busy_s()
    assert 0 < busy < t.window_s
    idle = sum(s for _, s in t.idle_gaps(20))
    assert busy + idle == pytest.approx(t.window_s, rel=1e-6)
    assert {k for k, _ in t.idle_gaps(20)} <= {
        "wait_arrival", "submit", "step", "client", "other"}
    # The engine's programs and the Pallas kernels, by the names the
    # chip's trace gives them.
    assert t.seconds(r"jit__fused(_paged)?_fn$", "modules") > 0
    assert t.seconds(r"%paged_attention$") > 0
    assert t.seconds(r"%bgmv$") > 0
    assert t.seconds(r"%paged_attention$") < busy
