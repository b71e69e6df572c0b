"""Engine spans (``serving/spans.py``): kept exactly while a profiler
capture runs, nested under the engine step, mirrored into the capture
as host annotations, bounded, cleared by ``reset_stats``, and without
effect on the tokens served."""
import collections
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Request
from repro.models import api
from repro.serving.engine import ChameleonEngine, EngineConfig
from repro.serving.spans import NULL_SPAN, SPANS, SpanRecorder

STEP_PHASES = {"engine.poll_loads", "engine.schedule", "engine.prefill",
               "engine.kv_place", "engine.decode", "engine.readback",
               "engine.idle_wait", "adapter.load"}


@contextlib.contextmanager
def capture(path):
    """A profiler capture as the benchmark's traced run takes it."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_events(path) -> list:
    """(name, stats) of every host-plane event of the capture."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(sorted(path.rglob("*.xplane.pb"))[-1]))
    return [(e.name, dict(e.stats)) for p in pd.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events]


@pytest.fixture(autouse=True)
def empty_recorder():
    SPANS.clear()
    yield
    SPANS.clear()


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("chameleon-llama-7b").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def serve(small_model, traced_dir=None, clock=None, n=6, **ecfg):
    """A tiny engine with more adapters than slots, run dry; the tokens
    each request streamed, and the engine."""
    cfg, params = small_model
    eng = ChameleonEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=96, n_lora_slots=2, n_adapters=6, seed=0,
        **ecfg), clock=clock)
    rng = np.random.default_rng(3)
    handles = [eng.submit(Request(
        input_len=int(rng.integers(4, 24)),
        output_len=int(rng.integers(2, 8)), adapter_id=i % 6))
        for i in range(n)]
    with capture(traced_dir) if traced_dir else contextlib.nullcontext():
        eng.run_until_drained()
    return [h.tokens for h in handles], eng


def test_off_gives_the_shared_null_span_and_keeps_nothing():
    assert not SPANS.on
    with SPANS.span("engine.step", rows=2) as sp:
        assert sp is NULL_SPAN and not sp and sp.id is None
        sp.set(K=4)
    assert SPANS.record("adapter.load", 0.0, 1.0) is None
    assert SPANS.spans == [] and SPANS.dropped == 0


def test_off_site_costs_one_check():
    checks = []
    rec = SpanRecorder(switch=lambda: checks.append(1) or False)
    assert rec.span("engine.decode", K=8, spec=False) is NULL_SPAN
    assert len(checks) == 1 and rec.spans == []


def test_buffer_is_bounded_and_counts_what_it_dropped():
    rec = SpanRecorder(capacity=3, switch=lambda: True)
    for i in range(5):
        with rec.span("engine.step", i=i):
            pass
    rec.record("request.queued", 0.0, 1.0, req_id=7)
    assert [s.attrs["i"] for s in rec.spans] == [0, 1, 2]
    assert rec.dropped == 3
    rec.clear()
    assert rec.spans == [] and rec.dropped == 0


def test_nesting_and_parents_under_a_capture(tmp_path):
    with capture(tmp_path):
        assert SPANS.on
        with SPANS.span("engine.step") as step:
            with SPANS.span("engine.decode", K=4) as dec:
                assert SPANS.current() == dec.id
                assert SPANS.outermost() == step.id
            SPANS.record("adapter.load", 1.0, 2.0, parent=SPANS.outermost())
    assert not SPANS.on
    by = {s.name: s for s in SPANS.spans}
    assert by["engine.step"].parent is None
    assert by["engine.decode"].parent == by["engine.step"].id
    assert by["adapter.load"].parent == by["engine.step"].id
    assert by["engine.step"].start <= by["engine.decode"].start
    assert by["engine.decode"].end <= by["engine.step"].end


def test_mirrored_annotations_carry_name_and_attributes(tmp_path):
    with capture(tmp_path):
        with SPANS.span("engine.prefill", B=2, S=64,
                        req_ids=[4, 5]) as sp:
            sp.set(extra=3)
        SPANS.record("request.decode", 0.0, 1.0, req_id=4)
    events = dict(host_events(tmp_path))
    assert events["engine.prefill"] == {
        "B": 2, "S": 64, "req_ids": "[4, 5]", "extra": 3}
    assert "request.decode" not in events     # held times: not mirrored


def test_engine_spans_nest_under_the_step(small_model, tmp_path):
    _, eng = serve(small_model, tmp_path)
    spans = SPANS.spans
    by_id = {s.id: s for s in spans}
    names = collections.Counter(s.name for s in spans)
    for name in ("engine.step", "engine.schedule", "engine.prefill",
                 "engine.kv_place", "engine.decode", "engine.readback",
                 "adapter.load"):
        assert names[name] > 0, names
    for s in spans:
        if s.name in STEP_PHASES:
            assert by_id[s.parent].name == "engine.step", s
        assert s.end >= s.start
    steps = [s for s in spans if s.name == "engine.step"]
    assert all("K" in s.attrs and "rows" in s.attrs for s in steps)
    # Every step's phases are mirrored into the capture by name.
    mirrored = collections.Counter(n for n, _ in host_events(tmp_path))
    for name in ("engine.step", "engine.prefill", "engine.kv_place",
                 "engine.decode", "engine.readback"):
        assert mirrored[name] == names[name], name
    assert eng.stats()["completed"] == 6


def test_kv_place_dispatches_one_program_with_the_prefix_cache_off(
        small_model, tmp_path):
    """Paged placement without the prefix cache writes every placed
    row's pages in one device program."""
    _, eng = serve(small_model, tmp_path, prefix_cache=False)
    places = [s for s in SPANS.spans if s.name == "engine.kv_place"]
    assert places and eng.stats()["completed"] == 6
    assert all(s.attrs["programs"] == 1 and s.attrs["pages"] >= 1
               for s in places), [s.attrs for s in places]


def test_request_spans_share_the_request_id(small_model, tmp_path):
    _, eng = serve(small_model, tmp_path)
    by_id = {s.id: s for s in SPANS.spans}
    req = collections.defaultdict(dict)
    for s in SPANS.spans:
        if s.name.startswith("request."):
            req[s.req_id][s.name] = s
    assert set(req) == {r.req_id for r in eng.completed}
    for r in eng.completed:
        q, p, d = (req[r.req_id][n] for n in (
            "request.queued", "request.prefill", "request.decode"))
        # Request times are the engine clock plus its base.
        assert q.start == pytest.approx(eng.t0 + r.arrival_time)
        assert q.end == p.start and p.end == d.start
        assert d.end == pytest.approx(eng.t0 + r.finish_time)
        assert q.attrs["adapter_wait"] == r.adapter_load_wait
        assert d.attrs["tokens"] == r.output_len
        served_by = by_id[p.parent]
        assert served_by.name == "engine.prefill"
        assert r.req_id in served_by.attrs["req_ids"]
    assert any(q["request.queued"].attrs["adapter_wait"] > 0
               for q in req.values())


def test_tokens_identical_with_and_without_a_capture(small_model, tmp_path):
    plain, _ = serve(small_model)
    assert SPANS.spans == []
    traced, _ = serve(small_model, tmp_path)
    assert SPANS.spans
    assert traced == plain


def test_reset_stats_clears_and_injected_clock_records_nothing(
        small_model, tmp_path):
    _, eng = serve(small_model, tmp_path)
    assert SPANS.spans
    eng.reset_stats()
    assert SPANS.spans == [] and SPANS.dropped == 0
    t = [0.0]

    def clock():
        t[0] += 0.01
        return t[0]
    serve(small_model, tmp_path / "virtual", clock=clock)
    assert SPANS.spans == []
