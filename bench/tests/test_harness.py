"""A whole run on the CPU at a tiny size, with the look for a chip
skipped: a sound run comes out correct, a run whose engine alters a
token where it is produced does not, the prefix-off path serves the
request mix, and a control run, judged by the bfloat16 reference in the
engine's place, comes out not correct."""
import json
import time

import pytest

import bench.harness.cell as cell
from bench.tests import tiny

SEED = 2**33 + 5


def run(root, name, **kw):
    return cell.run_cell(root, name, SEED, 2.0, False, time.perf_counter(),
                         require_tpu=False, **kw)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    c = json.loads(json.dumps(tiny.CONFIG))
    c["serving"]["engine"] = {"prefix_cache": False}
    req = json.loads(json.dumps(tiny.REQUESTS))
    req["prompt"]["multiple_of"] = 16
    r = tiny.make_root(tmp_path_factory.mktemp("b"), {"ses": tiny.SESSIONS})
    r2 = tiny.make_root(tmp_path_factory.mktemp("c"), {"req": req})
    (r2 / "bench/configs/tiny.json").write_text(json.dumps(c))
    return r, r2


def test_sound_runs_are_correct(root):
    for r, name in ((root[0], "tiny.ses"), (root[1], "tiny.req")):
        res = run(r, name)
        assert res["correct"], res["checks"]
        assert res["failed"] == 0 and res["attempted"] > 3
        assert res["diagnostics"]["compiles_in_window"] == 0
        m = res["metrics"]
        assert set(m) == {"ttft_p50_s", "ttft_p90_s", "tpot_p90_ms",
                          "output_tokens_per_s", "setup_s"}
        assert all(v["value"] > 0 for v in m.values())


def test_altered_token_is_caught(root, monkeypatch):
    from repro.serving.engine import ChameleonEngine
    orig = ChameleonEngine._record_token

    def altered(self, req, pos, tok, now):
        if pos == 1:
            tok = (tok + 1) % self.cfg.vocab_size
        return orig(self, req, pos, tok, now)

    monkeypatch.setattr(ChameleonEngine, "_record_token", altered)
    res = run(root[0], "tiny.ses")
    assert not res["correct"]
    assert res["checks"]["mean_logit_gap"]["value"] > 1e-3


def test_control_is_not_correct(tmp_path):
    """A control run is judged by the bfloat16 reference in the engine's
    place. At this size the two part only on some seeds: on this one the
    control puts another token first where the engine agrees with the
    reference, so with the limit at 0 the run is not correct, while the
    engine's own reading stays within it."""
    root = tiny.make_root(tmp_path, {"ses": tiny.SESSIONS}, limit=0.0)
    res = cell.run_cell(root, "tiny.ses", 12, 2.0, False,
                        time.perf_counter(), require_tpu=False,
                        control="bfloat16")
    d = res["diagnostics"]
    assert not res["correct"]
    assert res["checks"]["mean_logit_gap"]["value"] == \
        d["control"]["mean_logit_gap"] > 0
    assert d["program"]["mean_logit_gap"] == 0
    assert d["control"]["flip_share"] > 0
    assert len(res["gaps"]["control"]) == d["checked_tokens"]


def test_traced_run_reports_layers(root, monkeypatch):
    monkeypatch.setattr(cell, "_peaks", lambda root, kind: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    res = cell.run_cell(root[0], "tiny.ses", SEED, 2.0, True,
                        time.perf_counter(), require_tpu=False)
    m = res["metrics"]
    assert {"queue_wait_p90_s", "adapter_hit_rate", "prefix_token_share",
            "dispatches_per_token"} <= set(m)
    # A CPU trace holds no TPU plane: the device readers find nothing.
    assert "decode_mfu" not in m and "device_idle_share" not in m
    assert res["device"]["window_s"] > 1.9


def test_requests_due_before_the_close_are_all_sent():
    """A step that runs past the close must not drop the requests that
    fell due during it: they are sent late and timed from their due
    time."""
    from types import SimpleNamespace

    from bench.harness.client import Client
    from bench.harness.traffic import Item

    class SlowEngine:
        def __init__(self):
            self.live = []

        def submit(self, req, on_token):
            h = SimpleNamespace(done=False, tokens=[], state=None, cb=on_token)
            self.live.append(h)
            return h

        def busy(self):
            return any(not h.done for h in self.live)

        def step(self):
            time.sleep(0.3)
            for h in self.live:
                h.cb(7)
                h.tokens, h.done = [7], True
                h.state = SimpleNamespace(name="FINISHED")

    mix = SimpleNamespace(kind="requests")
    items = [Item(i, due, [1, 2], 1, 0) for i, due in
             enumerate((0.0, 0.1, 0.25))]
    client = Client(SlowEngine(), mix, items, 64, spans=False)
    client.run(0.3, 2.0)
    assert [g.item.idx for g in client.logs] == [0, 1, 2]
    assert all(g.finished and g.ttft > 0 for g in client.logs)
    assert client.logs[2].late > 0
