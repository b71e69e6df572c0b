"""The CI schema gate itself: ``benchmarks.check_json`` must reject
malformed documents and documents that silently drop a required
acceptance claim — otherwise a benchmark entrypoint can change shape
and the bench-smoke job keeps passing on nothing.
"""
import json

import pytest

from benchmarks.check_json import REQUIRED_VALIDATED, check_doc, main


def good_doc(name="fig10_latency_load_prefix_ab"):
    validated = {k: True for k in REQUIRED_VALIDATED.get(name, set())}
    validated.setdefault("extra_claim", 1.5)
    return {
        "name": name,
        "paper_ref": "Figures 10, 11, 12",
        "rows": [{"mode": "off", "p99_ttft": 1.0},
                 {"mode": "on", "p99_ttft": 0.5}],
        "validated": validated,
    }


class TestCheckDoc:
    def test_well_formed_doc_passes(self):
        assert check_doc(good_doc(), "x.json") == []

    @pytest.mark.parametrize("name", sorted(REQUIRED_VALIDATED))
    def test_each_missing_required_key_rejected(self, name):
        """Dropping any single required validated key must fail the
        schema check for every registered benchmark."""
        for key in sorted(REQUIRED_VALIDATED[name]):
            doc = good_doc(name)
            del doc["validated"][key]
            errs = check_doc(doc, "x.json")
            assert errs and key in errs[0], (
                f"{name}: missing {key!r} not rejected: {errs}")

    def test_unregistered_name_needs_no_keys(self):
        doc = good_doc("some_future_benchmark")
        doc["validated"] = {"whatever": 1}
        assert check_doc(doc, "x.json") == []

    @pytest.mark.parametrize("mutate", [
        lambda d: d.pop("name"),
        lambda d: d.pop("rows"),
        lambda d: d.__setitem__("rows", []),
        lambda d: d.__setitem__("validated", [1, 2]),
        lambda d: d["rows"].append({"other": 1}),        # key drift
        lambda d: d["rows"].append({"mode": {"a": 1},    # nested dict
                                    "p99_ttft": 1.0}),
    ])
    def test_malformed_docs_rejected(self, mutate):
        doc = good_doc()
        mutate(doc)
        assert check_doc(doc, "x.json"), "malformed doc passed"

    def test_non_object_rejected(self):
        assert check_doc([1, 2, 3], "x.json")


class TestMain:
    def test_main_flags_bad_file(self, tmp_path, capsys):
        ok = tmp_path / "ok.json"
        ok.write_text(json.dumps(good_doc()))
        bad = tmp_path / "bad.json"
        doc = good_doc()
        del doc["validated"]["tokens_identical"]
        bad.write_text(json.dumps(doc))
        assert main([str(ok)]) == 0
        assert main([str(ok), str(bad)]) == 1
        assert "tokens_identical" in capsys.readouterr().err

    def test_main_unreadable_and_usage(self, tmp_path):
        assert main([]) == 2
        garbled = tmp_path / "garbled.json"
        garbled.write_text("{not json")
        assert main([str(garbled)]) == 1


class TestRunFailsLoudly:
    """``benchmarks.run`` prints a failing module's row, keeps going,
    then exits non-zero: an error is never recorded as a result."""

    def test_module_error_fails_the_run(self, monkeypatch, capsys):
        import types

        from benchmarks import run

        def boom(quick):
            raise RuntimeError("kaput")

        ok = types.SimpleNamespace(NAME="ok_mod", run=lambda quick: [])
        bad = types.SimpleNamespace(NAME="bad_mod", run=boom)
        monkeypatch.setattr(run, "MODULES", (bad, ok))
        monkeypatch.setattr(run, "save_rows", lambda name, rows: None)
        monkeypatch.setattr("sys.argv", ["run"])
        with pytest.raises(SystemExit) as exc:
            run.main()
        assert exc.value.code not in (0, None)
        out = capsys.readouterr().out
        assert "bad_mod," in out and "kaput" in out and "ok_mod," in out
