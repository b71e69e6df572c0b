"""Assert benchmark result JSONs match the CI schema.

Usage: ``python -m benchmarks.check_json results/*.json``

Schema (written by ``common.emit_json``): a document is an object with
``name`` (str), ``paper_ref`` (str), ``rows`` (non-empty list of flat
dicts with consistent keys and JSON-scalar/list values), ``validated``
(dict of derived claims). Exit code is non-zero on any violation, so
the bench-smoke CI job fails when an entrypoint silently changes its
output shape.
"""
from __future__ import annotations

import json
import sys

SCALARS = (str, int, float, bool, type(None))

# Benchmarks whose ``validated`` dict the CI jobs consume by key: a
# missing key here means an entrypoint silently dropped an acceptance
# claim, which must fail the schema check, not the consumer.
REQUIRED_VALIDATED = {
    "decode_hotloop": {
        "tokens_identical", "speedup_tokens_per_sec", "speedup_ge_2x",
        "dispatch_ratio", "dispatch_ratio_ge_2x", "kv_donated",
        "host_sync_fraction_seed", "host_sync_fraction_fused",
    },
    "spec_decode": {
        "tokens_identical", "spec_accept_rate",
        "speedup_tokens_per_sec", "speedup_ge_1_3x",
        "dispatches_per_token_nonspec", "dispatches_per_token_spec",
    },
    "fig10_latency_load_paged_ab": {"all_completed", "tokens_identical"},
    "fig10_latency_load_spec_ab": {
        "all_completed", "tokens_identical", "spec_accept_rate"},
    "fig10_latency_load_loading_ab": {
        "all_completed", "overlap_beats_sync_p99_ttft"},
    "fig10_latency_load_hotloop_ab": {"all_completed",
                                      "tokens_identical"},
    "fig10_latency_load_prefix_ab": {
        "all_completed", "tokens_identical", "prefix_hit_rate",
        "prefix_reduces_p99_ttft"},
    "fig17_scalability_sharded_engine": {
        "all_completed", "tokens_identical", "mesh_shape", "n_devices",
        "throughput_ratio_mesh_over_single"},
    "gateway": {"all_completed", "fair_tenant_p99_improves"},
    "disagg_interference": {"all_completed", "tokens_identical",
                            "handoffs", "prefill_util", "decode_util"},
}


def _flat(d: dict, what: str) -> list[str]:
    errs = []
    for k, v in d.items():
        if not isinstance(k, str):
            errs.append(f"{what}: non-string key {k!r}")
        if isinstance(v, dict):
            errs.append(f"{what}[{k}]: nested dict not allowed")
        elif isinstance(v, list):
            if not all(isinstance(x, SCALARS) for x in v):
                errs.append(f"{what}[{k}]: list of non-scalars")
        elif not isinstance(v, SCALARS):
            errs.append(f"{what}[{k}]: bad value type {type(v).__name__}")
    return errs


def check_doc(doc, path: str) -> list[str]:
    errs = []
    if not isinstance(doc, dict):
        return [f"{path}: top level must be an object"]
    for key, typ in (("name", str), ("paper_ref", str), ("rows", list),
                     ("validated", dict)):
        if not isinstance(doc.get(key), typ):
            errs.append(f"{path}: missing/mistyped key {key!r} "
                        f"(want {typ.__name__})")
    if errs:
        return errs
    if not doc["rows"]:
        errs.append(f"{path}: rows is empty")
        return errs
    keys0 = set(doc["rows"][0])
    for i, row in enumerate(doc["rows"]):
        if not isinstance(row, dict):
            errs.append(f"{path}: rows[{i}] is not an object")
            continue
        if set(row) != keys0:
            errs.append(f"{path}: rows[{i}] keys {sorted(set(row))} "
                        f"differ from rows[0] keys {sorted(keys0)}")
        errs.extend(_flat(row, f"{path}: rows[{i}]"))
    errs.extend(_flat(doc["validated"], f"{path}: validated"))
    required = REQUIRED_VALIDATED.get(doc["name"], set())
    missing = required - set(doc["validated"])
    if missing:
        errs.append(f"{path}: validated missing required keys "
                    f"{sorted(missing)}")
    return errs


def main(paths: list[str]) -> int:
    if not paths:
        print("usage: python -m benchmarks.check_json FILE.json ...",
              file=sys.stderr)
        return 2
    failures = []
    for path in paths:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as exc:
            failures.append(f"{path}: unreadable ({exc})")
            continue
        errs = check_doc(doc, path)
        failures.extend(errs)
        if not errs:
            print(f"ok: {path} ({doc['name']}, {len(doc['rows'])} rows)")
    for msg in failures:
        print("SCHEMA ERROR:", msg, file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
