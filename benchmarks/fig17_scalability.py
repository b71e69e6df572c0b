"""Figs. 17/18: scalability over model size and memory capacity.

Llama-7B/13B/30B on A100-80G (Fig. 17: normalized P99 + throughput) and
Llama-7B under 24/48/80 GB memory configs (Fig. 18). Paper claims:
Chameleon wins across all sizes (−60 % P99-ish, 1.4–1.9× throughput);
larger memory ⇒ larger win (more room for adapter caching).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.serving import NodeConfig, build_node, synthesize
from repro.serving.cost_model import A100_80G, HW_PRESETS, MODEL_PRESETS
from repro.serving.trace import TraceConfig

from .common import run_system, ttft_slo

NAME = "fig17_scalability"
PAPER_REF = "Figures 17 and 18"

# Load levels per model size (bigger model = slower node = lower RPS).
LOADS = {"llama-7b": (8.0, 12.0, 16.0), "llama-13b": (4.0, 6.0, 8.0),
         "llama-30b": (1.5, 2.5, 3.5)}
N_ADAPTERS = {"llama-7b": 500, "llama-13b": 100, "llama-30b": 10}


def _mem_hw(gb: float):
    return dataclasses.replace(A100_80G, hbm_gb=gb, name=f"a100-{gb:.0f}g")


def run(quick: bool = False):
    duration = 45.0 if quick else 120.0
    rows = []
    # --- Fig 17: model sizes on A100-80G ---
    for model in ("llama-7b", "llama-13b", "llama-30b"):
        loads = LOADS[model][:2] if quick else LOADS[model]
        for level, rps in zip(("low", "med", "high"), loads):
            out = {}
            for system in ("slora", "chameleon"):
                m, *_ = run_system(
                    system, rps, duration=duration,
                    node_kw={"hw": "a100-80g", "model": model,
                             "n_adapters": N_ADAPTERS[model]})
                out[system] = m
            rows.append({
                "figure": "17", "model": model, "load": level, "rps": rps,
                "p99_norm": out["chameleon"].p99_ttft()
                    / max(out["slora"].p99_ttft(), 1e-9),
                "goodput_ratio": out["chameleon"].goodput_tokens_per_s()
                    / max(out["slora"].goodput_tokens_per_s(), 1e-9),
            })
    # --- Fig 18: memory capacities, llama-7b ---
    import repro.serving.systems as sysmod
    for gb in (24.0, 48.0, 80.0):
        hw = _mem_hw(gb)
        sysmod.HW_PRESETS[hw.name] = hw
        HW_PRESETS[hw.name] = hw
        out = {}
        for system in ("slora", "chameleon"):
            m, *_ = run_system(system, 10.0, duration=duration,
                               node_kw={"hw": hw.name, "model": "llama-7b",
                                        "n_adapters": 500})
            out[system] = m
        rows.append({
            "figure": "18", "hbm_gb": gb,
            "p99_norm": out["chameleon"].p99_ttft()
                / max(out["slora"].p99_ttft(), 1e-9),
            "hit_gain": out["chameleon"].cache_stats["hit_rate"]
                - out["slora"].cache_stats["hit_rate"],
        })
    return rows


def validate(rows) -> dict:
    f17 = [r for r in rows if r["figure"] == "17"]
    f18 = sorted((r for r in rows if r["figure"] == "18"),
                 key=lambda r: r["hbm_gb"])
    wins = sum(1 for r in f17 if r["p99_norm"] < 1.0)
    return {
        "chameleon_wins_fraction": round(wins / max(len(f17), 1), 2),
        "p99_norm_by_model": {
            m: round(float(np.mean([r["p99_norm"] for r in f17
                                    if r["model"] == m])), 3)
            for m in ("llama-7b", "llama-13b", "llama-30b")},
        "bigger_memory_bigger_win":
            f18[-1]["p99_norm"] <= f18[0]["p99_norm"] + 0.05,
        "p99_norm_by_mem": {r["hbm_gb"]: round(r["p99_norm"], 3)
                            for r in f18},
    }


def _pick_mesh_shape() -> tuple:
    """Largest parity-grid mesh the host's devices support — CI runs
    with XLA_FLAGS=--xla_force_host_platform_device_count=4 so the
    full (2,2) data×model mesh is exercised there."""
    import jax
    n = len(jax.devices())
    for shape in ((2, 2), (1, 2), (2, 1), (1, 1)):
        if shape[0] * shape[1] <= n:
            return shape
    return (1, 1)


def run_real_engine(n_requests: int = 24, seed: int = 0,
                    quick: bool = False,
                    mesh_shape: tuple | None = None) -> list[dict]:
    """Scalability measured on the *real* engine: one ChameleonEngine
    across N devices vs the same engine single-device.

    The fig10-style paged workload (shared-prefix-heavy, multi-adapter,
    mixed greedy/sampled) runs with the full serving data plane on —
    paged KV, fused hot loop, prefix cache — first with
    ``mesh_shape=None``, then sharded. The only variable is the mesh:
    DESIGN §4's exact-reductions mode makes the sharded arm
    token-identical, asserted per request by submission order.
    ``MemoryPool.check_invariants()`` runs after every engine step.
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import Request, SamplingParams
    from repro.models import api as model_api
    from repro.serving.engine import ChameleonEngine, EngineConfig

    cfg = get_config("chameleon-llama-7b").reduced()
    params = model_api.init_params(cfg, jax.random.PRNGKey(seed),
                                   jnp.float32)
    if quick:
        n_requests = min(n_requests, 12)
    mesh_shape = mesh_shape or _pick_mesh_shape()

    # Shared-prefix-heavy multi-adapter trace (the prefix cache must
    # have something to hit) with real token ids; every 3rd request
    # samples stochastically so the sharded sampler is exercised too.
    rng = np.random.default_rng(seed)
    pres = [rng.integers(3, 256, size=40).tolist() for _ in range(2)]
    specs = []
    for i in range(n_requests):
        prompt = (pres[i % 2]
                  + rng.integers(3, 256,
                                 size=int(rng.integers(4, 13))).tolist())
        specs.append((prompt, int(rng.integers(8, 24)),
                      int(rng.integers(0, 8)),
                      SamplingParams(temperature=0.8, top_k=8, seed=i)
                      if i % 3 == 2 else None))

    rows = []
    tokens_by_mode = {}
    for mode, ms in (("single", None), ("mesh", mesh_shape)):
        eng = ChameleonEngine(cfg, params, EngineConfig(
            max_slots=4, max_len=128, n_lora_slots=4, n_adapters=8,
            seed=seed, paged=True, fused_hotloop=True,
            prefix_cache=True, async_load=False,
            queued_prefetch=False, histogram_prefetch=False,
            mesh_shape=ms))
        handles = [eng.submit(Request(input_len=len(p), output_len=o,
                                      adapter_id=a, prompt=list(p)),
                              sampling=sp)
                   for p, o, a, sp in specs]
        t0 = time.perf_counter()
        steps = 0
        while eng.busy() and steps < 50_000:
            eng.step()
            eng.pool.check_invariants(
                free_page_ids=getattr(eng, "free_pages", None))
            steps += 1
        wall = time.perf_counter() - t0
        # req_ids are globally monotonic across engine instances:
        # compare by submission order via the handles.
        streamed = [h.tokens for h in handles]
        tokens_by_mode[mode] = streamed
        n_tok = sum(len(t) for t in streamed)
        ss = eng.shard_stats()
        rows.append({
            "mode": mode,
            "mesh_shape": "x".join(map(str, ms)) if ms else "none",
            "n_devices": ss.get("n_devices", 1),
            "submitted": n_requests,
            "completed": len(eng.completed),
            "steps": steps,
            "wall_s": round(wall, 3),
            "tokens_per_s": round(n_tok / max(wall, 1e-9), 1),
            "prefix_hit_rate": eng.stats()["prefix_hit_rate"],
            "tokens_identical_to_single":
                tokens_by_mode["single"] == streamed,
            "per_shard_pages_used": ss.get("per_shard_pages_used", []),
            "per_shard_lora_slot_bytes":
                ss.get("per_shard_lora_slot_bytes", 0),
        })
    return rows


def validate_real_engine(rows) -> dict:
    single = next(r for r in rows if r["mode"] == "single")
    mesh = next(r for r in rows if r["mode"] == "mesh")
    return {
        # Both arms must fully drain — equal truncation is not success.
        "all_completed":
            single["completed"] == single["submitted"]
            and mesh["completed"] == mesh["submitted"],
        # The acceptance claim (DESIGN §4): the sharded data plane is
        # bit-token-identical to single-device, greedy and sampled,
        # with fused hot loop + prefix cache + paged KV all enabled.
        "tokens_identical": bool(mesh["tokens_identical_to_single"]),
        "mesh_shape": mesh["mesh_shape"],
        "n_devices": mesh["n_devices"],
        "throughput_ratio_mesh_over_single": round(
            mesh["tokens_per_s"] / max(single["tokens_per_s"], 1e-9), 3),
        "prefix_hit_rate_mesh": mesh["prefix_hit_rate"],
    }


if __name__ == "__main__":
    import argparse

    from .common import emit_json

    ap = argparse.ArgumentParser()
    ap.add_argument("--real-engine", action="store_true",
                    help="A/B the real engine single-device vs "
                         "mesh-sharded (token parity + throughput) "
                         "instead of the simulator sweep")
    ap.add_argument("--mesh", metavar="DxM", default=None,
                    help="mesh shape for the sharded arm, e.g. 2x2 "
                         "(default: largest the host devices support)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write {name, paper_ref, rows, validated} "
                         "to PATH (CI schema)")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    if args.real_engine:
        ms = (tuple(int(x) for x in args.mesh.split("x"))
              if args.mesh else None)
        rows = run_real_engine(quick=args.quick, mesh_shape=ms)
        validated = validate_real_engine(rows)
        variant = f"{NAME}_sharded_engine"
    else:
        rows = run(quick=True)
        validated = validate(rows)
        variant = NAME
    for r in rows:
        print({k: (round(v, 3) if isinstance(v, float) else v)
               for k, v in r.items()})
    print(validated)
    if args.json:
        print("wrote", emit_json(args.json, variant, PAPER_REF, rows,
                                 validated))
