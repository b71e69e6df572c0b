"""A tiny checkout for the CPU tests: the real harness and readers, a
two-layer model of the same family as the cells, and short mixes."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

CONFIG = {
    "name": "tiny", "source": "test", "registry": "internlm2-1.8b",
    "reference": "dense_gqa", "reference_options": {"qk_norm": False},
    "config": {"hidden_size": 64, "intermediate_size": 128,
               "num_attention_heads": 4, "num_hidden_layers": 2,
               "num_key_value_heads": 2, "head_dim": 16,
               "rms_norm_eps": 1e-5, "rope_theta": 10000,
               "tie_word_embeddings": False, "vocab_size": 256},
    "reduced": {}, "assumed": {}, "deployment": "test",
    "serving": {"r_max": 16, "n_lora_slots": 2, "max_slots": 2,
                "max_len": 160, "page_size": 16},
}

REQUESTS = {
    "kind": "requests", "rate_per_s": 4.0, "schedule_seed": 1,
    "prompt": {"median": 40, "sigma": 0.5, "min": 8, "max": 100},
    "output": {"median": 6, "sigma": 0.8, "min": 1, "max": 40},
    "adapters": {"count": 5, "ranks": [2, 4, 8, 16], "alpha": 1.0},
}

SESSIONS = {
    "kind": "sessions", "session_rate_per_s": 2.0, "system_prompt": 32,
    "schedule_seed": 2,
    "turns": {"min": 2, "max": 3}, "think_s": 0.05,
    "user": {"median": 10, "sigma": 0.5, "min": 4, "max": 24},
    "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 16},
    "adapters": {"count": 2, "ranks": [4, 8], "alpha": 1.0},
}


def make_root(tmp: Path, mixes: dict, qk_norm: bool = False,
              limit: float = 1e-3) -> Path:
    """A checkout under ``tmp`` with one cell per mix, all on CONFIG."""
    root = Path(tmp)
    (root / "bench").mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "references"):
        shutil.copytree(BENCH / sub, root / "bench" / sub,
                        dirs_exist_ok=True)
    shutil.copy(BENCH / "peaks.json", root / "bench" / "peaks.json")
    cfg = json.loads(json.dumps(CONFIG))
    cfg["reference_options"]["qk_norm"] = qk_norm
    for sub in ("configs", "traffic", "checks"):
        (root / "bench" / sub).mkdir(exist_ok=True)
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = []
    # The prefix cache's reader, kept for a later cell, is read here.
    if all(m["name"] != "prefix_token_share" for m in bench["per_layer"]):
        bench["per_layer"].append({
            "name": "prefix_token_share", "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "prefix cache",
            "moves": "ttft_p50_s"})
    for name, mix in mixes.items():
        (root / f"bench/traffic/{name}.json").write_text(json.dumps(mix))
        (root / f"bench/checks/tiny.{name}.json").write_text(json.dumps(
            {"limits": {"mean_logit_gap": limit}}))
        bench["workloads"].append({"name": f"tiny.{name}", "config": "tiny",
                                   "traffic": name, "chips": 1,
                                   "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
