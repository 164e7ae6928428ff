"""Tiny cells for the CPU tests: the benchmark's two engines (dense and
the sharded cohort) at sizes a test run holds, written into a scratch root
laid out like the checkout (``BENCHMARK.json``, ``fedbench/...``)."""
from __future__ import annotations

import json
from pathlib import Path

# the program and the reference agree to ~1e-7 on the CPU (float32 at
# full precision on both sides); 1e-4 leaves three decades of room
LIMITS = {"loss1": 1e-4, "loss": 1e-4, "weights1": 1e-4, "weights": 1e-4,
          "scores": 1e-4, "scores1": 1e-4,
          "update1": 1e-4, "updateN": 1e-4}

CNN = {"name": "tiny-cnn", "family": "cnn", "num_layers": 3, "d_model": 0,
       "image_size": 16, "image_channels": 3, "cnn_channels": [8, 16, 16],
       "cnn_hidden": 32, "num_classes": 10, "dtype": "float32"}
FED = {"score_power": 4.0, "score_decay": 0.5, "power_warmup_rounds": 2,
       "aggregator": "fedtest", "attack": "random_weights",
       "selector": "rotating"}

WORKLOADS = {
    "tiny-cnn": {
        "config": "tiny-cnn", "engine": "dense", "generator": "cifar_like",
        "traffic": {"num_users": 4, "num_samples": 600, "global_test": 64,
                    "server_frac": 0.1, "holdout_frac": 0.2,
                    "min_classes": 2, "max_classes": 6, "image_size": 16,
                    "channels": 3, "num_classes": 10, "noise": 0.9,
                    "shift": 2, "smooth": 2, "stack_rows": 256},
        "federation": dict(FED, num_users=4, num_testers=2, num_malicious=1,
                           local_steps=2, participation=1.0),
        "train": {"optimizer": "sgd", "lr": 0.05, "batch_size": 8},
        "eval_batch": 16, "sample_shape": [16, 16, 3], "reference_group": 4,
        "check": {"rounds": 3, "limits": LIMITS}},
    "tiny-cohort": {
        "config": "tiny-cnn", "engine": "population",
        "generator": "synthetic_population",
        "traffic": {"num_clients": 64, "per_client": 16, "image_size": 16,
                    "channels": 3, "num_classes": 10, "noise": 0.9,
                    "global_test": 32, "server": 16},
        "federation": dict(FED, num_users=64, num_testers=3,
                           num_malicious=10, local_steps=2,
                           participation=0.125),
        "cohort": 8, "crosstest_block": 4,
        "train": {"optimizer": "sgd", "lr": 0.05, "batch_size": 4},
        "eval_batch": 8, "sample_shape": [16, 16, 3], "reference_group": 3,
        "reference_eval_chunk": 8,
        "check": {"rounds": 3, "limits": LIMITS}},
}


def make_root(root: Path, chips: int = 1) -> Path:
    """Write the tiny cells' files under ``root`` and return it."""
    (root / "fedbench" / "workloads").mkdir(parents=True, exist_ok=True)
    (root / "fedbench" / "configs").mkdir(parents=True, exist_ok=True)
    for cfg in (CNN,):
        (root / "fedbench" / "configs" / f"{cfg['name']}.json").write_text(
            json.dumps(cfg))
    for name, w in WORKLOADS.items():
        (root / "fedbench" / "workloads" / f"{name}.json").write_text(
            json.dumps(w))
    bench = {
        "configs": [{"name": c["name"],
                     "file": f"fedbench/configs/{c['name']}.json"}
                    for c in (CNN,)],
        "workloads": [{"name": n, "config": w["config"], "traffic": n,
                       "chips": chips if w["engine"] == "population" else 1}
                      for n, w in WORKLOADS.items()],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "rounds_per_s", "unit": "rounds/s"}],
        "per_layer": [{"name": "device_idle_share", "unit": "%"},
                      {"name": "round_mfu", "unit": "%"}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
