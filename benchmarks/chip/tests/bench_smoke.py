"""Smoke-size cells for the benchmark's CPU tests: the StableLM
configuration cut to two layers of width 256, served through the same
harness on the CPU, its output check at the configuration's own limits."""
import copy
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

SMOKE_MODEL = dict(n_layers=2, d_model=256, n_heads=4, n_kv_heads=4, d_ff=512,
                   vocab=512, head_dim=64)
SMOKE_ENGINE = dict(max_seq=128, prefill_chunk=16, decode_block=4)
TRAFFIC = {
    "open": {"arrivals": {"process": "spread", "rate_per_s": 4.0,
                          "order_seed": 1},
             "prompt": {"dist": "lognormal", "median": 10, "sigma": 0.8,
                        "min": 2, "max": 60},
             "output": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                        "min": 4, "max": 40},
             "max_total": 127},
}


def smoke_config(name: str) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg["model"].update(SMOKE_MODEL)
    cfg["engine"].update(SMOKE_ENGINE)
    return cfg


def smoke_cell(config: str, loop: str) -> "harness.Cell":
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return harness.Cell(f"smoke.{loop}", BENCH, 1, config,
                        smoke_config(config), loop, TRAFFIC[loop],
                        spec["end_to_end"], spec["per_layer"])


def run(config: str, loop: str, seed: int, seconds: float = 2.0,
        control=None):
    """(result line, run data) of one smoke run on the CPU."""
    return harness.run_cell(smoke_cell(config, loop), seed, seconds, False,
                            time.perf_counter(), control=control,
                            require_tpu=False)
