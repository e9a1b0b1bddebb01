#!/usr/bin/env python3
"""Record ``data/tiny_moe_v5e.xplane.pb``: a profiler trace of the serving
engine on the DeepSeek-MoE chip-share configuration at a tiny width.

    python3 benchmarks/chip/tests/record_moe_trace.py --out <file>

Run it on one TPU v5e.  The model is ``configs/deepseek-moe-16b-ep8.json``
with the sizes of ``TINY_MODEL`` (a dense layer and two MoE layers of
width 256, 4 of 16 routed experts of width 192 held, top 6, 2 shared), the
engine ``TINY_ENGINE`` (8 slots, prefill chunks of 4, decode blocks of 2),
greedy.  At these sizes the TPU compiler lays the expert layer out as at
the cell's: one ``[held, rows, expert width]`` fusion and one float32
``[rows, width]`` fusion per MoE layer and step.  Inside the
``bench.traced`` span two requests are submitted (prompts of 7 and 3
tokens, 4 new tokens each) and the engine ticks in ``bench.tick`` spans
until both are done, then flushes.  Python calls are not traced and the
``/host:metadata`` plane is dropped from the file.  Prints the device,
the file's size and the engine's counters as JSON.
"""
import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

CONFIG = HERE.parent / "configs" / "deepseek-moe-16b-ep8.json"
TINY_ENGINE = {"n_slots": 8, "max_seq": 32, "prefill_chunk": 4,
               "decode_block": 2}


def tiny_model() -> dict:
    model = json.loads(CONFIG.read_text())["model"]
    return {**model, "n_layers": 3, "d_model": 256, "n_heads": 2,
            "n_kv_heads": 2, "head_dim": 128, "d_ff": 512, "vocab": 512,
            "moe": {**model["moe"], "n_experts": 16, "experts_held": 4,
                    "expert_d_ff": 192}}


TINY_MODEL = tiny_model()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation
    from record_serve_trace import drop_planes
    from repro.configs.base import ArchConfig
    from repro.models import model as model_lib
    from repro.serve import ServeEngine

    cfg = ArchConfig(**TINY_MODEL)
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.bfloat16)
    eng = ServeEngine(cfg, params, dtype=jnp.bfloat16, **TINY_ENGINE)
    eng.warmup()
    before = eng.health()["counters"]

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    logdir = tempfile.mkdtemp(prefix="moe_trace_")
    jax.profiler.start_trace(logdir, profiler_options=opts)
    with TraceAnnotation("bench.traced"):
        uids = [eng.submit(np.arange(1, 8), max_new=4),
                eng.submit(np.arange(1, 4), max_new=4)]
        while any(eng.status(u) != "done" for u in uids):
            with TraceAnnotation("bench.tick"):
                eng.decode_block_step()
        eng.flush()
    jax.profiler.stop_trace()

    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    with open(path, "rb") as f:
        raw = f.read()
    with open(args.out, "wb") as f:
        f.write(drop_planes(raw, {"/host:metadata"}))
    shutil.rmtree(logdir, ignore_errors=True)
    after = eng.health()["counters"]
    dev = jax.devices()[0]
    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "bytes": os.path.getsize(args.out),
        "counters": {k: (after[k] - before[k]
                         if not isinstance(after[k], list) else
                         [a - b for a, b in zip(after[k], before[k])])
                     for k in after}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
