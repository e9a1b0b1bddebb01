#!/usr/bin/env python3
"""Record ``data/tiny_serve_v5e.xplane.pb``: a profiler trace of the
serving engine at a tiny width, with the program's ``serve.*`` spans.

    python3 benchmarks/chip/tests/record_serve_trace.py --out <file>

Run it on one TPU v5e.  One layer of width 128, 8 slots, prefill chunks
of 4, decode blocks of 2, greedy.  Inside the ``bench.traced`` span: two
requests are submitted (prompts of 7 and 3 tokens, so feeds of 6 = 4 + 2
and 2 prompt tokens, 4 new tokens each), the engine ticks in ``bench.tick``
spans until both are done, flushes, and sleeps 5 ms in a
``bench.wait_arrival`` span.  To keep the file small, Python calls are
not traced and the ``/host:metadata`` plane (the modules' HLO protos,
which the reduction does not read) is dropped from the file.  Prints the
device, the file's size, the engine's counters and the requests' stamps
as JSON.
"""
import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT / "src"))


def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        out |= (b & 0x7F) << shift
        i += 1
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, wire type, start, end) of each top-level field of a
    serialized protobuf message."""
    i = 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            _, i = _varint(buf, i)
        elif kind == 1:
            i += 8
        elif kind == 2:
            n, i = _varint(buf, i)
            i += n
        elif kind == 5:
            i += 4
        else:
            raise ValueError(f"unexpected wire type {kind}")
        yield key >> 3, kind, start, i


def drop_planes(xspace: bytes, names) -> bytes:
    """The serialized ``XSpace`` without the planes named in ``names``
    (``XSpace.planes`` is field 1, ``XPlane.name`` field 2)."""
    out = bytearray()
    for num, kind, start, end in _fields(xspace):
        if num == 1 and kind == 2:
            _, body = _varint(xspace, start + 1)
            plane = xspace[body:end]
            name = next((plane[_varint(plane, s + 1)[1]:e].decode()
                         for n, k, s, e in _fields(plane)
                         if n == 2 and k == 2), "")
            if name in names:
                continue
        out += xspace[start:end]
    return bytes(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation
    from repro.configs.base import ArchConfig
    from repro.models import model as model_lib
    from repro.serve import ServeEngine

    cfg = ArchConfig(name="serve-trace-tiny", family="dense", n_layers=1,
                     d_model=128, n_heads=2, n_kv_heads=2, d_ff=256,
                     vocab=256, head_dim=64)
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.bfloat16)
    eng = ServeEngine(cfg, params, n_slots=8, max_seq=32,
                      dtype=jnp.bfloat16, prefill_chunk=4, decode_block=2)
    eng.warmup()
    before = dict(eng.health()["counters"])

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    logdir = tempfile.mkdtemp(prefix="serve_trace_")
    jax.profiler.start_trace(logdir, profiler_options=opts)
    with TraceAnnotation("bench.traced"):
        uids = [eng.submit(np.arange(1, 8), max_new=4),
                eng.submit(np.arange(1, 4), max_new=4)]
        while any(eng.status(u) != "done" for u in uids):
            with TraceAnnotation("bench.tick"):
                eng.decode_block_step()
        eng.flush()
        with TraceAnnotation("bench.wait_arrival"):
            time.sleep(0.005)
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
        "counters": {k: after[k] - before[k] for k in after},
        "times": {u: eng.request_times(u) for u in uids}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
