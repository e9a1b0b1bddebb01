"""One benchmark run: a cell of ``BENCHMARK.json`` on one seed.

Everything that belongs to a configuration, a traffic mix or a metric is
found by name:

* ``BENCHMARK.json`` names the cell's configuration file and traffic mix;
* ``traffic/<traffic>.json`` holds the mix's parameters (``loadgen``),
  among them the name of its arrival process (``arrivals/<process>.py``);
* the configuration file holds the model's sizes, the keyword arguments
  of the program's ``ServeEngine`` (``engine``) and, where the model is
  served through a weight plan, of ``decode_exec_config`` (``dispatch``),
  the name of its plain reference (``references/<reference>.py``), the
  limits of the output check and the settings of its control;
* ``metrics/<metric>.py`` holds each metric's reader, ``read(run)``, which
  returns the metric's value or None where it finds nothing to read.

A run: refuse without a TPU; make the weights on the device from the
seed; build the program's ``ServeEngine`` and warm its shapes; then for
``seconds`` drive only the served path, ``ServeEngine.submit`` for each arrival and
``decode_block_step`` ticks; stamp every token when the tick that credits
it returns; after the close, check a sample of the finished requests
against the plain reference.  Set-up is everything from process start to
the window's start.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import loadgen  # noqa: E402
import stats  # noqa: E402

# seconds of the traced sub-window, centred in the measured window
TRACE_S = 5.0
# after the close, the longest wait for the first token of every request
# due in the window
DRAIN_S = 60.0
TERMINAL = ("done", "cancelled", "deadline_missed", "failed", "shed")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg) -> None:
    if not isinstance(msg, str):
        msg = json.dumps(msg)
    print(msg, flush=True)


def load_module(path: Path):
    name = "bench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    dir: Path          # the benchmark's directory: configs, traffic, ...
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its files read.
    Traffic mixes, readers and references are found under the first of
    the benchmark's ``paths``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    bench = root / spec["paths"][0]
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    wl = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]

    def applies(m):
        return name in m.get("workloads", [name])

    return Cell(name=name, dir=bench, chips=int(wl["chips"]),
                config_name=wl["config"],
                config=json.loads((root / entry["file"]).read_text()),
                traffic_name=wl["traffic"],
                traffic=json.loads(
                    (bench / "traffic" / f"{wl['traffic']}.json").read_text()),
                end_to_end=[m for m in spec["end_to_end"] if applies(m)],
                per_layer=[m for m in spec["per_layer"] if applies(m)])


def chips(n: int):
    """The devices, where JAX finds at least ``n`` TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips; JAX found {len(devs)}")
    return devs


def peaks_for(kind: str) -> Dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; peaks.json has "
                       f"{sorted(table)}")
    return table[kind]


def seed_key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              seed % 2 ** 64 >> 32)


def arch_config(model: Dict):
    from repro.configs.base import ArchConfig, SparsityConfig
    model = dict(model)
    sp = model.pop("sparsity", None)
    return ArchConfig(**model, sparsity=SparsityConfig(**(sp or {})))


@dataclass
class Built:
    params: Dict
    engine: object
    reference: object
    timings: Dict[str, float]


def build(cell: Cell, seed: int, control: Optional[Dict] = None) -> Built:
    """Weights from the seed, the engine, and its warmed shapes.
    ``control`` (the output check's control) holds settings that
    override those of the configuration's ``engine`` and ``dispatch``
    groups, under the same two keys."""
    import jax
    import jax.numpy as jnp
    from repro.serve.engine import ServeEngine, decode_exec_config

    c = cell.config
    control = control or {}
    eng = {**c["engine"], **control.get("engine", {})}
    cfg = arch_config(c["model"])
    reference = load_module(cell.dir / "references" / f"{c['reference']}.py")
    dtype = jnp.dtype(c["dtype"])
    timings = {}

    t = time.perf_counter()
    params = jax.jit(lambda k: reference.make_params(c["model"], k, dtype))(
        seed_key(seed))
    jax.block_until_ready(params)
    timings["weights_s"] = time.perf_counter() - t

    exec_cfg = None
    t = time.perf_counter()
    if c.get("dispatch") is not None:
        exec_cfg = decode_exec_config(cfg, eng["n_slots"], params=params,
                                      **{**c["dispatch"],
                                         **control.get("dispatch", {})})
        timings["plan_s"] = time.perf_counter() - t

    t = time.perf_counter()
    engine = ServeEngine(cfg, params, dtype=dtype, exec_cfg=exec_cfg,
                         verify_plan=False, **eng)
    engine.warmup()
    timings["engine_warmup_s"] = time.perf_counter() - t
    return Built(params, engine, reference, timings)


@dataclass
class Window:
    """What the measured window saw."""
    records: List[stats.Record]
    start: float
    end: float
    cutoff: float
    steps: List[tuple] = field(default_factory=list)
    compiles: int = 0
    lateness: List[float] = field(default_factory=list)
    trace_span: Optional[tuple] = None


def count_dispatch(engine, steps: List[tuple]):
    """Log every prefill segment and decode block the engine dispatches:
    (host time, kind, device steps), each dispatch in a host span.  The
    program counts no steps itself, so this wraps its two jitted entry
    points and reads their arguments by position: the prefill's tokens
    (argument 2, one row per step) and the decode block's static length
    (argument 9).  A change to either signature stops the run."""
    import jax
    prefill, decode_many = engine._prefill, engine._decode_many

    def counted_prefill(*a, **k):
        if len(a) != 8 or k or np.ndim(a[2]) != 1:
            raise RuntimeError("ServeEngine._prefill no longer takes "
                               "(params, state, tokens, valid, slot, "
                               "slot_pos, start, reset)")
        with jax.profiler.TraceAnnotation("bench.dispatch.prefill"):
            out = prefill(*a, **k)
        steps.append((time.perf_counter(), "prefill", int(a[2].shape[0])))
        return out

    def counted_decode(*a, **k):
        if len(a) != 10 or k or not isinstance(a[9], (int, np.integer)):
            raise RuntimeError("ServeEngine._decode_many no longer takes "
                               "(params, state, tokens, pos, live, rem, "
                               "temp, top_k, seeds, n_steps)")
        with jax.profiler.TraceAnnotation("bench.dispatch.decode"):
            out = decode_many(*a, **k)
        steps.append((time.perf_counter(), "decode", int(a[9])))
        return out

    engine._prefill, engine._decode_many = counted_prefill, counted_decode


def run_window(engine, plan: List[loadgen.Planned], traffic: Dict,
               seconds: float, trace_dir: Optional[str]) -> Window:
    """Drive the served path for ``seconds``; see the module docstring."""
    import jax

    compiles = [0]

    def on_event(name, _secs, **_kw):
        if name.endswith("backend_compile_duration"):
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    steps: List[tuple] = []
    count_dispatch(engine, steps)
    recs: List[stats.Record] = []
    queued: Dict[int, stats.Record] = {}
    active: Dict[int, stats.Record] = {}
    by_uid: Dict[int, stats.Record] = {}
    lateness: List[float] = []
    state = {"next": 0}

    def submit(p: loadgen.Planned, due: float):
        with jax.profiler.TraceAnnotation("bench.submit"):
            uid = engine.submit(p.prompt, max_new=p.max_new)
        now = time.perf_counter()
        r = stats.Record(p.index, uid, p.prompt, p.max_new, due, now)
        lateness.append(now - due)
        recs.append(r)
        queued[uid] = active[uid] = by_uid[uid] = r

    def credit(out: Dict, t: float):
        for uid, toks in out.items():
            if toks and uid in by_uid:
                by_uid[uid].chunks.append((t, len(toks)))
                by_uid[uid].tokens.extend(int(x) for x in toks)

    def tick():
        with jax.profiler.TraceAnnotation("bench.tick"):
            out = engine.decode_block_step()
        t = time.perf_counter()
        credit(out, t)
        for uid in list(queued):
            if engine.status(uid) != "queued":
                queued.pop(uid).admitted = t
        for uid in list(active):
            status = engine.status(uid)
            if status in TERMINAL:
                active.pop(uid).status = status

    def idle() -> bool:
        h = engine.health()
        return not (h["queue_depth"] or h["decoding"] or h["prefilling"]
                    or h["inflight_blocks"])

    tracer = {"on": False}

    def trace_control(now: float, t0: float, force_stop: bool = False):
        if trace_dir is None:
            return
        lo = t0 + max(seconds / 2 - TRACE_S / 2, 0.0)
        if not tracer["on"] and "span" not in tracer and now >= lo \
                and not force_stop:
            jax.profiler.start_trace(trace_dir)
            tracer["ann"] = jax.profiler.TraceAnnotation("bench.traced")
            tracer["ann"].__enter__()
            tracer["on"], tracer["t"] = True, time.perf_counter()
        elif tracer["on"] and (force_stop or now >= tracer["t"] + TRACE_S):
            tracer["ann"].__exit__(None, None, None)
            tracer["span"] = (tracer["t"], time.perf_counter())
            jax.profiler.stop_trace()
            tracer["on"] = False

    t0 = time.perf_counter()
    end = t0 + seconds
    base = compiles[0]
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        trace_control(now, t0)
        while state["next"] < len(plan) and \
                t0 + plan[state["next"]].due <= now:
            p = plan[state["next"]]
            state["next"] += 1
            submit(p, t0 + p.due)
        if idle():
            nxt = (t0 + plan[state["next"]].due if state["next"] < len(plan)
                   else end)
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
            continue
        tick()
    window_compiles = compiles[0] - base
    trace_control(time.perf_counter(), t0, force_stop=True)
    # every request due in the window is followed to its first token
    # (bounded by DRAIN_S), so the tail counts its whole wait
    stop = time.perf_counter() + DRAIN_S
    while time.perf_counter() < stop and any(
            r.first_token is None for r in recs) and not idle():
        tick()
    cutoff = time.perf_counter()
    credit(engine.flush(), cutoff)
    for uid in list(active):
        active[uid].status = engine.status(uid) or active[uid].status
    jax.monitoring.unregister_event_duration_listener(on_event)
    return Window(recs, t0, end, cutoff, steps, window_compiles, lateness,
                  trace_span=tracer.get("span"))


@dataclass
class RunData:
    """What a metric reader gets."""
    cell: Cell
    records: List[stats.Record]
    start: float
    end: float
    cutoff: float
    setup_s: float
    peaks: Dict           # the chip's peaks (``peaks.json``)
    steps: List[tuple]
    trace: Optional[object] = None
    trace_span: Optional[tuple] = None
    check: Dict[str, float] = field(default_factory=dict)

    def due_in_window(self) -> List[stats.Record]:
        return [r for r in self.records if self.start <= r.due < self.end]

    def steps_in(self, lo: float, hi: float) -> Dict[str, int]:
        out = {"prefill": 0, "decode": 0}
        for t, kind, n in self.steps:
            if lo <= t <= hi:
                out[kind] += n
        return out


def read_metrics(entries: List[Dict], run: RunData) -> Dict[str, Dict]:
    out = {}
    for m in entries:
        reader = load_module(run.cell.dir / "metrics" / f"{m['name']}.py")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, control: Optional[Dict] = None,
             require_tpu: bool = True):
    """One run; returns the result line's object and the run's data."""
    import jax
    cache = None
    if require_tpu:
        from repro.launch.compile_cache import setup_compile_cache
        cache = setup_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = chips(cell.chips) if require_tpu else jax.devices()
    dev = devs[0]
    log({"device": {"platform": dev.platform, "kind": dev.device_kind,
                    "count": len(devs)}, "compile_cache": cache,
         "cell": cell.name, "seed": seed, "seconds": seconds,
         "trace": trace, "control": control})
    peaks = peaks_for(dev.device_kind) if require_tpu else {}

    built = build(cell, seed, control)
    eng = cell.config["engine"]
    plan = loadgen.schedule(cell.traffic, seconds, seed,
                            cell.config["model"]["vocab"])
    log({"traffic": cell.traffic_name, "arrivals": cell.traffic["arrivals"],
         "schedule": loadgen.describe(plan)})
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    setup_s = time.perf_counter() - t_start
    log({"setup_s": setup_s, **built.timings})

    win = run_window(built.engine, plan, cell.traffic, seconds, trace_dir)
    used = devs[:cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    summary = None
    if trace_dir is not None:
        trace_mod = load_module(HERE / "trace.py")
        summary = trace_mod.reduce(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    del built.engine
    gc.collect()

    run = RunData(cell, win.records, win.start, win.end, win.cutoff,
                  setup_s, peaks,
                  win.steps, summary, win.trace_span)
    due = run.due_in_window()
    failed = sum(r.status in TERMINAL and r.status != "done" for r in due)
    steps = run.steps_in(win.start, win.end)
    log({"window": {"attempted": len(due), "failed": failed,
                    "finished": sum(r.status == "done" for r in due),
                    "tokens": stats.tokens_in(win.records, win.start,
                                              win.end),
                    "steps_fed": steps, "compiles_in_window": win.compiles,
                    "generator_late_s": {
                        "median": stats.percentile(win.lateness, 50),
                        "max": max(win.lateness, default=None)},
                    "memory_peak_bytes": peak}})

    # the output check, after the window and with the engine freed
    import check
    done = [r for r in win.records if r.status == "done"]
    sample = check.sample(done, seed)
    flat = {k: v for k, v in cell.config["model"].items()
            if not isinstance(v, (dict, list))}
    t = time.perf_counter()
    gaps = check.served_gaps(built.reference, built.params, flat, sample,
                             eng["max_seq"])
    got = run.check = check.numbers(gaps)
    limits = cell.config["correct"]["limits"]
    log({"check": {"requests": len(sample),
                   "served_tokens": sum(len(g) for g in gaps),
                   "per_request_max": [float(g.max()) for g in gaps],
                   **got, "seconds": time.perf_counter() - t}})
    checks = {k: {"value": got.get(k), "limit": v}
              for k, v in limits.items()}
    correct = bool(got) and bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())

    entries = cell.per_layer if trace else cell.end_to_end
    result = {"correct": correct, "attempted": len(due), "failed": failed,
              "metrics": read_metrics(entries, run),
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs), "memory_peak_bytes": peak}}
    if summary is not None:
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = summary.breakdown()
        log({"trace": {"busy_s": summary.busy_s,
                       "window_s": summary.window_s,
                       "steps_fed": run.steps_in(*win.trace_span)
                       if win.trace_span else None}})
    result["checks"] = checks
    return result, run


def report(result: Dict) -> None:
    """The numbers compared, as the last lines of standard error, and the
    result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
