"""The harness: it refuses to run without a TPU, and finds a cell's
configuration, traffic mix and metrics by name, so that a new one is new
files plus new entries."""
import io
import json
import os
import shutil
import subprocess
import sys
from contextlib import redirect_stdout
from types import SimpleNamespace

import pytest

from bench_smoke import BENCH, ROOT
import harness
import loadgen
import run as run_py

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_refuses_without_a_tpu():
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run_py.main(["--workload", SPEC["workloads"][0]["name"],
                          "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert '"correct"' not in out.getvalue()


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_files_and_readers(cell):
    c = harness.find_cell(cell)
    assert c.config["model"]["n_layers"] > 0
    assert loadgen.arrival_process(c.traffic["arrivals"]["process"])
    assert (c.dir / "references" / f"{c.config['reference']}.py").exists()
    names = {m["name"] for m in c.end_to_end + c.per_layer}
    assert "setup_s" in names
    for name in names:
        assert hasattr(harness.load_module(c.dir / "metrics" / f"{name}.py"),
                       "read")


def test_a_new_config_traffic_and_metric_are_files_plus_entries(
        tmp_path, monkeypatch):
    """Add a configuration, a traffic mix with an arrival process of its
    own and a metric by copying the benchmark and adding files and
    entries only."""
    shutil.copytree(BENCH, tmp_path / SPEC["paths"][0],
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = tmp_path / SPEC["paths"][0]
    cfg = json.loads((bench / "configs" / "stablelm-1.6b.json").read_text())
    cfg["model"]["n_layers"] = 3
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "burst.json").write_text(json.dumps(
        {**json.loads((bench / "traffic" / "chat.json").read_text()),
         "arrivals": {"process": "pairs", "rate_per_s": 9.0}}))
    (bench / "arrivals" / "pairs.py").write_text(
        "def dues(arrivals, seconds, rng):\n"
        "    n = int(arrivals['rate_per_s'] * seconds)\n"
        "    return [float(i // 2) for i in range(n)]\n")
    monkeypatch.setattr(loadgen, "ARRIVALS", bench / "arrivals")
    (bench / "metrics" / "tokens_total.py").write_text(
        "from stats import tokens_in\n\n\ndef read(run):\n"
        "    return tokens_in(run.records, run.start, run.end)\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "tiny", "source": "x",
                            "file": f"{SPEC['paths'][0]}/configs/tiny.json",
                            "reduced": ["n_layers"], "why": "x"})
    spec["workloads"].append({"name": "tiny.burst", "config": "tiny",
                              "traffic": "burst", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "tokens_total", "unit": "tokens",
                              "better": "higher", "source": "host_clock",
                              "layer": "x", "moves": "ttft_p95_ms",
                              "workloads": ["tiny.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.find_cell("tiny.burst", root=tmp_path)
    assert cell.config["model"]["n_layers"] == 3
    plan = loadgen.schedule(cell.traffic, 2.0, 1, vocab=100)
    assert [r.due for r in plan] == [float(i // 2) for i in range(18)]
    assert [m["name"] for m in cell.per_layer] == ["tokens_total"]
    from stats import Record
    r = Record(0, 1, [1, 2], 4, 0.0, 0.0, chunks=[(0.5, 3), (2.0, 1)])
    run = SimpleNamespace(cell=cell, records=[r], start=0.0, end=1.0)
    got = harness.read_metrics(cell.per_layer, run)
    assert got == {"tokens_total": {"value": 3, "unit": "tokens"}}


def test_peaks_are_keyed_by_device_kind():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("some other chip")


def test_step_counting_stops_on_a_changed_engine_signature():
    import numpy as np
    engine = SimpleNamespace(_prefill=lambda *a: "state",
                             _decode_many=lambda *a: ("toks", "state"))
    steps = []
    harness.count_dispatch(engine, steps)
    engine._prefill(0, 1, np.zeros(4, np.int32), 3, 4, 5, 6, 7)
    engine._decode_many(0, 1, 2, 3, 4, 5, 6, 7, 8, 16)
    assert [(kind, n) for _, kind, n in steps] == [("prefill", 4),
                                                    ("decode", 16)]
    with pytest.raises(RuntimeError, match="_prefill"):
        engine._prefill(0, 1, np.zeros(4, np.int32), 3, 4, 5, 6)
    with pytest.raises(RuntimeError, match="_decode_many"):
        engine._decode_many(0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 16)
