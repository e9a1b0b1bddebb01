"""The one traffic generator: a traffic file's parameters to a request list.

A traffic file (``traffic/<name>.json``) is data only:

    {"arrivals": {"process": "spread", "rate_per_s": 0.28, "order_seed": 1},
     "prompt": {"dist": "lognormal", "median": 32, "sigma": 0.8,
                "min": 8, "max": 384},
     "output": {"dist": "lognormal", "median": 64, "sigma": 0.6,
                "min": 16, "max": 256},
     "max_total": 1023}

``arrivals.process`` names the arrival process, a module of its own,
``arrivals/<process>.py``, whose ``dues(arrivals, seconds, rng)`` gives
the due times of the window's requests.  Length distributions are
``lognormal`` (median, sigma), clipped to [min, max]; the lengths are the distribution's quantiles at evenly spaced
probabilities, paired with the due times in an order drawn from
``order_seed``.

So every run of a traffic file gets the same sizes and arrivals in the
same order; the run's seed draws only the prompt tokens (and, in the
harness, the weights).  With a few tens of requests in a window, the
order alone moves a tail by tens of percent, so a seed that reordered the
schedule would measure a different workload on every seed.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List

import numpy as np

ARRIVALS = Path(__file__).resolve().parent / "arrivals"


@dataclass
class Planned:
    """One request of the schedule, ``due`` seconds after the window
    opens."""
    index: int
    due: float
    prompt: np.ndarray
    max_new: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for ``seed`` (any integer) and a named stream."""
    return np.random.default_rng([seed % 2 ** 64 >> 32, seed % 2 ** 32,
                                  stream])


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """``n`` integer lengths at the distribution's (i + 0.5) / n quantiles,
    clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(x), dist["min"], dist["max"]).astype(np.int64)


def arrival_process(name: str):
    """The module ``arrivals/<name>.py``."""
    path = ARRIVALS / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"unknown arrival process {name!r}; known: "
                         f"{sorted(p.stem for p in ARRIVALS.glob('*.py'))}")
    spec = importlib.util.spec_from_file_location(f"arrivals_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def schedule(traffic: Dict, seconds: float, seed: int,
             vocab: int) -> List[Planned]:
    """The request list for one run."""
    arr = traffic["arrivals"]
    process = arrival_process(arr["process"])
    rng = rng_for(int(arr.get("order_seed", 0)), 0)
    dues = process.dues(arr, seconds, rng)
    n = len(dues)
    prompts = rng.permutation(quantiles(traffic["prompt"], n))
    outputs = rng.permutation(quantiles(traffic["output"], n))
    cap = traffic.get("max_total")
    if cap is not None:
        outputs = np.minimum(outputs, cap - prompts)
    tok_rng = rng_for(seed, 1)
    return [Planned(i, dues[i],
                    tok_rng.integers(0, vocab, size=int(prompts[i]),
                                     dtype=np.int32),
                    int(outputs[i]))
            for i in range(n)]


def describe(plan: List[Planned]) -> Dict[str, object]:
    """Length statistics of a schedule, for the run's earlier lines."""
    p = np.array([len(r.prompt) for r in plan])
    o = np.array([r.max_new for r in plan])
    return {"requests": len(plan),
            "prompt": {"min": int(p.min()), "median": float(np.median(p)),
                       "max": int(p.max()), "sum": int(p.sum())},
            "output": {"min": int(o.min()), "median": float(np.median(o)),
                       "max": int(o.max()), "sum": int(o.sum())}}
