"""Arithmetic from per-request host timestamps to end-to-end metrics.

Times are host-clock seconds (``time.perf_counter``).  A request's tokens
are stamped when the serving tick that credits them returns, so a block
of T tokens carries one stamp.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np


@dataclass
class Record:
    """One request as the benchmark saw it."""
    index: int
    uid: int
    prompt: np.ndarray
    max_new: int
    due: float                   # when it was due (open loop) or submitted
    submitted: float             # when ``submit`` was called
    admitted: Optional[float] = None   # first tick after which not queued
    chunks: List[Tuple[float, int]] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    status: str = "queued"

    @property
    def first_token(self) -> Optional[float]:
        return self.chunks[0][0] if self.chunks else None


def percentile(values, q: float) -> Optional[float]:
    """Linear-interpolated ``q``-th percentile; None for no values."""
    values = list(values)
    return float(np.percentile(values, q)) if values else None


def ttft_s(records: List[Record], cutoff: float) -> List[float]:
    """Due time to first token for every record; a record with no first
    token counts as ``cutoff`` - due."""
    return [(r.first_token if r.first_token is not None else cutoff) - r.due
            for r in records]


def queue_wait_s(records: List[Record], cutoff: float) -> List[float]:
    """Due time to admission for every record; one never admitted counts
    as ``cutoff`` - due."""
    return [(r.admitted if r.admitted is not None else cutoff) - r.due
            for r in records]


def tpot_s(records: List[Record], start: float, end: float) -> List[float]:
    """(last - first in-window token time) / (n - 1) for each record with
    at least two tokens stamped inside [start, end]."""
    out = []
    for r in records:
        stamps = [t for t, n in r.chunks if start <= t <= end
                  for _ in range(n)]
        if len(stamps) >= 2:
            out.append((stamps[-1] - stamps[0]) / (len(stamps) - 1))
    return out


def tokens_in(records: List[Record], start: float, end: float) -> int:
    """Tokens stamped inside [start, end]."""
    return sum(n for r in records for t, n in r.chunks if start <= t <= end)
