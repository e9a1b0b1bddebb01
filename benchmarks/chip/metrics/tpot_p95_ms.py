"""tpot_p95_ms: 95th percentile, over the requests with at least two
tokens received in the window, of (last - first in-window token time) /
(tokens - 1)."""
from stats import percentile, tpot_s


def read(run):
    values = tpot_s(run.records, run.start, run.end)
    return percentile(values, 95) * 1e3 if values else None
