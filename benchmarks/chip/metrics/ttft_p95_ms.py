"""ttft_p95_ms: 95th percentile, over every request due in the window, of
the time from its due time to the host's receipt of its first token.
After the close the benchmark keeps ticking (no new arrivals) until each
such request has its first token; one that has none by then counts as the
wait until that cut-off."""
from stats import percentile, ttft_s


def read(run):
    recs = run.due_in_window()
    if not recs:
        return None
    return percentile(ttft_s(recs, run.cutoff), 95) * 1e3
