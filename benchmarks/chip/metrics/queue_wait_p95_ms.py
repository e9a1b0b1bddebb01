"""queue_wait_p95_ms (serve engine tick): 95th percentile, over every
request due in the window, of the time from its due time to the end of
the first tick after which ``ServeEngine.status`` no longer says
``queued``; one never admitted counts as the wait until the cut-off."""
from stats import percentile, queue_wait_s


def read(run):
    recs = run.due_in_window()
    if not recs:
        return None
    return percentile(queue_wait_s(recs, run.cutoff), 95) * 1e3
