"""Metric arithmetic on synthetic timestamps, and the traffic generator."""
import numpy as np
import pytest

import bench_smoke  # noqa: F401  (puts the benchmark on the path)
import loadgen
import stats
from stats import Record


def rec(due, chunks, prompt_len=4, admitted=None):
    return Record(index=0, uid=0, prompt=np.zeros(prompt_len, np.int32),
                  max_new=99, due=due, submitted=due, admitted=admitted,
                  chunks=list(chunks))


def test_ttft_counts_a_request_without_first_token_to_the_cutoff():
    recs = [rec(1.0, [(1.5, 1)]), rec(2.0, [])]
    assert stats.ttft_s(recs, cutoff=10.0) == [0.5, 8.0]


def test_queue_wait_counts_an_unadmitted_request_to_the_cutoff():
    recs = [rec(1.0, [], admitted=3.0), rec(2.0, [])]
    assert stats.queue_wait_s(recs, cutoff=5.0) == [2.0, 3.0]


def test_tpot_uses_only_tokens_stamped_in_the_window():
    r = rec(0.0, [(1.0, 1), (2.0, 4), (3.0, 4), (20.0, 8)])
    # in [1.5, 10]: 8 tokens, stamps 2.0 x4 and 3.0 x4
    assert stats.tpot_s([r], 1.5, 10.0) == [pytest.approx(1.0 / 7)]
    # one token in the window gives no value
    assert stats.tpot_s([rec(0.0, [(2.0, 1)])], 0.0, 10.0) == []


def test_tok_s_clips_to_the_window():
    recs = [rec(0.0, [(0.5, 16), (1.0, 16), (4.9, 16), (5.1, 16)])]
    assert stats.tokens_in(recs, 1.0, 5.0) == 32


def test_percentile():
    assert stats.percentile([], 95) is None
    assert stats.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 50) == 3.0
    assert stats.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 95) == 4.8


CHAT = bench_smoke.TRAFFIC["open"]


def test_schedule_is_fixed_and_tokens_come_from_the_seed():
    seed = 2 ** 31 + 977
    a = loadgen.schedule(CHAT, 51.0, seed, vocab=1000)
    b = loadgen.schedule(CHAT, 51.0, seed, vocab=1000)
    c = loadgen.schedule(CHAT, 51.0, seed + 1, vocab=1000)
    assert [r.prompt.tolist() for r in a] == [r.prompt.tolist() for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]
    # every seed gets the same sizes and arrivals in the same order
    assert [(r.due, len(r.prompt), r.max_new) for r in a] == \
        [(r.due, len(r.prompt), r.max_new) for r in c]
    other = loadgen.schedule(
        {**CHAT, "arrivals": {**CHAT["arrivals"], "order_seed": 2}},
        51.0, seed, 1000)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in other)
    assert [r.max_new for r in a] != [r.max_new for r in other]


def test_open_loop_arrivals_fill_the_window():
    plan = loadgen.schedule(CHAT, 51.0, 5, vocab=100)
    dues = [r.due for r in plan]
    assert len(plan) == round(CHAT["arrivals"]["rate_per_s"] * 51.0)
    assert dues[0] == 0.0 and dues == sorted(dues) and dues[-1] < 51.0
    for r in plan:
        assert CHAT["prompt"]["min"] <= len(r.prompt) <= CHAT["prompt"]["max"]
        assert len(r.prompt) + r.max_new <= CHAT["max_total"]


def test_committed_chat_schedule():
    """The chat cell's traffic file gives 14 requests in a 51 s window,
    the same ones on every seed."""
    import json
    traffic = json.loads((bench_smoke.BENCH / "traffic" / "chat.json")
                         .read_text())
    plan = loadgen.schedule(traffic, 51.0, 3, vocab=100352)
    assert len(plan) == 14
    assert [(len(r.prompt), r.max_new) for r in plan][:3] == \
        [(11, 67), (66, 36), (25, 95)]


def test_lognormal_quantiles_have_the_stated_median():
    q = loadgen.quantiles({"dist": "lognormal", "median": 32, "sigma": 0.8,
                           "min": 8, "max": 384}, 101)
    assert q[50] == 32 and q.min() >= 8 and q.max() <= 384


def test_an_unknown_arrival_process_is_refused():
    with pytest.raises(ValueError, match="arrival process"):
        loadgen.schedule({**CHAT, "arrivals": {"process": "poisson"}},
                         51.0, 5, vocab=100)
