"""Observability of the serving engine: the ``serve.*`` host spans (names,
nesting, keyword arguments), the dispatch counters in
``health()["counters"]``, the per-request lifecycle stamps behind
``request_times(uid)``, and the stable names of the jitted entry points
that profiler traces show as ``jit_serve_*``."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.models import model as model_lib
from repro.serve import ServeEngine, VirtualClock
from repro.serve import engine as engine_mod

DISPATCH_COUNTERS = ("admitted", "prefill_segments", "prefill_tokens",
                     "prefill_steps", "prefill_forwards", "decode_blocks",
                     "decode_steps", "syncs")


@functools.lru_cache(maxsize=None)
def _tiny():
    cfg = ArchConfig(name="obs-tiny", family="dense", n_layers=1,
                     d_model=32, n_heads=4, n_kv_heads=4, d_ff=64,
                     vocab=128, norm="rmsnorm")
    return cfg, model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                      dtype=jnp.float32)


def _engine(**kw):
    cfg, params = _tiny()
    kw = {"n_slots": 2, "max_seq": 64, "decode_block": 4, **kw}
    return ServeEngine(cfg, params, **kw)


def _drive(eng, max_ticks=200):
    for _ in range(max_ticks):
        h = eng.health()
        if not (h["queue_depth"] or h["decoding"] or h["prefilling"]
                or h["inflight_blocks"]):
            return
        eng.decode_block_step()
    raise AssertionError("engine did not drain")


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each span's
    name, keyword arguments and enclosing span."""

    def __init__(self):
        self.spans, self._stack = [], []

    def __call__(self, name, **kwargs):
        rec = self

        class _Span:
            def __enter__(self):
                parent = rec._stack[-1] if rec._stack else None
                rec.spans.append({"name": name, "kwargs": kwargs,
                                  "parent": parent})
                rec._stack.append(name)

            def __exit__(self, *exc):
                rec._stack.pop()

        return _Span()


def test_spans_name_nest_and_carry_the_dispatch(monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(engine_mod, "TraceAnnotation", rec)
    eng = _engine(prefill_chunk=4, async_dispatch=True)
    uid = eng.submit(np.arange(1, 12), max_new=9)    # feed 10: 4 + 4 + 2
    _drive(eng)
    assert eng.status(uid) == "done"
    names = {s["name"] for s in rec.spans}
    assert names == {"serve.tick", "serve.admit", "serve.prefill.dispatch",
                     "serve.decode.dispatch", "serve.sync", "serve.account"}
    by = lambda n: [s for s in rec.spans if s["name"] == n]  # noqa: E731
    assert all(s["parent"] is None for s in by("serve.tick"))
    (admit,) = by("serve.admit")
    assert admit["parent"] == "serve.tick"
    assert admit["kwargs"] == {"uid": uid, "slot": 0}
    feeds = by("serve.prefill.dispatch")
    assert [s["parent"] for s in feeds] == ["serve.admit", "serve.tick",
                                            "serve.tick"]
    assert [s["kwargs"] for s in feeds] == [
        {"uid": uid, "tokens": 4, "steps": 4},
        {"uid": uid, "tokens": 4, "steps": 4},
        {"uid": uid, "tokens": 2, "steps": 2}]
    decodes = by("serve.decode.dispatch")
    assert decodes and all(s["parent"] == "serve.tick" for s in decodes)
    assert all(s["kwargs"]["live"] == 1 for s in decodes)
    assert sum(s["kwargs"]["steps"] for s in decodes) >= 9
    syncs = by("serve.sync")
    assert [s["kwargs"]["steps"] for s in syncs] == \
        [s["kwargs"]["steps"] for s in decodes]
    assert all(s["parent"] == "serve.tick" for s in syncs)
    assert len(by("serve.account")) == len(syncs)


@pytest.mark.parametrize("async_dispatch,chunk", [(True, 4), (False, 4),
                                                  (True, None)])
def test_counters_equal_the_dispatched_work(async_dispatch, chunk):
    """Counter deltas against the executables' own arguments: valid and
    padded prefill steps, scanned decode steps, one sync per block."""
    eng = _engine(prefill_chunk=chunk, async_dispatch=async_dispatch)
    eng.submit(np.arange(1, 3), max_new=2)           # warm one request
    _drive(eng)
    before = dict(eng.health()["counters"])
    seen = {"segments": 0, "valid": 0, "padded": 0, "blocks": 0,
            "steps": 0}
    prefill, decode_many = eng._prefill, eng._decode_many

    def counted_prefill(p, s, toks, valid, *rest):
        seen["segments"] += 1
        seen["valid"] += int(np.sum(valid))
        seen["padded"] += int(np.shape(toks)[0])
        return prefill(p, s, toks, valid, *rest)

    def counted_decode(*args):
        seen["blocks"] += 1
        seen["steps"] += int(args[9])
        return decode_many(*args)

    eng._prefill, eng._decode_many = counted_prefill, counted_decode
    for n, new in ((11, 7), (2, 9), (6, 3)):
        eng.submit(np.arange(1, n + 1), max_new=new)
    _drive(eng)
    eng.flush()
    after = eng.health()["counters"]
    delta = {k: after[k] - before[k] for k in DISPATCH_COUNTERS}
    assert delta == {"admitted": 3, "prefill_segments": seen["segments"],
                     "prefill_tokens": seen["valid"],
                     "prefill_steps": seen["padded"],
                     "prefill_forwards": seen["segments"],
                     "decode_blocks": seen["blocks"],
                     "decode_steps": seen["steps"],
                     "syncs": seen["blocks"]}
    assert delta["prefill_tokens"] == 10 + 1 + 5


@pytest.mark.parametrize("arch,one_pass", [
    ("stablelm-1.6b", True), ("deepseek-moe-16b", True),
    ("mamba2-1.3b", False), ("recurrentgemma-9b", False)])
def test_prefill_forwards_count_one_pass_segments(arch, one_pass):
    """``prefill_forwards`` counts the segments fed in one forward pass:
    every segment of a KV-only stack (dense, MoE), none of an SSM or
    RG-LRU stack, which keep the token-serial scan."""
    from repro.configs.base import get_smoke_config
    cfg = get_smoke_config(arch)
    eng = ServeEngine(cfg, model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                                 dtype=jnp.float32),
                      n_slots=2, max_seq=32, decode_block=4,
                      dtype=jnp.float32, prefill_chunk=4)
    for n in (11, 3):
        eng.submit(np.arange(1, n + 1), max_new=2)
    _drive(eng)
    c = eng.health()["counters"]
    assert c["prefill_segments"] == 3 + 1
    assert c["prefill_forwards"] == (c["prefill_segments"] if one_pass
                                     else 0)


def test_verify_blocks_count_their_window():
    cfg, params = _tiny()
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=64, decode_block=4,
                      speculate_k=2)
    eng.submit(np.arange(1, 5), max_new=8)
    _drive(eng)
    eng.flush()
    c = eng.health()["counters"]
    assert eng.spec_stats["verify_blocks"] > 0
    assert c["decode_blocks"] == c["syncs"] > 0
    # a verify block scans spec_k + 1 steps
    assert c["decode_steps"] >= 3 * eng.spec_stats["verify_blocks"]


def test_request_stamps_are_ordered_on_the_engine_clock():
    """One slot, a clock that moves one second per tick: the second
    request waits in the queue until the first finishes, and each stamp
    reads the tick it was reached in."""
    clk = VirtualClock()
    eng = _engine(n_slots=1, prefill_chunk=4, clock=clk)
    a = eng.submit(np.arange(1, 14), max_new=5)
    clk.advance(0.5)
    b = eng.submit(np.arange(1, 4), max_new=3)
    assert eng.request_times(b) == {"submitted": 0.5, "admitted": None,
                                    "prefilled": None, "first_token": None,
                                    "finished": None}
    for _ in range(100):
        clk.advance(1.0)
        eng.decode_block_step()
        if eng.status(b) == "done":
            break
    eng.flush()
    order = ("submitted", "admitted", "prefilled", "first_token", "finished")
    ta, tb = eng.request_times(a), eng.request_times(b)
    assert tuple(ta) == tuple(tb) == order
    for t in (ta, tb):
        stamps = [t[k] for k in order]
        assert None not in stamps and stamps == sorted(stamps)
    # a feeds 12 tokens in chunks of 4, two a tick: admitted in the first
    # tick (clock 1.5), its last chunk dispatched in the second (2.5)
    assert (ta["submitted"], ta["admitted"], ta["prefilled"]) == (0.0, 1.5,
                                                                   2.5)
    # b is admitted in the tick that frees the slot
    assert tb["submitted"] == 0.5 and tb["admitted"] == ta["finished"]
    assert eng.request_times(12345) is None


def test_stamps_survive_slot_recycling_and_shedding():
    clk = VirtualClock(start=3.0)
    eng = _engine(n_slots=1, max_queue=1, clock=clk)
    a = eng.submit(np.arange(1, 4), max_new=2)
    b = eng.submit(np.arange(1, 4), max_new=2)       # queue full: shed
    assert eng.status(b) == "shed"
    assert eng.request_times(b) == {"submitted": 3.0, "admitted": None,
                                    "prefilled": None, "first_token": None,
                                    "finished": 3.0}
    _drive(eng)
    c = eng.submit(np.arange(1, 4), max_new=2)
    _drive(eng)
    assert eng.status(a) == eng.status(c) == "done"
    assert eng.request_times(a)["finished"] == 3.0
    assert eng.health()["counters"]["admitted"] == 2


def test_jitted_entry_points_have_stable_names():
    eng = _engine(speculate_k=2)
    assert {eng._decode.__name__, eng._decode_many.__name__,
            eng._prefill.__name__, eng._verify.__name__} == {
        "serve_decode", "serve_decode_many", "serve_prefill",
        "serve_verify"}
    zero = np.zeros((eng.n_slots,), np.int32)
    text = eng._decode_many.lower(
        eng._exec_params, eng.state, zero, zero,
        np.zeros((eng.n_slots,), bool), zero, None, None, None,
        2).as_text()
    assert text.startswith("module @jit_serve_decode_many")
