"""The output check at smoke size on the CPU: sound runs pass it, the
int8 control reads wider gaps than the program, and runs with the served
path broken underneath fail it."""
import contextlib
import io

import jax.numpy as jnp
import pytest

import bench_smoke

CELLS = [("stablelm-1.6b", "open")]


def quiet_run(*args, **kw):
    with contextlib.redirect_stdout(io.StringIO()):
        return bench_smoke.run(*args, **kw)


@pytest.mark.parametrize("config,loop", CELLS)
def test_sound_run_is_correct(config, loop):
    r, _ = quiet_run(config, loop, seed=11)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


def test_int8_control_reads_wider_gaps_than_the_program():
    """The number the configuration compares reads wider under its
    control (the program's int8 path) than under the program."""
    config = bench_smoke.smoke_config("stablelm-1.6b")
    control = config["correct"]["control"]
    (name,) = config["correct"]["limits"]
    seeds = (1, 2, 3)
    prog = [quiet_run("stablelm-1.6b", "open", s)[1].check[name]
            for s in seeds]
    ctrl = [quiet_run("stablelm-1.6b", "open", s, control=control)[1]
            .check[name] for s in seeds]
    # the sample of finished requests moves with the CPU's speed, so the
    # margin here is 2x
    assert sum(ctrl) > 2 * sum(prog), (prog, ctrl)


def altered_token(monkeypatch):
    """The first token each decode block emits for every slot is replaced
    by the next id, where it is produced."""
    from repro.models import model as M
    real = M.decode_many

    def decode_many(p, cfg, *a, **k):
        toks, *rest = real(p, cfg, *a, **k)
        bumped = jnp.where(toks[0] >= 0, (toks[0] + 1) % cfg.vocab, toks[0])
        return (toks.at[0].set(bumped), *rest)

    monkeypatch.setattr(M, "decode_many", decode_many)


def unchanged_state(monkeypatch):
    """Prefill returns the decode state it was given: the prompt is never
    written to the cache."""
    from repro.models import model as M
    monkeypatch.setattr(M, "prefill_into_slot",
                        lambda p, cfg, toks, valid, slot, state, *a, **k:
                        state)


@pytest.mark.parametrize("fault", [altered_token, unchanged_state])
@pytest.mark.parametrize("config,loop", CELLS)
def test_broken_served_path_is_not_correct(monkeypatch, fault, config, loop):
    fault(monkeypatch)
    r, _ = quiet_run(config, loop, seed=11)
    assert not r["correct"], r["checks"]
