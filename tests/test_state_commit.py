"""The decode-state commit: masked per row, in place.

``masked_decode_step``, ``decode_many`` and ``prefill_into_slot`` commit
state only for active rows.  Across every state family (dense, windowed
dense, MoE, SSM, RG-LRU, encoder-decoder) an inactive row's state must
come back bit-identical, and an active row's state (and every row's
logits) must equal what the select-based commit gives: run the step for
every row, then ``jnp.where(active, new, old)`` over each whole state
leaf.  That reference lives here, in a few lines, and runs through the
same ``decode_many`` loop and the token-serial prefill scan,
``prefill_serial``.  Stacks of KV caches alone (dense, MoE) prefill in
one forward pass instead, which rounds in another order: there the
admitted row is compared to the reference scan within a tolerance and
every other row bit for bit (``tests/test_prefill_forward.py`` covers
that path's cases in full).

The structural test guards the mechanism: lowered on the smoke StableLM
config, neither fused entry point holds a ``select`` over the whole
stacked (L, B, C, KVH·hd) KV state.
"""
import dataclasses
import functools
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_smoke_config
from repro.models import model as M, transformer

B, MAX_SEQ = 4, 16
ACTIVE = np.array([True, False, True, False])


def _windowed_dense():
    return dataclasses.replace(get_smoke_config("stablelm-1.6b"), window=8)


FAMILIES = {
    "dense": lambda: get_smoke_config("stablelm-1.6b"),
    "windowed": _windowed_dense,
    "moe": lambda: get_smoke_config("deepseek-moe-16b"),
    "ssm": lambda: get_smoke_config("mamba2-1.3b"),
    "rglru": lambda: get_smoke_config("recurrentgemma-9b"),
    "encdec": lambda: get_smoke_config("whisper-tiny"),
}


@functools.lru_cache(maxsize=None)
def _family(name):
    cfg = FAMILIES[name]()
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def _random_state(cfg, seed):
    """A decode state with every leaf random, so an untouched row shows."""
    state = M.init_decode_state(cfg, B, MAX_SEQ, dtype=jnp.float32)
    leaves, tree = jax.tree.flatten(state)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
        for k, a in zip(keys, leaves)])


# --- the select-based commit, kept as the reference -------------------------

def _select(mask, new, old):
    return jax.tree.map(
        lambda n, o: jnp.where(
            mask.reshape((1, -1) + (1,) * (o.ndim - 2)), n, o), new, old)


def _ref_masked_decode_step(p, cfg, tokens, state, pos, active):
    logits, new = M.decode_step(p, cfg, tokens, state, pos)
    return logits, _select(active, new, state)


def _ref_reset_row(state, row, reset):
    hit = (jnp.arange(B) == row) & jnp.asarray(reset, bool)
    return _select(hit, jax.tree.map(jnp.zeros_like, state), state)


@pytest.fixture
def reference(monkeypatch):
    """Swap the select-based commit into ``model`` for the enclosed calls."""
    def use():
        monkeypatch.setattr(M, "masked_decode_step", _ref_masked_decode_step)
        monkeypatch.setattr(M, "_reset_row", _ref_reset_row)
    return use


def _assert_rows(got, want, before, rows_kept):
    """``got`` equals ``want`` exactly, and rows ``rows_kept`` of ``got``
    equal ``before`` bit for bit."""
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    for (path, g), w, b in zip(flat_got, jax.tree.leaves(want),
                               jax.tree.leaves(before)):
        g, w, b = np.asarray(g), np.asarray(w), np.asarray(b)
        np.testing.assert_array_equal(g, w, err_msg=jax.tree_util.keystr(path))
        np.testing.assert_array_equal(
            g[:, rows_kept].view(np.uint8), b[:, rows_kept].view(np.uint8),
            err_msg=jax.tree_util.keystr(path))


# --- the commit contract -----------------------------------------------------

@pytest.mark.parametrize("pos_kind", ["per_slot", "scalar"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_masked_decode_step_commits_active_rows_only(family, pos_kind,
                                                     reference):
    cfg, params = _family(family)
    state = _random_state(cfg, 1)
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, 1)), jnp.int32)
    pos = (jnp.asarray(rng.integers(0, MAX_SEQ, B), jnp.int32)
           if pos_kind == "per_slot" else jnp.int32(5))
    active = jnp.asarray(ACTIVE)
    logits, got = jax.jit(functools.partial(M.masked_decode_step, cfg=cfg))(
        params, tokens=tokens, state=state, pos=pos, active=active)
    reference()
    want_logits, want = _ref_masked_decode_step(params, cfg, tokens, state,
                                                pos, active)
    _assert_rows(got, want, state, ~ACTIVE)
    # every row, filler included, computes what it computed before
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))


@pytest.mark.parametrize("family", list(FAMILIES))
def test_decode_many_commits_active_rows_only(family, reference):
    cfg, params = _family(family)
    state = _random_state(cfg, 3)
    rng = np.random.default_rng(4)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, B), jnp.int32)
    pos = jnp.asarray(rng.integers(0, MAX_SEQ - 4, B), jnp.int32)
    live = jnp.asarray([True, True, False, True])
    rem = jnp.asarray([3, 0, 3, 1], jnp.int32)    # row 1 spent, row 3 stops
    run = functools.partial(M.decode_many, cfg=cfg, n_steps=3, rem=rem)
    got = run(params, tokens=tokens, state=state, pos=pos, live=live)
    reference()
    want = run(params, tokens=tokens, state=state, pos=pos, live=live)
    _assert_rows(got[1], want[1], state, np.array([False, True, True, False]))
    for g, w in zip(got[:1] + got[2:], want[:1] + want[2:]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("reset", [True, False])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_prefill_into_slot_commits_admitted_row_only(family, reset,
                                                     reference):
    cfg, params = _family(family)
    state = _random_state(cfg, 5)
    rng = np.random.default_rng(6)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, 6), jnp.int32)
    valid = jnp.asarray([True] * 4 + [False] * 2)
    slot_pos = jnp.asarray(rng.integers(0, MAX_SEQ, B), jnp.int32)
    run = functools.partial(M.prefill_into_slot, cfg=cfg, tokens=toks,
                            valid=valid, slot=jnp.int32(2),
                            slot_pos=slot_pos, start=jnp.int32(3),
                            reset=jnp.asarray(reset))
    got = jax.jit(run)(params, state=state)
    reference()
    if not transformer.kv_state_only(cfg):
        _assert_rows(got, run(params, state=state), state, np.arange(B) != 2)
        return
    # the one-pass prefill against the select-based token-serial scan
    want = functools.partial(M.prefill_serial, cfg=cfg, tokens=toks,
                             valid=valid, slot=jnp.int32(2),
                             slot_pos=slot_pos, start=jnp.int32(3),
                             reset=jnp.asarray(reset))(params, state=state)
    kept = np.arange(B) != 2
    for (path, g), w, b in zip(jax.tree_util.tree_leaves_with_path(got),
                               jax.tree.leaves(want), jax.tree.leaves(state)):
        g, w, b = np.asarray(g), np.asarray(w), np.asarray(b)
        where = jax.tree_util.keystr(path)
        np.testing.assert_allclose(g[:, 2], w[:, 2], rtol=1e-5, atol=1e-5,
                                   err_msg=where)
        np.testing.assert_array_equal(g[:, kept].view(np.uint8),
                                      b[:, kept].view(np.uint8),
                                      err_msg=where)


# --- the flat cache read -----------------------------------------------------

@pytest.mark.parametrize("kvh,g,sq", [(4, 1, 1), (2, 4, 1), (1, 8, 3)])
def test_cache_attention_matches_dense_attention(kvh, g, sq):
    """Attention over flat (B, C, KVH*hd) cache rows equals
    ``dense_attention`` over the same K/V split into heads (MHA, GQA, MQA
    with a multi-token window)."""
    from repro.models.attention import cache_attention, dense_attention
    b, c, hd = 3, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (b, sq, kvh, g, hd))
    k = jax.random.normal(ks[1], (b, c, kvh, hd))
    v = jax.random.normal(ks[2], (b, c, kvh, hd))
    mask = jax.random.bernoulli(ks[3], 0.7, (b, 1, 1, sq, c))
    mask = mask.at[..., 0].set(True)
    want = dense_attention(q, k, v, mask)
    got = cache_attention(q, k.reshape(b, c, -1), v.reshape(b, c, -1), mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# --- no whole-state select ---------------------------------------------------

def _stablelm_lowered():
    cfg = get_smoke_config("stablelm-1.6b")
    params = jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(
        lambda: M.init_decode_state(cfg, B, 64, dtype=jnp.bfloat16))
    k = state["layers"]["k"]
    full = "tensor<{}xbf16>".format("x".join(map(str, k.shape)))
    i32 = jax.ShapeDtypeStruct((B,), jnp.int32)
    flag = jax.ShapeDtypeStruct((B,), jnp.bool_)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    decode = jax.jit(
        lambda p, s, t, pos, live, rem: M.decode_many(
            p, cfg, t, s, pos, live, 4, rem=rem),
        donate_argnums=(1,)).lower(params, state, i32, i32, flag, i32)
    prefill = jax.jit(
        lambda p, s, t, v, slot, sp, start, reset: M.prefill_into_slot(
            p, cfg, t, v, slot, s, sp, start, reset),
        donate_argnums=(1,)).lower(
            params, state, jax.ShapeDtypeStruct((8,), jnp.int32),
            jax.ShapeDtypeStruct((8,), jnp.bool_), scalar, i32, scalar,
            jax.ShapeDtypeStruct((), jnp.bool_))
    return full, {"decode_many": decode.as_text(),
                  "prefill_into_slot": prefill.as_text()}


def test_no_select_over_the_whole_stacked_state():
    full, texts = _stablelm_lowered()
    select = re.compile(r"stablehlo\.select\b.*" + re.escape(full))
    for name, text in texts.items():
        assert full in text, name           # the state is in the module
        hits = [ln.strip() for ln in text.splitlines() if select.search(ln)]
        assert not hits, (name, hits[:2])
