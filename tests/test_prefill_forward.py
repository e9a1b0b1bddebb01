"""Chunk-parallel prefill: ``prefill_into_slot`` feeds a prompt segment of
a KV-only stack (dense, MoE) as one forward pass of the admitted row.

Against the token-serial scan it replaced, kept callable as
``model.prefill_serial``: on the f32 smoke StableLM and DeepSeek-MoE
configs, the admitted row's K/V agree, every other row is bit-untouched,
the greedy tokens of a following ``decode_many`` block are identical, and
MoE routing counts are equal and count valid tokens only.  The cases
cover the first chunk with its reset, a later chunk over a written
prefix, a padded tail, and segments that end at ``max_seq - 1``, where a
padded token's position clamps onto the last slot.

The structural test reads the lowered smoke StableLM prefill: one loop,
over the layers (none over the segment), and no logits head.
"""
import functools
import re

import jax
import jax.extend.core as jax_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.models import model as M

B, MAX_SEQ, ROW, W = 4, 32, 2, 8
FAMILIES = ("stablelm-1.6b", "deepseek-moe-16b")

# (start, valid tokens of the W-token segment, reset)
CASES = {
    "first_chunk_reset": (0, W, True),
    "later_chunk": (W, W, False),
    "padded_tail": (5, 3, False),
    "prompt_ends_at_max_seq_minus_1": (MAX_SEQ - 7, 6, False),
    "feed_fills_the_last_slot": (MAX_SEQ - 5, 5, False),
}


@functools.lru_cache(maxsize=None)
def _family(arch):
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def _random_state(cfg, seed):
    state = M.init_decode_state(cfg, B, MAX_SEQ, dtype=jnp.float32)
    leaves, tree = jax.tree.flatten(state)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [jax.random.normal(k, a.shape, a.dtype)
                                     for k, a in zip(keys, leaves)])


def _prefill(fn, cfg, params, state, toks, valid, start, reset):
    run = jax.jit(functools.partial(fn, cfg=cfg, moe_counts=True))
    return run(params, tokens=jnp.asarray(toks), valid=jnp.asarray(valid),
               slot=jnp.int32(ROW), state=state,
               slot_pos=jnp.asarray([3, 7, 0, 11], jnp.int32),
               start=jnp.int32(start), reset=jnp.asarray(reset))


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("arch", FAMILIES)
def test_one_pass_prefill_matches_the_token_serial_scan(arch, case):
    cfg, params = _family(arch)
    start, n_valid, reset = CASES[case]
    rng = np.random.default_rng(sum(map(ord, case)))
    state = _random_state(cfg, 1)
    if start:       # the row's prefix, written as an earlier chunk would be
        prefix = rng.integers(1, cfg.vocab, start).astype(np.int32)
        state, _ = _prefill(M.prefill_serial, cfg, params, state, prefix,
                            np.ones(start, bool), 0, True)
    toks = np.zeros(W, np.int32)
    toks[:n_valid] = rng.integers(1, cfg.vocab, n_valid)
    valid = np.arange(W) < n_valid
    before = jax.tree.map(np.asarray, state)
    want, want_cnt = _prefill(M.prefill_serial, cfg, params, state, toks,
                              valid, start, reset)
    got, got_cnt = _prefill(M.prefill_into_slot, cfg, params, state, toks,
                            valid, start, reset)

    others = np.arange(B) != ROW
    for (path, g), w, b in zip(jax.tree_util.tree_leaves_with_path(got),
                               jax.tree.leaves(want), jax.tree.leaves(before)):
        g, w = np.asarray(g), np.asarray(w)
        where = jax.tree_util.keystr(path)
        np.testing.assert_allclose(g[:, ROW], w[:, ROW], rtol=1e-5,
                                   atol=1e-5, err_msg=where)
        np.testing.assert_array_equal(g[:, others].view(np.uint8),
                                      b[:, others].view(np.uint8),
                                      err_msg=where)

    if cfg.moe.enabled:
        n_moe = cfg.n_layers - cfg.moe.first_dense_layers
        np.testing.assert_array_equal(np.asarray(got_cnt),
                                      np.asarray(want_cnt))
        assert int(got_cnt[0]) == n_valid * cfg.moe.top_k * n_moe
    else:
        assert got_cnt is None and want_cnt is None

    # the next block decodes the same greedy tokens from either state
    pos = np.asarray([3, 7, start + n_valid, 11], np.int32)
    nxt = jnp.asarray(rng.integers(1, cfg.vocab, B), jnp.int32)
    rem = jnp.asarray(np.clip(MAX_SEQ - pos, 0, 4), jnp.int32)
    block = jax.jit(functools.partial(M.decode_many, cfg=cfg, n_steps=4))
    live = jnp.ones((B,), bool)
    got_toks = block(params, tokens=nxt, state=got, pos=jnp.asarray(pos),
                     live=live, rem=rem)[0]
    want_toks = block(params, tokens=nxt, state=want, pos=jnp.asarray(pos),
                      live=live, rem=rem)[0]
    np.testing.assert_array_equal(np.asarray(got_toks),
                                  np.asarray(want_toks))


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "recurrentgemma-9b"])
def test_recurrent_families_keep_the_token_serial_scan(arch):
    """SSD and RG-LRU state carries token to token: ``prefill_into_slot``
    is the token-serial scan there, bit for bit."""
    cfg, params = _family(arch)
    state = _random_state(cfg, 2)
    toks = np.arange(1, W + 1, dtype=np.int32)
    valid = np.arange(W) < 5
    got, _ = _prefill(M.prefill_into_slot, cfg, params, state, toks, valid,
                      2, True)
    want, _ = _prefill(M.prefill_serial, cfg, params, state, toks, valid,
                       2, True)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _scan_lengths(jaxpr):
    """The ``length`` of every scan in ``jaxpr``, nested ones included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _scan_lengths(sub)
    return out


def test_prefill_is_one_pass_over_the_layers():
    """The smoke StableLM prefill of a 16-token segment holds one loop,
    the layer scan, and no loop over the segment; the final norm and the
    head feed nothing; and no select spans the stacked state."""
    cfg = get_smoke_config("stablelm-1.6b")
    seg = 16
    assert cfg.n_layers != seg
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: M.init_decode_state(cfg, B, 64))
    args = (params, state, jax.ShapeDtypeStruct((seg,), jnp.int32),
            jax.ShapeDtypeStruct((seg,), jnp.bool_),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.bool_))

    def fn(p, s, t, v, slot, sp, start, reset):
        return M.prefill_into_slot(p, cfg, t, v, slot, s, sp, start, reset)

    closed = jax.make_jaxpr(fn)(*args)
    assert _scan_lengths(closed.jaxpr) == [cfg.n_layers]
    used = {v for eqn in closed.jaxpr.eqns for v in eqn.invars
            if isinstance(v, jax_core.Var)}
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(args)[0]]
    head = [v for path, v in zip(paths, closed.jaxpr.invars)
            if path.startswith(("[0]['lm_head']", "[0]['final_norm']"))]
    assert head and not used.intersection(head)
    text = jax.jit(fn, donate_argnums=(1,)).lower(*args).as_text()
    assert text.count("stablehlo.while") == 1
    k = state["layers"]["k"]
    full = "tensor<{}xbf16>".format("x".join(map(str, k.shape)))
    select = re.compile(r"stablehlo\.select\b.*" + re.escape(full))
    assert full in text
    assert not [ln for ln in text.splitlines() if select.search(ln)]
