"""The plain reference against the program's own prefill and decode, on
dense weights and on weights K-block pruned and served through the
program's weight plan."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_smoke
from bench_smoke import BENCH
import harness

REF = harness.load_module(BENCH / "references" / "stablelm.py")


def program_logits(cfg, params, tokens, n_slots=8, max_seq=64,
                   exec_cfg=None):
    """Logits of the program's path: the prompt fed by
    ``prefill_into_slot`` into slot 3, then one ``masked_decode_step`` per
    remaining token, as the engine's prefill and decode run them."""
    from repro.kernels import ops
    from repro.models import model as M
    p = len(tokens) // 2
    state = M.init_decode_state(cfg, n_slots, max_seq, dtype=jnp.bfloat16)
    slot_pos = np.zeros((n_slots,), np.int32)
    live = np.zeros((n_slots,), bool)
    live[3] = True
    out = []
    with ops.exec_config(exec_cfg or ops.ExecConfig()):
        state = M.prefill_into_slot(params, cfg, jnp.asarray(tokens[:p - 1]),
                                    jnp.ones((p - 1,), bool), 3, state,
                                    jnp.asarray(slot_pos), 0, True)
        for t in range(p - 1, len(tokens)):
            toks = np.zeros((n_slots, 1), np.int32)
            toks[3, 0] = tokens[t]
            pos = np.full((n_slots,), t, np.int32)
            lg, state = M.masked_decode_step(params, cfg, jnp.asarray(toks),
                                             state, jnp.asarray(pos),
                                             jnp.asarray(live))
            out.append(np.asarray(lg[3, 0], np.float32))
    return np.stack(out)


def prune_stack(params, bk=64, bn=128):
    """Half of the (bk, bn) K-blocks of every stack matmul weight zeroed by
    the program's rule (highest L2 kept), layer by layer."""
    from repro.core.sparsity import prune_k_blocks
    layers = params["stack"]["layers"]
    out = {g: dict(v) for g, v in layers.items()}
    for g, leaf in [("attn", "wq"), ("attn", "wkv"), ("attn", "wo"),
                    ("mlp", "w_in"), ("mlp", "w_gate"), ("mlp", "w_out")]:
        w = np.asarray(layers[g][leaf], np.float32)
        tk = -(-w.shape[1] // bk)
        pruned = [prune_k_blocks(w[i], bk, bn, tk // 2)
                  for i in range(w.shape[0])]
        out[g][leaf] = jnp.asarray(np.stack(pruned), layers[g][leaf].dtype)
    return {**params, "stack": {**params["stack"], "layers": out}}


@pytest.mark.parametrize("pruned", [False, True], ids=["dense", "kb50"])
def test_program_prefill_and_decode_match_the_reference(pruned):
    c = bench_smoke.smoke_config("stablelm-1.6b")
    cfg = harness.arch_config(c["model"])
    params = REF.make_params(c["model"], jax.random.PRNGKey(7), jnp.bfloat16)
    exec_cfg = None
    if pruned:
        params = prune_stack(params)
        cfg = harness.arch_config({**c["model"],
                                   "sparsity": {"weight_sparsity": 0.5}})
        from repro.serve.engine import decode_exec_config
        exec_cfg = decode_exec_config(cfg, 8, params=params)
        assert exec_cfg.plan.entries
        params = exec_cfg.plan.attach(params)
    tokens = np.random.default_rng(0).integers(0, 512, 24).astype(np.int32)
    got = program_logits(cfg, params, tokens, exec_cfg=exec_cfg)
    flat = {k: v for k, v in c["model"].items() if not isinstance(v, dict)}
    raw = jax.tree.map(lambda x: x.w if hasattr(x, "wkidx") else x, params,
                       is_leaf=lambda x: hasattr(x, "wkidx"))
    want = np.asarray(REF.logits(raw, flat, jnp.asarray(tokens)))
    want = want[len(tokens) // 2 - 1:]
    scale = np.abs(want).max()
    # bf16 weights, activations and cache against float32 at HIGHEST:
    # a few bf16 ulps (2^-8) of the largest logit
    assert np.abs(got - want).max() <= 2 ** -6 * scale
