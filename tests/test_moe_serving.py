"""The dropless, per-token MoE layer that serving runs (``moe.decode_moe``),
one chip's share of the routed experts, and its plain reference
(``benchmarks/chip/references/deepseek_moe.py``), at smoke size on the CPU.
"""
import dataclasses
import functools
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, MoEConfig, get_smoke_config
from repro.models import model as M
from repro.models import moe as moe_mod
from repro.serve.engine import ServeEngine

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmarks" / "chip"
CONFIG = json.loads(
    (BENCH / "configs" / "deepseek-moe-16b-ep8.json").read_text())


def _load_reference():
    path = BENCH / "references" / "deepseek_moe.py"
    spec = importlib.util.spec_from_file_location("ref_deepseek_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()

# the benchmark configuration's model at smoke width: every MoE key as
# the file has it but the expert counts and widths
SMOKE_MODEL = {**CONFIG["model"], "n_layers": 3, "d_model": 64,
               "n_heads": 4, "n_kv_heads": 4, "head_dim": 16, "d_ff": 96,
               "vocab": 256,
               "moe": {**CONFIG["model"]["moe"], "n_experts": 16,
                       "experts_held": 4, "expert_d_ff": 32}}


def _flat(model):
    return {k: v for k, v in model.items() if not isinstance(v, dict)}


def _cfg(model=SMOKE_MODEL, **moe):
    cfg = ArchConfig(**model)
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **moe))


def _layer_params(cfg, seed=0, dtype=jnp.float32):
    return moe_mod.init_moe(cfg, jax.random.PRNGKey(seed), dtype)


def _decode_moe(p, cfg, x):
    return jax.jit(functools.partial(moe_mod.decode_moe, cfg=cfg))(p, x=x)


def _share(p, offset, held):
    """The routed experts [offset, offset + held) of an uncut layer."""
    out = dict(p)
    for key in ("experts_in", "experts_gate", "experts_out"):
        out[key] = p[key][offset:offset + held]
    return out


def _program_logits(cfg, params, tokens, n_slots=4, max_seq=32):
    """The program's served path: the first half of ``tokens`` fed by
    ``prefill_into_slot`` into slot 1, then one ``masked_decode_step``
    per remaining token; the logits at the decoded positions."""
    p = len(tokens) // 2
    dtype = jax.tree.leaves(params)[0].dtype
    state = M.init_decode_state(cfg, n_slots, max_seq, dtype=dtype)
    live = np.zeros((n_slots,), bool)
    live[1] = True
    state = jax.jit(functools.partial(M.prefill_into_slot, cfg=cfg))(
        params, tokens=jnp.asarray(tokens[:p - 1]),
        valid=jnp.ones((p - 1,), bool), slot=1, state=state,
        slot_pos=jnp.zeros((n_slots,), jnp.int32))
    step = jax.jit(functools.partial(M.masked_decode_step, cfg=cfg))
    out = []
    for t in range(p - 1, len(tokens)):
        toks = np.zeros((n_slots, 1), np.int32)
        toks[1, 0] = tokens[t]
        lg, state = step(params, tokens=jnp.asarray(toks), state=state,
                         pos=jnp.full((n_slots,), t, jnp.int32),
                         active=jnp.asarray(live))
        out.append(np.asarray(lg[1, 0], np.float32))
    return np.stack(out)


# float32: the program's dots and the reference's differ only in the order
# of their sums (4e-7 of the largest logit read); bfloat16 weights,
# activations and cache against float32 at HIGHEST: each rounding is 2^-8
# relative, the smoke model's logits are small beside its hidden state
# (1 % of the largest logit read on three seeds), and a near-tie among the
# top 6 that the rounding flips swaps one held expert's gated part
# (3 % read on one seed): 2^-4 of the largest logit
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2 ** -4)],
                         ids=["f32", "bf16"])
def test_served_logits_match_the_reference(dtype, tol):
    cfg = ArchConfig(**SMOKE_MODEL)
    assert cfg.moe.partial and cfg.moe.expert_offset == REF.EXPERT_OFFSET
    params = jax.jit(lambda k: REF.make_params(SMOKE_MODEL, k, dtype))(
        jax.random.PRNGKey(3))
    tokens = np.random.default_rng(0).integers(0, 256, 20).astype(np.int32)
    got = _program_logits(cfg, params, tokens)
    ref = jax.jit(functools.partial(REF.logits, model=_flat(SMOKE_MODEL)))
    want = np.asarray(ref(params, tokens=jnp.asarray(tokens)))
    want = want[len(tokens) // 2 - 1:]
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


def test_expert_shares_sum_to_the_uncut_layer():
    """Eight chips' shares of 16 experts, each holding 2: their routed
    parts plus the shared experts, counted once, give the uncut layer, and
    the uncut layer is the reference's."""
    cfg = _cfg(experts_held=16)
    p = _layer_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 1, cfg.d_model))
    whole, _ = _decode_moe(p, cfg, x)
    shared = moe_mod._shared_ffn(p["shared"], x[:, 0])
    total = shared
    for offset in range(0, 16, 2):
        part, _ = _decode_moe(_share(p, offset, 2),
                              _cfg(experts_held=2, expert_offset=offset), x)
        total = total + (part[:, 0] - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole[:, 0]),
                               rtol=1e-5, atol=1e-5)
    want = REF.expert_share(p, x[:, 0]) + REF._swiglu(p["shared"], x[:, 0])
    np.testing.assert_allclose(np.asarray(whole[:, 0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_a_row_does_not_depend_on_its_neighbours():
    """A row's output is the same whatever the other rows hold, filler
    included, and when every row picks the same experts."""
    cfg = _cfg()
    p = _layer_params(cfg)
    rng = np.random.default_rng(2)
    x = rng.normal(size=(8, 1, cfg.d_model)).astype(np.float32)
    base, _ = _decode_moe(p, cfg, jnp.asarray(x))
    for others in ("zeros", "random", "collide"):
        y = x.copy()
        if others == "zeros":              # idle slots' token-0 filler
            y[1:] = 0.0
        elif others == "random":
            y[1:] = rng.normal(size=y[1:].shape)
        else:                              # every row routes as row 0
            y[1:] = y[0]
        got, _ = _decode_moe(p, cfg, jnp.asarray(y))
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(base[0]))
        if others == "collide":
            np.testing.assert_array_equal(np.asarray(got[1:]),
                                          np.asarray(got[:1].repeat(7, 0)))


def test_served_stream_does_not_depend_on_its_neighbours():
    """Through the whole model: slot 0's logits are the same next to live
    rows, filler rows or copies of itself."""
    cfg = _cfg({**SMOKE_MODEL, "n_layers": 2}, experts_held=16)
    params = M.init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    state = M.init_decode_state(cfg, 4, 16, dtype=jnp.float32)
    pos = jnp.zeros((4,), jnp.int32)
    step = jax.jit(functools.partial(M.masked_decode_step, cfg=cfg))
    outs = []
    for toks, live in (([7, 0, 0, 0], [1, 0, 0, 0]),
                       ([7, 9, 3, 5], [1, 1, 1, 1]),
                       ([7, 7, 7, 7], [1, 1, 0, 1])):
        lg, _ = step(params, tokens=jnp.asarray(toks, jnp.int32)[:, None],
                     state=state, pos=pos, active=jnp.asarray(live, bool))
        outs.append(np.asarray(lg[0]))
    for other in outs[1:]:
        np.testing.assert_array_equal(other, outs[0])


@pytest.mark.parametrize("norm", [False, True])
def test_gates_follow_norm_topk_prob(norm):
    """``norm_topk_prob`` False keeps the softmax probabilities of the top
    k as gates (DeepSeek-MoE's published gate); True renormalises them."""
    cfg = _cfg(experts_held=16, norm_topk_prob=norm)
    p = _layer_params(cfg)
    x = np.random.default_rng(5).normal(size=(3, cfg.d_model))
    x = x.astype(np.float32)
    got, _ = _decode_moe(p, cfg, jnp.asarray(x)[:, None])
    probs = np.asarray(jax.nn.softmax(x @ np.asarray(p["router"]), -1))
    want = np.array(moe_mod._shared_ffn(p["shared"], jnp.asarray(x)))
    for t in range(3):
        top = np.argsort(-probs[t])[:cfg.moe.top_k]
        gates = probs[t, top] / (probs[t, top].sum() if norm else 1.0)
        for e, g in zip(top, gates):
            one = _share(p, int(e), 1)
            h = x[t] @ np.asarray(one["experts_in"][0])
            gt = x[t] @ np.asarray(one["experts_gate"][0])
            act = gt / (1 + np.exp(-gt)) * h
            want[t] += g * (act @ np.asarray(one["experts_out"][0]))
    np.testing.assert_allclose(np.asarray(got[:, 0]), want, rtol=1e-4,
                               atol=1e-4)


def test_the_capacity_path_refuses_a_share_of_the_experts():
    cfg = ArchConfig(**SMOKE_MODEL)
    p = _layer_params(cfg)
    x = jnp.zeros((1, 4, cfg.d_model))
    for fn in (moe_mod.apply_moe, moe_mod.apply_moe_gshard):
        with pytest.raises(ValueError, match="holds 4 of 16"):
            fn(p, cfg, x)


def test_json_model_builds_the_moe_group():
    cfg = ArchConfig(**CONFIG["model"])
    moe = CONFIG["model"]["moe"]
    assert isinstance(cfg.moe, MoEConfig) and cfg.moe.enabled
    assert (cfg.moe.n_experts, cfg.moe.experts_held, cfg.moe.expert_offset,
            cfg.moe.top_k, cfg.moe.n_shared, cfg.moe.expert_d_ff,
            cfg.moe.first_dense_layers, cfg.moe.norm_topk_prob) == (
        moe["n_experts"], moe["experts_held"], moe["expert_offset"],
        moe["top_k"], moe["n_shared"], moe["expert_d_ff"],
        moe["first_dense_layers"], moe["norm_topk_prob"])
    assert ArchConfig(**{**CONFIG["model"], "moe": {}}).moe == MoEConfig()
    with pytest.raises(ValueError, match="do not lie in"):
        MoEConfig(n_experts=8, experts_held=4, expert_offset=6)


def test_reference_constants_are_the_configuration_files():
    moe = CONFIG["model"]["moe"]
    assert REF.TOP_K == moe["top_k"]
    assert REF.NORM_TOPK_PROB == moe["norm_topk_prob"]
    assert REF.EXPERT_OFFSET == moe["expert_offset"]
    pub = CONFIG["published"]
    assert (pub["num_experts_per_tok"], pub["norm_topk_prob"],
            pub["n_routed_experts"]) == (moe["top_k"],
                                         moe["norm_topk_prob"],
                                         moe["n_experts"])
    # the smoke configuration of the program's registry keeps the gate too
    assert not get_smoke_config("deepseek-moe-16b").moe.norm_topk_prob


@functools.partial(jax.jit, static_argnames="model")
def _host_routing(params, model, seq):
    """Top-k expert ids (n_moe_layers, S, k) of every position of ``seq``,
    through the reference's blocks."""
    model = dict(model)
    positions = jnp.arange(seq.shape[0], dtype=jnp.int32)
    x = params["embed"][seq].astype(jnp.float32)
    stack = params["stack"]
    for i in range(stack["dense_layers"]["ln1"]["scale"].shape[0]):
        lp = jax.tree.map(lambda a: a[i], stack["dense_layers"])
        x = REF._block(model, lp, x, positions, REF._dense_mlp)
    picks = []
    for i in range(stack["layers"]["ln1"]["scale"].shape[0]):
        lp = jax.tree.map(lambda a: a[i], stack["layers"])
        h = x + REF._attention(model, lp["attn"],
                               REF._rmsnorm(lp["ln1"], x), positions)
        y = REF._rmsnorm(lp["ln2"], h)
        probs = jax.nn.softmax(y @ lp["moe"]["router"], axis=-1)
        picks.append(jax.lax.top_k(probs, REF.TOP_K)[1])
        x = REF._block(model, lp, x, positions, REF._moe_mlp)
    return jnp.stack(picks)


def test_engine_counts_routing_of_live_rows():
    """``health()["counters"]`` sums the routing picks of the rows that
    commit, over all experts and per held expert, as counted on the host
    from the served tokens; filler rows add nothing."""
    cfg = ArchConfig(**SMOKE_MODEL)
    params = jax.jit(lambda k: REF.make_params(SMOKE_MODEL, k,
                                               jnp.float32))(
        jax.random.PRNGKey(6))
    eng = ServeEngine(cfg, params, n_slots=4, max_seq=32,
                      dtype=jnp.float32, prefill_chunk=4, decode_block=4)
    before = eng.health()["counters"]
    assert before["moe_assignments"] == 0
    assert before["moe_assignments_held"] == [0] * 4
    rng = np.random.default_rng(8)
    reqs = [(rng.integers(1, 256, n).astype(np.int32), new)
            for n, new in ((5, 6), (9, 3), (2, 7))]
    uids = [eng.submit(p, max_new=new) for p, new in reqs]
    out = eng.run_until_drained()
    counters = eng.health()["counters"]
    n_moe = cfg.n_layers - cfg.moe.first_dense_layers
    picks = []
    for uid, (prompt, _) in zip(uids, reqs):
        seq = np.concatenate([prompt, np.asarray(out[uid], np.int32)])[:-1]
        picks.append(np.asarray(_host_routing(
            params, tuple(_flat(SMOKE_MODEL).items()), jnp.asarray(seq))))
    picks = np.concatenate(picks, axis=1)
    assert counters["moe_assignments"] == picks.size == sum(
        (len(p) + len(out[u]) - 1) * n_moe * REF.TOP_K
        for u, (p, _) in zip(uids, reqs))
    held = [int((picks == e).sum()) for e in range(4)]
    assert counters["moe_assignments_held"] == held
    assert sum(held) > 0
