"""Energy/latency model invariants (the paper's own evaluation framework),
with hypothesis property tests."""
from hypothesis import given, settings, strategies as st
import numpy as np
import pytest

from repro.core.energy_model import (DENSE, EYERISS, FLEXNN, TPU, ConvLayer,
                                     Schedule, SparsityStats, evaluate,
                                     flexnn_variant, rf_feasible)
from repro.core.scheduler import (enumerate_schedules, optimize_layer,
                                  optimize_network, select_matmul_schedule,
                                  roofline_time, TPU_V5E)
from repro.configs.cnn_zoo import NETWORKS, resnet50

L1 = ConvLayer("l1", ox=56, oy=56, oc=256, ic=64)            # 1x1 (paper §II)
L3 = ConvLayer("l3", ox=28, oy=28, oc=128, ic=128, fx=3, fy=3)
LDW = ConvLayer("ldw", ox=28, oy=28, oc=144, ic=144, fx=3, fy=3, groups=144)
SP = SparsityStats(act_density=0.5, wt_density=0.4)


def test_macs_counts():
    assert L1.macs == 56 * 56 * 256 * 64
    assert L3.macs == 28 * 28 * 128 * 128 * 9
    assert LDW.macs == 28 * 28 * 144 * 9          # depthwise: ic/groups = 1


def test_paper_resnet50_example_dims():
    """§II-A: ResNet50 2nd conv: IF 56×56×64, FL 1×1×64×256, OF 56×56×256."""
    net = resnet50()
    l = next(l for l in net if l.ic == 64 and l.oc == 256 and l.fx == 1)
    assert (l.ox, l.oy) == (56, 56)
    assert l.if_size == 56 * 56 * 64
    assert l.of_size == 56 * 56 * 256


@pytest.mark.parametrize("layer", [L1, L3, LDW])
def test_sparsity_reduces_cost(layer):
    """Two-sided ≤ weight-sided ≤ dense, in energy AND cycles (fixed sched)."""
    sched = Schedule(b_ic=8, b_oc=4, b_ox=2, b_oy=2, p_oc=8, p_ic=2)
    dense = evaluate(layer, sched, flexnn_variant("none"), SP)
    ws = evaluate(layer, sched, flexnn_variant("weight"), SP)
    two = evaluate(layer, sched, FLEXNN, SP)
    assert two.energy <= ws.energy <= dense.energy
    assert two.cycles <= ws.cycles <= dense.cycles


def test_dense_stats_equalize_variants():
    """With no sparsity, all three variants cost the same."""
    sched = Schedule(b_ic=8, b_oc=4, p_oc=8)
    costs = [evaluate(L1, sched, flexnn_variant(v), DENSE).energy
             for v in ("none", "weight", "two_sided")]
    assert max(costs) - min(costs) < 1e-6 * costs[0]


def test_flexible_beats_fixed_dataflows():
    """The paper's core claim: per-layer optimal schedule ≤ any fixed one
    on the same hardware description."""
    for layer in (L1, L3):
        flex = optimize_layer(layer, FLEXNN, DENSE).energy
        for df in ("ws", "os", "is", "nlr", "rs"):
            fixed = optimize_layer(layer, FLEXNN, DENSE, dataflow=df).energy
            assert flex <= fixed * (1 + 1e-9), (layer.name, df)


def test_optimize_network_runs_over_resnet50():
    costs = optimize_network(resnet50()[:8], FLEXNN)
    assert all(c.energy > 0 and c.cycles > 0 for c in costs)


@settings(max_examples=15, deadline=None)
@given(ox=st.sampled_from([7, 14, 28, 56]),
       oc=st.sampled_from([16, 64, 256]),
       ic=st.sampled_from([16, 64, 256]),
       fx=st.sampled_from([1, 3]),
       da=st.floats(0.2, 0.6), dw=st.floats(0.2, 1.0))
def test_cost_positive_and_sparsity_monotone(ox, oc, ic, fx, da, dw):
    """Monotone within the compression-pays regime (density ≤ 0.875 — above
    it the 1 bit/byte ZVC bitmap overhead exceeds the savings, which the
    model correctly charges; §IV)."""
    layer = ConvLayer("h", ox=ox, oy=ox, oc=oc, ic=ic, fx=fx, fy=fx)
    sched = Schedule(b_ic=min(8, ic), b_oc=min(4, oc), p_oc=min(8, oc))
    sp = SparsityStats(da, dw)
    c = evaluate(layer, sched, FLEXNN, sp)
    assert c.energy > 0 and c.cycles > 0
    denser = evaluate(layer, sched, FLEXNN,
                      SparsityStats(min(da * 1.3, 0.875), dw))
    assert c.energy <= denser.energy * (1 + 1e-9)


def test_rf_feasibility_caps_blocking():
    big = Schedule(b_ic=64, b_oc=64, b_ox=16, b_oy=16)
    assert not rf_feasible(L3, big, FLEXNN)
    # 3×3 conv: FL tile = 9·b_ic·b_oc bytes must fit the 64 B FL RF
    assert rf_feasible(L3, Schedule(b_ic=4, b_oc=1), FLEXNN)
    assert not rf_feasible(L3, Schedule(b_ic=4, b_oc=4), FLEXNN)   # 144 B
    assert rf_feasible(L1, Schedule(b_ic=4, b_oc=4), FLEXNN)       # 1×1: 16 B


def test_enumerate_schedules_all_feasible():
    scheds = list(enumerate_schedules(L3, FLEXNN))
    assert len(scheds) > 100
    for s in scheds[::97]:
        assert rf_feasible(L3, s, FLEXNN)


def test_eyeriss_tpu_cost_ratios():
    """Table I: Eyeriss RF 1:1, TPU RF 0.06, FlexNN 0.125; SRAM 6, DRAM 200."""
    assert EYERISS.cost_rf == 1.0 and EYERISS.cost_inter_pe == 2.0
    assert TPU.cost_rf == 0.06
    assert FLEXNN.cost_rf == 0.125
    for acc in (EYERISS, TPU, FLEXNN):
        assert acc.cost_sram == 6.0 and acc.cost_dram == 200.0


def test_vectorized_matches_scalar():
    """The vectorized grid search winner re-scores identically in the scalar
    evaluator (semantics pin)."""
    best = optimize_layer(L3, FLEXNN, SP)
    rescored = evaluate(L3, best.schedule, FLEXNN, SP)
    assert abs(best.energy - rescored.energy) < 1e-6 * rescored.energy
    assert abs(best.cycles - rescored.cycles) < 1e-6 * rescored.cycles


# ---------------------------------------------------------------------------
# TPU-native matmul schedule selection
# ---------------------------------------------------------------------------

def test_select_matmul_schedule_fits_vmem():
    s = select_matmul_schedule(4096, 4096, 4096)
    vmem = (s.bm * s.bk + s.bk * s.bn) * 2 * 2 + s.bm * s.bn * 4
    assert vmem <= TPU_V5E.vmem_bytes
    assert s.flops == 2.0 * 4096 ** 3
    assert roofline_time(s) > 0


def test_select_matmul_schedule_prefers_reuse_for_skinny():
    """Tall-skinny (decode-like) matmuls should not pick output-stationary
    128³ blindly — HBM traffic must be ≤ the naive default's."""
    naive = select_matmul_schedule(128, 128, 128)
    s = select_matmul_schedule(128, 8192, 8192)
    assert s.hbm_bytes <= 2 * (128 * 8192 + 8192 * 8192 + 128 * 8192) * 2.5


def test_ic_p_splits_contraction():
    s1 = select_matmul_schedule(1024, 1024, 8192, ic_p=1)
    s8 = select_matmul_schedule(1024, 1024, 8192, ic_p=8)
    assert s8.flops == pytest.approx(s1.flops / 8)
