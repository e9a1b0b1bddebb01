"""System-level tests: the dry-run/roofline machinery end-to-end at reduced
scale (subprocess meshes), artifact sanity, and the benchmark validations."""
import glob
import json
import os

import pytest

from conftest import REPO_ROOT, run_with_devices

# heavy: subprocess meshes + artifact validation — excluded from the fast
# CI lane
pytestmark = pytest.mark.slow

ART = os.path.join(REPO_ROOT, "artifacts")


def test_dryrun_cell_small_mesh():
    """The dry-run path (lower → compile → memory/cost/collectives) works on
    a reduced arch over an 8-device (2 data × 4 model) mesh."""
    run_with_devices("""
import dataclasses, jax
from repro.configs.base import get_smoke_config, SHAPES
from repro.launch.step_builders import build_cell_step, lower_cell
from repro.roofline.hlo import parse_collectives

mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_smoke_config('yi-9b')
shape = dataclasses.replace(SHAPES['train_4k'], seq_len=64, global_batch=4,
                            n_micro=1, loss_chunk=32, attn_chunk=32,
                            remat='none')
step = build_cell_step('yi-9b', 'train_4k', mesh, cfg=cfg, shape=shape)
compiled = lower_cell(step).compile()
ma = compiled.memory_analysis()
assert ma.temp_size_in_bytes > 0
ca = compiled.cost_analysis()
ca = ca[0] if isinstance(ca, (list, tuple)) else ca
assert ca['flops'] > 0
coll = parse_collectives(compiled.as_text(), 8)
assert coll.wire_bytes > 0          # FSDP/TP collectives present
print('dry-run small mesh OK:', int(ca['flops']), 'flops/dev')
""")


def test_decode_cell_small_mesh():
    run_with_devices("""
import dataclasses, jax
from repro.configs.base import get_smoke_config, SHAPES
from repro.launch.step_builders import build_cell_step, lower_cell

mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_smoke_config('gemma-2b')
shape = dataclasses.replace(SHAPES['decode_32k'], seq_len=64, global_batch=4)
step = build_cell_step('gemma-2b', 'decode_32k', mesh, cfg=cfg, shape=shape)
compiled = lower_cell(step).compile()
assert compiled.memory_analysis().output_size_in_bytes > 0
print('decode cell OK')
""")


def test_roofline_slope_fit_exact_on_synthetic():
    from repro.roofline.analysis import fit_and_extrapolate
    # cost = 10 + 3·L exactly → extrapolation must be exact
    pts = [([1.0, 1.0], {m: 13.0 for m in _metrics()}),
           ([1.0, 2.0], {m: 16.0 for m in _metrics()})]
    out = fit_and_extrapolate(pts, [1.0, 80.0])
    assert abs(out["flops"] - (10 + 3 * 80)) < 1e-6


def _metrics():
    from repro.roofline.analysis import METRICS
    return METRICS


def test_structure_points_families():
    from repro.configs.base import get_config
    from repro.roofline.analysis import structure_points
    pts, full = structure_points(get_config("yi-9b"))
    assert [p[0].n_layers for p in pts] == [1, 2] and full == [1.0, 48.0]
    pts, full = structure_points(get_config("deepseek-moe-16b"))
    assert [p[0].n_layers for p in pts] == [2, 3]      # 1 dense + {1,2} moe
    assert full == [1.0, 27.0]
    pts, full = structure_points(get_config("recurrentgemma-9b"))
    assert [p[0].n_layers for p in pts] == [3, 6, 5]
    assert full == [1.0, 12.0, 1.0]                    # 12 groups + trailing


# ---------------------------------------------------------------------------
# Artifact gates (produced by the dry-run / roofline sweeps)
# ---------------------------------------------------------------------------

def _records(mesh):
    return [json.load(open(p))
            for p in glob.glob(f"{ART}/dryrun/{mesh}/*.json")]


@pytest.mark.parametrize("mesh", ["single", "multi"])
def test_dryrun_artifacts_complete_and_green(mesh):
    recs = _records(mesh)
    if not recs:
        pytest.skip("dry-run artifacts not generated in this checkout")
    assert len(recs) == 32                     # 10 archs × shapes − skips
    for r in recs:
        assert r["ok"], r["arch"]
        assert r["fits_hbm"], (r["arch"], r["shape"],
                               r["live_bytes_tpu_est"] / 2**30)
        assert r["cost"]["flops"] > 0
        assert r["devices"] == (512 if mesh == "multi" else 256)


def test_multi_pod_actually_shards_pod_axis():
    """512-dev mesh halves per-device work vs 256-dev — with the known
    scan-body-once caveat: microbatched train cells keep the same per-micro
    device batch (n_micro is clamped instead), so their *reported* per-device
    FLOPs stay ≈flat while true per-step FLOPs halve (the roofline pipeline
    accounts for this via the unrolled slope fits)."""
    recs = _records("multi")
    if not recs:
        pytest.skip("no artifacts")
    singles = {(r["arch"], r["shape"]): r for r in _records("single")}
    checked = 0
    for r in recs:
        if r["shape"] == "long_500k":     # batch=1: unshardable on batch
            continue
        s = singles[(r["arch"], r["shape"])]
        ratio = r["cost"]["flops"] / s["cost"]["flops"]
        # per-device per-(micro)step tokens set the expectation: cost_analysis
        # counts the microbatch scan body once, so the expected ratio is
        # (nm_single·256)/(nm_multi·512)
        expected = (s["n_micro"] * 256) / (r["n_micro"] * 512)
        # decode steps are tiny: replicated per-step overhead (norms on a
        # few rows, state plumbing) pushes the ratio above the ideal
        slack = 2.0 if r["shape"].startswith("decode") else 1.45
        assert expected * 0.7 <= ratio <= expected * slack, \
            (r["arch"], r["shape"], ratio, expected)
        checked += 1
    assert checked == 30
