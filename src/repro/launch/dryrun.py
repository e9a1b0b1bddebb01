"""Multi-pod dry-run (deliverable (e)).

Proves the distribution config is coherent without real hardware: for every
assigned (architecture × input-shape) cell, ``jax.jit(step).lower(...)
.compile()`` must succeed on the single-pod (16, 16) mesh AND the two-pod
(2, 16, 16) mesh (512 placeholder host devices, set in ``main`` before the
first device is touched).  Per cell it records:

  * ``memory_analysis()``  — per-device bytes (proves the cell fits HBM),
  * ``cost_analysis()``    — per-device FLOPs / bytes accessed,
  * the post-SPMD collective schedule (parsed from ``compiled.as_text()``).

Artifacts land in ``artifacts/dryrun/<mesh>/<arch>@<shape>.json`` and feed
EXPERIMENTS.md §Dry-run and the roofline analysis (§Roofline).

Usage:
    python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import os
import time
import traceback

import jax

from repro.configs.base import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.core.descriptors import compile_network_schedule, site_plan_estimate
from repro.launch.mesh import make_production_mesh
from repro.launch.step_builders import build_cell_step, lower_cell
from repro.roofline.hlo import f32_upcast_bytes, parse_collectives

HBM_BYTES = 16 * 1024**3          # v5e: 16 GB per chip


def run_cell(arch_id: str, shape_name: str, mesh_kind: str,
             out_dir: str) -> dict:
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_dev = mesh.devices.size
    t0 = time.time()
    step = build_cell_step(arch_id, shape_name, mesh)
    lowered = lower_cell(step)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    mem = {k: int(getattr(ma, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")}
    # donated args alias outputs: live set = args + temps + (out - aliased)
    live = (mem["argument_size_in_bytes"] + mem["temp_size_in_bytes"]
            + max(mem["output_size_in_bytes"] - mem["alias_size_in_bytes"], 0))
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    cost = {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and k in
            ("flops", "bytes accessed", "transcendentals",
             "bytes accessed output", "optimal_seconds")}

    hlo = compiled.as_text()
    coll = parse_collectives(hlo, n_dev)

    # per-site descriptor table (§III-A registers): the chosen dataflow +
    # sparsity mode per matmul site, observable alongside the XLA analysis.
    # Coverage is total (ISSUE 4): MoE batched-expert einsum sites
    # (moe.experts_*, with per-expert plan economics under "experts" /
    # "per_expert_*"), shared-expert and router sites, and the lm_head
    # logits contraction all carry entries like the 2-D matmul leaves.
    # "plan" records the weight-sparsity-plan economics per site (density,
    # tight max_nnz vs tk, ZVC bytes saved) — modeled from the config prior,
    # since the dry-run lowers against ShapeDtypeStructs (no real params);
    # engines with params measure the same stats via WeightSparsityPlan.
    arch_cfg = get_config(arch_id)
    n_model_shards = int(dict(mesh.shape).get("model", 1))
    ns = compile_network_schedule(arch_cfg, SHAPES[shape_name],
                                  model_shards=n_model_shards)
    sites = {
        name: {
            "m": d.m, "n": d.n, "k": d.k,
            "stationarity": d.schedule.stationarity,
            "blocks": [d.schedule.bm, d.schedule.bn, d.schedule.bk],
            "ic_p": d.reduce.ic_p, "reduce_strategy": d.reduce.strategy,
            "sparsity_mode": d.sparsity_mode,
            "hbm_bytes": d.schedule.hbm_bytes,
            "flops": d.schedule.flops,
            "plan": site_plan_estimate(d, arch_cfg,
                                       model_shards=n_model_shards),
        } for name, d in ns.sites.items()}
    # XLA:CPU float-normalization inflation (absent on the TPU target):
    # hoisted f32 copies of bf16 scan-carried weights/caches.  Subtract a
    # conservative estimate (never below temp/3) for the TPU-side verdict.
    upcast = f32_upcast_bytes(hlo)
    temp_tpu = max(mem["temp_size_in_bytes"] - upcast,
                   mem["temp_size_in_bytes"] // 3)
    live_tpu = (mem["argument_size_in_bytes"] + temp_tpu
                + max(mem["output_size_in_bytes"]
                      - mem["alias_size_in_bytes"], 0))
    record = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_kind,
        "devices": n_dev,
        "mesh_shape": dict(zip(mesh.axis_names,
                               [int(s) for s in mesh.devices.shape])),
        "n_micro": step.shape.n_micro, "remat": step.shape.remat,
        "sites": sites,
        "seconds": {"lower": round(t_lower, 1),
                    "compile": round(t_compile, 1)},
        "memory": mem,
        "live_bytes_per_device": int(live),
        "f32_upcast_bytes": int(upcast),
        "live_bytes_tpu_est": int(live_tpu),
        "fits_hbm": bool(live_tpu <= HBM_BYTES),
        "cost": cost,
        "collectives": {
            "operand_bytes": coll.operand_bytes,
            "wire_bytes": coll.wire_bytes,
            "by_kind": coll.by_kind(),
            "count": len(coll.ops),
        },
        "hlo_lines": hlo.count("\n"),
        "ok": True,
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch_id}@{shape_name}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    # compile-only tool: placeholder CPU devices, never an accelerator
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    jax.config.update("jax_platforms", "cpu")

    if args.all:
        cells = [(a, s) for a in ARCH_IDS for s in SHAPES
                 if shape_applicable(get_config(a), SHAPES[s])]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    failures = []
    for mesh_kind in meshes:
        out_dir = os.path.join(args.out, mesh_kind)
        for arch_id, shape_name in cells:
            path = os.path.join(out_dir, f"{arch_id}@{shape_name}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"[skip] {arch_id}@{shape_name} ({mesh_kind})")
                continue
            tag = f"{arch_id}@{shape_name} ({mesh_kind})"
            try:
                r = run_cell(arch_id, shape_name, mesh_kind, out_dir)
                print(f"[ok]   {tag}  live={r['live_bytes_per_device']/2**30:.2f}GiB "
                      f"tpu_est={r['live_bytes_tpu_est']/2**30:.2f}GiB "
                      f"fits={r['fits_hbm']} "
                      f"flops/dev={r['cost'].get('flops', 0):.3e} "
                      f"coll={r['collectives']['wire_bytes']/2**30:.3f}GiB "
                      f"compile={r['seconds']['compile']}s", flush=True)
                if not r["fits_hbm"]:
                    failures.append((tag, "exceeds HBM"))
            except Exception as e:  # noqa: BLE001 — record and continue
                traceback.print_exc()
                failures.append((tag, repr(e)[:200]))
                os.makedirs(out_dir, exist_ok=True)
                with open(path, "w") as f:
                    json.dump({"arch": arch_id, "shape": shape_name,
                               "mesh": mesh_kind, "ok": False,
                               "error": traceback.format_exc()[-2000:]},
                              f, indent=1)
                print(f"[FAIL] {tag}: {e}", flush=True)

    print(f"\n{len(cells) * len(meshes) - len(failures)}/"
          f"{len(cells) * len(meshes)} cells passed")
    for tag, err in failures:
        print(f"  FAIL {tag}: {err}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
