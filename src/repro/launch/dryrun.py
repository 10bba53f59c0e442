import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell on placeholder devices; record memory/cost/collective analysis.

The two lines above MUST stay the first statements in this file — jax locks
the device count at first init, and every import below may pull jax in.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out artifacts/dryrun
  (per-cell JSON is cached; --force recompiles)
"""
import argparse
import json
import pathlib
import time
import traceback


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             force: bool = False, perf_override=None, tag: str = "") -> dict:
    from repro.configs import SHAPES, get_config
    from repro.launch import roofline as RF
    from repro.launch.cells import perf_for
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import (make_decode_step, make_prefill_step,
                                    make_train_step, params_sds)

    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    out = pathlib.Path(out_dir) / mesh_name
    out.mkdir(parents=True, exist_ok=True)
    fname = out / f"{arch}__{shape_name}{tag}.json"
    if fname.exists() and not force:
        return json.loads(fname.read_text())

    cfg = get_config(arch)
    cell = SHAPES[shape_name]
    data_width = 32 if multi_pod else 16
    perf = perf_override or perf_for(arch, shape_name, data_width)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256

    t0 = time.time()
    if cell.kind == "train":
        jt, args = make_train_step(cfg, cell, mesh, perf=perf,
                                   multi_pod=multi_pod)
    elif cell.kind == "prefill":
        jt, args = make_prefill_step(cfg, cell, mesh, perf=perf,
                                     multi_pod=multi_pod)
    else:
        jt, args = make_decode_step(cfg, cell, mesh, perf=perf,
                                    multi_pod=multi_pod)
    lowered = jt.lower(*args)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = RF.parse_collectives(hlo)

    # XLA cost analysis counts scan bodies ONCE -> reconstruct true totals
    # from per-component compiles x trip counts (see costing.py)
    from repro.launch.costing import ComponentCoster
    coster = ComponentCoster(cfg, cell, mesh, perf, multi_pod=multi_pod)
    t0 = time.time()
    recon = coster.reconstruct(
        {"flops": float(cost.get("flops", 0.0)),
         "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
        float(coll["total_wire_bytes"]))
    t_cost = time.time() - t0
    total = recon["total"]

    mf = RF.model_flops_per_device(cfg, cell, params_sds(cfg), n_chips)
    terms = RF.roofline(
        {"flops": total["flops"], "bytes accessed": total["bytes"]},
        {"total_wire_bytes": total["wire"]}, mf)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": cell.kind, "n_chips": n_chips,
        "perf": {"remat": perf.remat, "attn_chunk": perf.attn_chunk,
                 "accum_steps": perf.accum_steps},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_per_device": mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "cost_raw_scan_once": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
        "cost": {"flops": total["flops"], "bytes_accessed": total["bytes"],
                 "wire_bytes": total["wire"], "costing_s": round(t_cost, 1)},
        "cost_components": {
            name: {"flops": c["cost"]["flops"], "bytes": c["cost"]["bytes"],
                   "wire": c["cost"]["wire"], "true_count": c["true"]}
            for name, c in recon["per_component"].items()},
        "collectives": {k: (v if isinstance(v, dict) else float(v))
                        for k, v in coll.items()},
        "roofline": {
            "compute_s": terms.compute_s,
            "memory_s": terms.memory_s,
            "collective_s": terms.collective_s,
            "dominant": terms.dominant,
            "model_flops_per_device": terms.model_flops,
            "useful_flop_ratio": terms.useful_ratio,
            "compute_fraction_of_bound": terms.roofline_fraction,
        },
    }
    fname.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    from repro.configs import SHAPES, cell_is_runnable, get_config, \
        list_configs
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = list_configs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        cfg = get_config(arch)
        for shape in shapes:
            ok, why = cell_is_runnable(cfg, SHAPES[shape])
            if not ok:
                print(f"SKIP  {arch:24s} {shape:12s} ({why})")
                continue
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                try:
                    rec = run_cell(arch, shape, mp, args.out,
                                   force=args.force)
                    r = rec["roofline"]
                    print(f"OK    {arch:24s} {shape:12s} {mesh_name:11s} "
                          f"compile={rec['compile_s']:7.1f}s "
                          f"mem/dev={rec['memory']['peak_bytes_per_device']/2**30:6.2f}GiB "
                          f"[C {r['compute_s']:.2e} M {r['memory_s']:.2e} "
                          f"N {r['collective_s']:.2e}] dom={r['dominant']}",
                          flush=True)
                except Exception as e:
                    failures.append((arch, shape, mesh_name, repr(e)))
                    print(f"FAIL  {arch:24s} {shape:12s} {mesh_name:11s} "
                          f"{type(e).__name__}: {e}", flush=True)
                    traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: "
                         + "; ".join(f"{a}/{s}/{m}" for a, s, m, _ in failures))
    print("ALL CELLS PASSED")


if __name__ == "__main__":
    main()
