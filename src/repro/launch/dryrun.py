import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede every other import (jax locks the device count on first init)

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
# ShapeDtypeStruct stand-ins (zero allocation), print memory/cost analysis,
# and extract the roofline terms.
#
# Usage:
#   python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k --mesh both
#   python -m repro.launch.dryrun --all --out results/dryrun.jsonl

import argparse
import json
import time
import traceback

import jax
from jax.sharding import NamedSharding

from repro import configs
from repro.distributed import sharding as shx
from . import roofline as rl
from .mesh import make_production_mesh


def run_cell(cell, *, multi_pod: bool, verbose: bool = True):
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = mesh.devices.size
    t0 = time.time()
    act = {k: NamedSharding(mesh, v)
           for k, v in cell.activation_specs(mesh).items()}
    shx.set_activation_specs(act)
    try:
        fn = cell.make_fn(mesh)
        args = cell.abstract_args(mesh)
        donate = cell.meta.get("donate_argnums", ())
        with jax.set_mesh(mesh):
            # donation must match the runtime executable (train cells donate
            # the TrainState), or memory_analysis double-counts the state
            lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        if verbose:
            print(f"  memory_analysis: args={mem.argument_size_in_bytes/2**30:.3f}GiB "
                  f"out={mem.output_size_in_bytes/2**30:.3f}GiB "
                  f"temp={mem.temp_size_in_bytes/2**30:.3f}GiB "
                  f"(per device)")
            cost = compiled.cost_analysis()
            print(f"  cost_analysis: flops/chip={cost.get('flops', 0):.3e} "
                  f"bytes/chip={cost.get('bytes accessed', 0):.3e}")
        r = rl.from_compiled(cell, compiled, mesh_name, chips)
        rec = r.to_dict()
        rec.update({"status": "ok", "t_lower_s": round(t_lower, 1),
                    "t_compile_s": round(t_compile, 1),
                    "kind": cell.kind})
        if verbose:
            print(f"  roofline: compute={r.t_compute*1e3:.2f}ms "
                  f"memory={r.t_memory*1e3:.2f}ms "
                  f"collective={r.t_collective*1e3:.2f}ms "
                  f"-> {r.bottleneck}-bound; useful-flops "
                  f"{r.useful_flops_fraction:.2%}")
        return rec
    finally:
        shx.set_activation_specs({})


def run(arch_names, shape_filter, mesh_sel, out_path=None, *,
        stop_on_error=False):
    records = []
    for name in arch_names:
        arch = configs.get_arch(name)
        for shape, cell in arch.cells.items():
            if shape_filter and shape != shape_filter:
                continue
            for multi_pod in ([False, True] if mesh_sel == "both"
                              else [mesh_sel == "multi"]):
                mesh_name = "2x16x16" if multi_pod else "16x16"
                tag = f"{name}/{shape}@{mesh_name}"
                if cell.skip:
                    print(f"SKIP {tag}: {cell.skip}")
                    records.append({"arch": name, "shape": shape,
                                    "mesh": mesh_name, "status": "skip",
                                    "reason": cell.skip})
                    continue
                print(f"DRYRUN {tag} ...", flush=True)
                t0 = time.time()
                try:
                    rec = run_cell(cell, multi_pod=multi_pod)
                    print(f"OK   {tag} ({time.time()-t0:.0f}s)", flush=True)
                except Exception as e:
                    print(f"FAIL {tag}: {type(e).__name__}: {e}")
                    traceback.print_exc()
                    rec = {"arch": name, "shape": shape, "mesh": mesh_name,
                           "status": "fail", "error": f"{type(e).__name__}: {e}"}
                    if stop_on_error:
                        raise
                records.append(rec)
                if out_path:
                    with open(out_path, "a") as f:
                        f.write(json.dumps(rec) + "\n")
    return records


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--assigned", action="store_true",
                    help="the 10 assigned archs only")
    ap.add_argument("--out", default=None)
    ap.add_argument("--stop-on-error", action="store_true")
    args = ap.parse_args()
    if args.all:
        names = configs.list_archs()
    elif args.assigned:
        names = configs.ASSIGNED
    elif args.arch:
        names = [a.strip() for a in args.arch.split(",")]
    else:
        ap.error("need --arch, --assigned or --all")
    recs = run(names, args.shape, args.mesh, args.out,
               stop_on_error=args.stop_on_error)
    ok = sum(1 for r in recs if r.get("status") == "ok")
    fail = sum(1 for r in recs if r.get("status") == "fail")
    skip = sum(1 for r in recs if r.get("status") == "skip")
    print(f"\n=== dry-run summary: {ok} ok, {fail} fail, {skip} skip ===")
    return 1 if fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
