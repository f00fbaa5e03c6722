"""Readings that the limits of ``correct`` are set from, for a cell of the
expert-encoder driver (``bench/drivers/train_moe.py``), on the chip.

    python bench/tools/limits_moe.py --workload train.moonlight_1chip.mind \
        --seeds 2 --controls 1 [--capacity-fault] [--first-seed N] \
        [--out chiprun_out/limits]

For each seed: the program's readings of the timed path (the same set-up
the cell's runs make, without the warm-up) against the plain reference
at float32 HIGHEST, as the cell compares them.  For the first
``--controls`` seeds also the control -- the reference put in the
program's place in bfloat16 -- against the float32 reference; with
``--capacity-fault`` also the program with a capacity-bound expert layer
(``capacity_dropping``), which drops the rows past each held expert's
share of the mean load, as a capacity-dropping layer does.  One JSON line
per reading goes to stdout and to ``--out``.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def emit(out, rec):
    line = json.dumps(rec)
    print(line, flush=True)
    with open(out, "a") as f:
        f.write(line + "\n")


@contextlib.contextmanager
def capacity_dropping():
    """The program's expert layer with a capacity: each held expert keeps
    at most ceil(real tokens x top_k / n_experts) rows of a chunk (the
    mean load), in token order, and drops the rest.  The held experts run
    densely (a fault needs no kernel); the routing and the shared experts
    are the program's."""
    import jax
    import jax.numpy as jnp
    from repro.core import buslm
    from repro.nn import moe
    orig = buslm.routed_moe

    def routed(p, x, valid, cfg, *, impl="xla"):
        gate, idx = moe.route_sigmoid(p, x, cfg)
        T, g = x.shape[0], cfg.n_held
        local = idx - cfg.held_start
        held = (local >= 0) & (local < g) & valid[:, None]       # [T, k]
        pick = jax.nn.one_hot(jnp.where(held, local, g), g + 1,
                              dtype=jnp.int32)[..., :g]           # [T, k, g]
        rank = jnp.cumsum(pick.reshape(-1, g), 0).reshape(pick.shape) - 1
        cap = -(-valid.sum() * cfg.top_k // cfg.n_experts)
        kept = held & ((rank * pick).sum(-1) < cap)
        w = jnp.einsum("tk,tkg->tg", jnp.where(kept, gate, 0.0),
                       pick.astype(jnp.float32)).astype(x.dtype)
        h = jax.nn.silu(jnp.einsum("td,gdf->gtf", x, p["w1"])) \
            * jnp.einsum("td,gdf->gtf", x, p["w3"])
        y = jnp.einsum("gtd,tg->td", jnp.einsum("gtf,gfd->gtd", h, p["w2"]),
                       w)
        sp = p["shared"]
        rows = (pick * kept[..., None]).sum((0, 1))
        return (y + moe.swiglu(sp["w1"], sp["w3"], sp["w2"], x),
                {"routed": rows.sum(), "slots": jnp.int32(T * g),
                 "expert_rows": rows})

    buslm.routed_moe = routed
    try:
        yield
    finally:
        buslm.routed_moe = orig


def readings(cell, config, seed, control, capacity, out, name):
    from bench.drivers import train_moe as drv
    from bench.reference import moonlight as ref
    t0 = time.perf_counter()
    traffic = dict(cell["traffic"])
    s = drv.Setup(seed, config, traffic)
    got, checked, corpus, start = (s.program_readings, s.checked, s.corpus,
                                   s.start)
    s.free()
    rs = s.router_seed
    want = drv.reference_steps(seed, config, corpus, checked, start,
                               router_seed=rs)

    def gaps(x):
        return {**drv.gaps(x, want), "ref_losses": want.losses.tolist(),
                "ref_routed": want.routed}

    emit(out, {"cell": name, "seed": seed, "who": "program", **gaps(got),
               "buckets": [c.bucket for c in checked],
               "losses": got.losses.tolist(), "routed": got.routed,
               "seconds": time.perf_counter() - t0})
    if control:
        x = drv.reference_steps(seed, config, corpus, checked, start,
                                router_seed=rs, nx=ref.BF16)
        emit(out, {"cell": name, "seed": seed, "who": "control_bf16",
                   **gaps(x), "losses": x.losses.tolist(),
                   "routed": x.routed})
    if capacity:
        with capacity_dropping():
            s = drv.Setup(seed, config, traffic, corpus=corpus, hist=s.hist)
            x = s.program_readings
            s.free()
        emit(out, {"cell": name, "seed": seed, "who": "fault_capacity",
                   **gaps(x), "losses": x.losses.tolist(),
                   "routed": x.routed})
    gc.collect()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--controls", type=int, default=1)
    ap.add_argument("--capacity-fault", action="store_true")
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "limits"))
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run as harness
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = harness.find(bench["workloads"], args.workload, "workload")
    harness.tpu_devices(int(wl["chips"]))
    harness.configure_cache()
    cell = json.loads((ROOT / "bench" / "cells"
                       / f"{wl['name']}.json").read_text())
    config = json.loads((ROOT / harness.find(
        bench["configs"], wl["config"], "config")["file"]).read_text())
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    out = out / f"{wl['name']}.jsonl"
    for i in range(args.seeds):
        readings(cell, config, args.first_seed + 7919 * i,
                 i < args.controls, args.capacity_fault and i == 0, out,
                 wl["name"])


if __name__ == "__main__":
    main()
