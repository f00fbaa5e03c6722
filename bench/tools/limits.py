"""Readings that the limits of ``correct`` are set from, at a cell's own
size, on the chip.

    python bench/tools/limits.py --workload <cell> --seeds 12 --controls 3 \
        [--first-seed N] [--out chiprun_out/limits]

For each seed: the program's readings of the timed path (the same
set-up the cell's runs make) against the plain reference.  For the first
``--controls`` seeds also the control -- the reference put in the
program's place in bfloat16 -- and, for training, the fault of a loss
averaged over half of the users, each against the float32 reference.
One JSON line per reading goes to stdout and to ``--out``.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]


def emit(out, rec):
    line = json.dumps(rec)
    print(line, flush=True)
    with open(out, "a") as f:
        f.write(line + "\n")


def train_readings(cell, config, seed, control, out, name):
    from bench.drivers import train
    from bench.reference import speedyfeed as ref
    t0 = time.perf_counter()
    s = train.Setup(seed, config, cell["traffic"])
    got, checked, corpus, start = (s.program_readings, s.checked, s.corpus,
                                   s.start)
    s.free()
    refs = {k: train.reference_steps(seed, config, corpus, checked, start,
                                     nx=nx)
            for k, nx in (("highest", ref.F32), ("stated", ref.STATED),
                          ("kernel", ref.KERNEL))}

    def readings(x):
        rec = {}
        for k, want in refs.items():
            rec.update({f"{n}_{k}": v for n, v in train.gaps(x, want).items()})
            rec[f"ref_losses_{k}"] = want.losses.tolist()
        return rec

    emit(out, {"cell": name, "seed": seed, "who": "program", **readings(got),
               "buckets": [c.bucket for c in checked],
               "losses": got.losses.tolist(),
               "seconds": time.perf_counter() - t0})
    if control:
        for who, kw in (("control_bf16", {"nx": ref.BF16}),
                        ("fault_half_batch", {"half_batch": True})):
            x = train.reference_steps(seed, config, corpus, checked, start,
                                      **kw)
            emit(out, {"cell": name, "seed": seed, "who": who,
                       **readings(x), "losses": x.losses.tolist()})
    gc.collect()


def encode_readings(cell, config, seed, control, out, name):
    import jax
    from bench.drivers import encode
    from bench.drivers.common import program_config
    from bench.reference import speedyfeed as ref
    from repro.launch.serve import Recommender
    t = cell["traffic"]
    corpus = encode.make_corpus(seed, config, t)
    rec = Recommender(program_config(config),
                      ref.init_params(seed, config["plm"]), corpus)
    emb = rec._encode_corpus(chunk=int(t["chunk"]))
    rows = encode.sample_rows(seed, t["n_articles"], int(t["check_rows"]))
    got = emb[rows]
    del rec, emb
    gc.collect()
    refs = {name: encode.reference_embeddings(seed, config, corpus, rows,
                                              nx=nx)
            for name, nx in (("highest", ref.F32), ("stated", ref.STATED),
                             ("kernel", ref.KERNEL))}

    def readings(x):
        rec = {"bf16_share": encode.bf16_share(x)}
        for k, v in refs.items():
            per = encode.article_gaps(x, v)
            rec[f"emb_gap_{k}"] = float(per.max())
            rec[f"per_article_{k}"] = [float(f"{g:.4g}") for g in per]
        return rec

    emit(out, {"cell": name, "seed": seed, "who": "program",
               **readings(got)})
    if control:
        for who, nx in (("control_bf16", ref.BF16),
                        ("control_bf16_f32_out", ref.BF16_F32_OUT)):
            x = encode.reference_embeddings(seed, config, corpus, rows, nx=nx)
            emit(out, {"cell": name, "seed": seed, "who": who,
                       **readings(x)})
        # a fault: each article answered with its neighbour's embedding
        emit(out, {"cell": name, "seed": seed, "who": "fault_answer_altered",
                   **readings(np.roll(got, 1, axis=0))})
    jax.clear_caches()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
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
    fn = {"train": train_readings, "encode": encode_readings}[cell["driver"]]
    for i in range(args.seeds):
        fn(cell, config, args.first_seed + 7919 * i, i < args.controls, out,
           wl["name"])


if __name__ == "__main__":
    main()
