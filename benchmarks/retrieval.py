"""Retrieval benchmark: QPS + recall@k for exact vs IVF-Flat vs IVF-PQ
over the padded-CSR device-resident indexes, the snapshot-lifecycle
control plane (swap latency, publish latency, per-query p50/p99 with an
in-flight background rebuild vs quiescent), and the scan-shape sweeps
that picked the kernel/crossover defaults in ``serving/index.py``.

Scale story (the numbers the million-vector build rests on): builds
train quantizers on a bounded sample with mini-batch k-means, so
``build_s`` stops growing with ntotal — the n=100k entries record the
measured build next to ``full_lloyd_extrapolated_s`` (full-corpus
Lloyd's measured at 8k with the target size's nlist, then extrapolated
linearly in n — n is the only axis that differs, since Lloyd's
per-iteration cost is O(n * nlist * d)).  An OPQ entry (``ivf-pq-opq``)
records the rotation's recall against the plain-PQ baseline.

Sweeps corpus sizes, measures batched query throughput and recall@10
against the exact-MIPS oracle for each index kind (IVF-PQ runs the full
two-stage pipeline: ANN recall@k' + exact re-rank — the served config).
Every build goes through ``IndexBuilder`` and queries go through
snapshots / ``RetrievalService.query``.  Throughput timing is best-of-N
on identical query streams; the lifecycle latencies are per-query
distributions read from the obs ``query_latency_ms{phase=...}``
histograms, with every executable warmed (one full rebuild + query)
before the timed windows — wall-clocking cold windows was how the old
numbers picked up compile time and reported 300ms+ p50s at n=2k.

  PYTHONPATH=src python benchmarks/retrieval.py [--sizes 2000 8000 100000]
      [--quick] [--no-sweep] [--out PATH]

1M entry: pass ``--sizes 1000000`` (ivf-pq only above --max-flat-n).
Writes BENCH_retrieval.json next to this file unless --out is given.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import threading
import time

import numpy as np

from repro import obs, serving
from repro.launch.compile_cache import configure_compile_cache
from repro.serving import index as serving_index
from repro.serving import loadgen


def make_vectors(n, d=64, rank=16, seed=0):
    rng = np.random.default_rng(seed)
    basis = rng.normal(size=(rank, d))
    x = rng.normal(size=(n, rank)) @ basis + 0.1 * rng.normal(size=(n, d))
    return x.astype(np.float32)


def recall_at_k(ids, ref_ids):
    k = ref_ids.shape[1]
    return float(np.mean([len(set(ids[b]) & set(ref_ids[b])) / k
                          for b in range(ids.shape[0])]))


def _shape_for(n):
    """(nlist, nprobe) per corpus size: the small-n configs match the
    pre-scale benchmark exactly (so build_s is comparable release to
    release); past 8k, cells grow toward 1024 and probes widen."""
    if n <= 8192:
        return max(8, min(64, n // 64)), 16
    return min(1024, n // 96), 64


def _builder_for(kind, d, n, *, opq=False, lloyd=False, shape_n=None,
                 devices=None):
    nlist, nprobe = _shape_for(shape_n or n)
    big = 1 << 30          # lloyd=True: disable sampling AND mini-batch —
    #                        the full-corpus Lloyd's baseline build
    # train_batch=4096 puts the fit_kmeans Lloyd/mini-batch dispatch at
    # 8192 rows: the small-n entries train full Lloyd (same quality as
    # the pre-scale benchmark), the 100k+ entries go mini-batch on the
    # 16384-row sample
    ivf = serving.IVFConfig(
        nlist=nlist, nprobe=nprobe,
        train_sample=big if lloyd else 16384,
        train_batch=big if lloyd else 4096)
    # PQ codebooks: k=64 per subspace saturates well below the coarse
    # quantizer's sample needs — 8192 rows (128/centroid) keeps the
    # subspace fit on the cheaper full-Lloyd dispatch at every size
    pq = serving.PQConfig(
        n_subvec=16, n_codes=64, opq_iters=4 if opq else 0,
        train_sample=big if lloyd else 8192,
        train_batch=big if lloyd else 4096)
    return serving.IndexBuilder(kind, d, ivf=ivf, pq=pq, devices=devices)


def bench_index(kind, x, q, ref_ids, *, k=10, iters=5, opq=False,
                devices=None, mesh_label=None):
    """``devices``: shard the built snapshot's CSR rows across that device
    list (the ``ShardedIndexSnapshot`` path); ``mesh_label`` tags the
    entry's kind (e.g. ``ivf-flat@data=8``) so mesh-sweep entries never
    collide with the plain ones."""
    d = x.shape[1]
    ids = np.arange(1, x.shape[0] + 1)
    builder = _builder_for(kind, d, x.shape[0], opq=opq, devices=devices)
    t0 = time.perf_counter()
    snap = builder.build(ids, x)
    build_s = time.perf_counter() - t0

    if kind == "ivf-pq":      # served config: two-stage with exact re-rank
        store = np.zeros((x.shape[0] + 1, d), np.float32)
        store[ids] = x
        svc = serving.RetrievalService(builder, store, k=k, k_prime=10 * k)
        svc.swap(snap)
        run = lambda: svc.query(q, k)
    else:
        run = lambda: snap.search(q, k)

    run()                     # warm the jitted scorers
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        _, got = run()
        times.append(time.perf_counter() - t0)
    qps = q.shape[0] / float(np.min(times))      # best-of-N: noisy box
    label = f"{kind}-opq" if opq else kind
    if mesh_label:
        label = f"{label}@{mesh_label}"
    out = {"kind": label, "build_s": round(build_s, 3), "qps": round(qps, 1),
           "recall_at_10": recall_at_k(got, ref_ids)}
    if kind != "exact":
        out["nlist"], out["nprobe"] = _shape_for(x.shape[0])
    if devices is not None:
        # acceptance invariant, recorded alongside the throughput: global
        # probing over replicated centroids makes the sharded candidate
        # set identical, so the top-k must match the unsharded build
        # (same seed, same config) id-for-id
        out["mesh_devices"] = len(devices)
        ref_snap = _builder_for(kind, d, x.shape[0], opq=opq).build(ids, x)
        _, want = ref_snap.search(q, k)
        _, got_s = snap.search(q, k)
        out["topk_matches_unsharded"] = bool(
            np.array_equal(np.asarray(got_s), np.asarray(want)))
    if kind == "ivf-pq":
        pay = getattr(snap, "payload_s", None)
        pay = snap.payload if pay is None else pay
        out["code_dtype"] = str(pay.dtype)
        out["code_bytes_per_vec"] = (pay.shape[-1] * pay.dtype.itemsize)
        if devices is None:
            out["block_n"] = min(serving_index.PQ_SCAN_BLOCK_N,
                                 snap.nprobe * snap.cap)
            out["scan_variant"] = serving_index.PQ_SCAN_VARIANT
        else:      # the sharded scan is the inline XLA gather ADC (no
            out["scan_variant"] = "sharded-gather"   # pallas partitioning)
        out["opq"] = opq
    return out


def bench_lloyd_baseline(d, *, n=8000, target_n=100000, k=10):
    """Full-corpus Lloyd's build (sampling and mini-batch disabled) at a
    size it still completes in minutes — the extrapolation anchor for
    the large-n entries' speedup claim.  Built with the TARGET size's
    nlist so the linear-in-n extrapolation is apples-to-apples: Lloyd's
    per-iteration cost is O(n * nlist * d), and n is the only axis that
    changes between anchor and target."""
    x = make_vectors(n)
    ids = np.arange(1, n + 1)
    builder = _builder_for("ivf-pq", d, n, lloyd=True, shape_n=target_n)
    t0 = time.perf_counter()
    builder.build(ids, x)
    return {"kind": "full-lloyd-anchor", "n": n,
            "nlist": _shape_for(target_n)[0],
            "build_s": round(time.perf_counter() - t0, 3)}


def bench_scan_sweep(x, q, *, k=10, iters=3):
    """LUT-kernel variant x block_n sweep + the IVF-Flat dense-vs-gather
    crossover, on one ivf-pq / ivf-flat build — the measurements behind
    PQ_SCAN_BLOCK_N / PQ_SCAN_VARIANT / DENSE_PROBE_FACTOR."""
    d, n = x.shape[1], x.shape[0]
    ids = np.arange(1, n + 1)
    snap_pq = _builder_for("ivf-pq", d, n).build(ids, x)
    snap_fl = _builder_for("ivf-flat", d, n).build(ids, x)

    def best_ms(run):
        run()                                    # warm
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            run()
            ts.append(time.perf_counter() - t0)
        return round(float(np.min(ts)) * 1e3, 2)

    entry = {"kind": "scan_sweep", "n": n, "pq_scan_ms": {},
             "flat_ms": {}}
    saved = (serving_index.PQ_SCAN_BLOCK_N, serving_index.PQ_SCAN_VARIANT,
             serving_index.DENSE_PROBE_FACTOR)
    try:
        for variant in ("onehot", "gather"):
            for bn in (512, 1024, 2048, 4096):
                serving_index.PQ_SCAN_VARIANT = variant
                serving_index.PQ_SCAN_BLOCK_N = bn
                entry["pq_scan_ms"][f"{variant}/bn={bn}"] = best_ms(
                    lambda: snap_pq.search(q, k))
        for regime, factor in (("dense", 1 << 30), ("gather", 0)):
            serving_index.DENSE_PROBE_FACTOR = factor
            entry["flat_ms"][regime] = best_ms(lambda: snap_fl.search(q, k))
    finally:
        (serving_index.PQ_SCAN_BLOCK_N, serving_index.PQ_SCAN_VARIANT,
         serving_index.DENSE_PROBE_FACTOR) = saved
    entry["chosen"] = {"block_n": serving_index.PQ_SCAN_BLOCK_N,
                       "variant": serving_index.PQ_SCAN_VARIANT,
                       "dense_probe_factor":
                           serving_index.DENSE_PROBE_FACTOR}
    return entry


def bench_lifecycle(x, q, *, k=10, swap_iters=200, query_reps=60,
                    publish_batches=50):
    """Control-plane latencies for the served (ivf-pq) configuration.

    swap_ms_p50/p99: RetrievalService.swap of a pre-built snapshot — the
      request-path cost of installing a nightly build (one reference
      assignment + delta reconciliation).
    publish_ms_*: service.publish of a 16-row batch with compaction
      disabled — the O(append) request-path cost (no IVF/PQ inline).
    query_*: per-query latency distributions from the obs
      ``query_latency_ms{phase="quiescent"|"during_rebuild"}`` histograms.
      Every executable the windows touch is warmed first (one full
      rebuild + a query), so the numbers are service time under load —
      not compile time, which is what the old cold-window wall-clocking
      reported.
    """
    d = x.shape[1]
    n = x.shape[0]
    ids = np.arange(1, n + 1)
    builder = _builder_for("ivf-pq", d, n)
    store = np.zeros((n + 1, d), np.float32)
    store[ids] = x
    svc = serving.RetrievalService(builder, store, k=k, k_prime=10 * k,
                                   compact_threshold=10 ** 9,
                                   auto_compact=False)
    snap_a = builder.build(ids, x)
    snap_b = builder.build(ids, x)
    svc.swap(snap_a)
    svc.query(q, k)                                   # warm executables

    swap_ms = []
    for i in range(swap_iters):
        t0 = time.perf_counter()
        svc.swap(snap_b if i % 2 == 0 else snap_a)
        swap_ms.append((time.perf_counter() - t0) * 1e3)

    rng = np.random.default_rng(3)
    fresh = rng.normal(size=(16, d)).astype(np.float32)
    svc.publish(np.arange(n + 1, n + 17), fresh)      # warm the append path
    publish_ms = []
    for b in range(publish_batches):
        fresh_ids = np.arange(n + 1 + 16 * b, n + 17 + 16 * b)
        t0 = time.perf_counter()
        svc.publish(fresh_ids, fresh)
        publish_ms.append((time.perf_counter() - t0) * 1e3)

    # drain the delta before the query windows: both must run over the
    # same state (main tier only) so the ONLY difference between them is
    # the background build.  Then warm EVERYTHING the windows will hit:
    # one full rebuild at the post-publish ntotal (compiles the train/
    # encode shapes the background loop reuses) and one query.
    svc.rebuild(mode="compact", block=True)
    svc.rebuild(mode="full", block=True)
    svc.query(q, k)

    def timed_queries(phase, reps):
        h = obs.histogram("query_latency_ms", phase=phase)
        for _ in range(reps):
            t0 = time.perf_counter()
            svc.query(q, k)
            h.observe((time.perf_counter() - t0) * 1e3)
        return h

    h_quiet = timed_queries("quiescent", query_reps)

    stop = threading.Event()

    def rebuild_loop():       # keep a build in flight for the whole window
        while not stop.is_set():
            svc.rebuild(mode="full", block=True)

    t = threading.Thread(target=rebuild_loop, daemon=True)
    t.start()
    h_during = timed_queries("during_rebuild", query_reps)
    stop.set()
    t.join()

    def pct(v, p):
        return round(float(np.percentile(v, p)), 3)

    return {"kind": "lifecycle", "n": n,
            "swap_ms_p50": pct(swap_ms, 50), "swap_ms_p99": pct(swap_ms, 99),
            "publish_ms_p50": pct(publish_ms, 50),
            "publish_ms_p99": pct(publish_ms, 99),
            "query_p99_ms_quiescent": round(h_quiet.percentile(99), 3),
            "query_p99_ms_during_rebuild": round(h_during.percentile(99), 3),
            "query_p50_ms_quiescent": round(h_quiet.percentile(50), 3),
            "query_p50_ms_during_rebuild": round(h_during.percentile(50), 3),
            "final_version": svc.version}


def bench_load_sweep(x, q, *, k=10, qps_points=(100.0, 200.0, 400.0),
                     duration_s=2.0, slo_ms=50.0, max_batch=32):
    """Open-loop Poisson load sweep against the raw service query path
    (no encoder): ivf-pq two-stage retrieve behind the continuous-
    batching ``RequestScheduler`` (docs/serving_scheduler.md).

    Complements the launcher's ``--open-loop`` (source="serve", full
    pipeline with user encode) with ``source="benchmark"`` entries that
    isolate index + scheduler behavior at corpus scale.  Scenarios:
    quiescent, and during_rebuild with a publish + full-rebuild churn
    loop holding builds in flight — the during-rebuild shapes (hybrid
    over-fetch width with a non-empty delta, the rebuild's train/encode
    shapes) are warmed OUTSIDE the measured window, same methodology as
    ``bench_lifecycle``."""
    d, n = x.shape[1], x.shape[0]
    ids = np.arange(1, n + 1)
    builder = _builder_for("ivf-pq", d, n)
    store = np.zeros((n + 1, d), np.float32)
    store[ids] = x
    svc = serving.RetrievalService(builder, store, k=k, k_prime=10 * k,
                                   compact_threshold=10 ** 9,
                                   auto_compact=False)
    svc.swap(builder.build(ids, x))

    def execute(payloads, pad_to):
        qb = np.zeros((pad_to, d), np.float32)
        for i, p in enumerate(payloads):
            qb[i] = p
        _, got = svc.query(qb, k)
        return [got[i] for i in range(len(payloads))]

    sched = serving.RequestScheduler(execute, max_batch=max_batch,
                                     max_wait_ms=1.0, max_queue=1024,
                                     slo_ms=slo_ms)
    sched.attach_to(svc)
    payloads = [q[i % q.shape[0]] for i in range(64)]
    rng = np.random.default_rng(5)
    fresh_ids = np.arange(n + 1, n + 17)
    extra = {"index": "ivf-pq", "n": n}
    try:
        sched.warmup(payloads[0])
        # warm cycle: one publish + bucket re-warm (delta non-empty) +
        # one full rebuild, all outside the measured windows
        svc.publish(fresh_ids, rng.normal(size=(16, d)).astype(np.float32))
        sched.warmup(payloads[0])
        svc.rebuild(mode="full", block=True)
        entries = [loadgen.sweep(
            sched, payloads, list(qps_points), duration_s=duration_s,
            slo_ms=slo_ms, seed=11, scenario="quiescent",
            source="benchmark", extra=extra)]
        stop = threading.Event()

        def churn():       # re-publish the SAME id block: warm shapes only
            while not stop.is_set():
                svc.publish(fresh_ids,
                            rng.normal(size=(16, d)).astype(np.float32))
                svc.rebuild(mode="full", block=True)

        t = threading.Thread(target=churn, daemon=True)
        t.start()
        mid = list(qps_points)[len(qps_points) // 2]
        entries.append(loadgen.sweep(
            sched, payloads, [mid], duration_s=duration_s, slo_ms=slo_ms,
            seed=23, scenario="during_rebuild", source="benchmark",
            extra=extra))
        stop.set()
        t.join(timeout=120.0)
    finally:
        sched.stop(drain=True)
    return entries


def main(argv=None):
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=int, nargs="+",
                    default=[2000, 8000, 100000])
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--iters", type=int, default=7)   # best-of-7: the box
    #                                                   noise flips thin
    #                                                   margins at 5
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: fewer timing reps, no lifecycle/sweep/"
                         "OPQ/Lloyd-anchor sections")
    ap.add_argument("--no-sweep", action="store_true",
                    help="skip the scan-shape sweep section")
    ap.add_argument("--max-flat-n", type=int, default=200000,
                    help="above this, only ivf-pq is benched (exact stays "
                         "the recall oracle)")
    ap.add_argument("--mesh", nargs="+", default=[], metavar="data=N",
                    help="also bench device-sharded IVF snapshots on each "
                         "N-way data mesh (data=1 = the unsharded "
                         "baseline); on CPU set XLA_FLAGS=--xla_force_"
                         "host_platform_device_count=N first — entries "
                         "document scaling shape + the sharded-vs-"
                         "unsharded top-k parity, not absolute speed")
    ap.add_argument("--mesh-merge", action="store_true",
                    help="merge the --mesh entries into the existing --out "
                         "JSON instead of re-running every section")
    ap.add_argument("--load-sweep", action="store_true",
                    help="open-loop Poisson load sweep through the request "
                         "scheduler against the raw ivf-pq query path "
                         "(quiescent + during_rebuild scenarios), merged "
                         "into --out by (kind, source, scenario) without "
                         "re-running the other sections")
    ap.add_argument("--load-n", type=int, default=8000,
                    help="corpus size for --load-sweep")
    ap.add_argument("--load-qps", type=float, nargs="+",
                    default=[100.0, 200.0, 400.0], metavar="QPS",
                    help="offered-QPS points for --load-sweep")
    ap.add_argument("--load-duration", type=float, default=2.0,
                    help="seconds of offered load per --load-sweep point")
    ap.add_argument("--load-slo-ms", type=float, default=50.0,
                    help="per-request SLO deadline for --load-sweep")
    ap.add_argument("--out", default=None,
                    help="output path (default: BENCH_retrieval.json next "
                         "to this file)")
    args = ap.parse_args(argv)
    if args.quick:
        args.iters = min(args.iters, 3)

    mesh_plan = []                      # (spec, device list | None)
    if args.mesh:
        from repro.launch.mesh import parse_mesh_arg
        for spec in dict.fromkeys(args.mesh):
            m = parse_mesh_arg(spec)
            mesh_plan.append(
                (spec, None if m is None else list(m.devices.flat)))

    def mesh_entries(n, x, q, ref_ids):
        out = []
        for spec, devs in mesh_plan:
            for kind in ("ivf-flat", "ivf-pq"):
                r = {"n": n, **bench_index(kind, x, q, ref_ids, k=args.k,
                                           iters=args.iters, devices=devs,
                                           mesh_label=spec)}
                r.setdefault("mesh_devices", 1)      # the data=1 baseline
                out.append(r)
                parity = r.get("topk_matches_unsharded")
                print(f"n={n:>7} {r['kind']:>16}: qps={r['qps']:>9} "
                      f"recall@10={r['recall_at_10']:.3f}"
                      + ("" if parity is None
                         else f" topk==unsharded: {parity}"))
        return out

    if args.load_sweep:
        # merge-style section (like --mesh-merge): record the scheduler
        # load-sweep entries without re-running the expensive sections
        out_p = pathlib.Path(args.out) if args.out else (
            pathlib.Path(__file__).parent / "BENCH_retrieval.json")
        obs.reset()
        x = make_vectors(args.load_n)
        q = make_vectors(256, seed=7)
        entries = bench_load_sweep(
            x, q, k=args.k, qps_points=args.load_qps,
            duration_s=args.load_duration, slo_ms=args.load_slo_ms,
            max_batch=args.batch)
        for e in entries:
            for pt in e["points"]:
                print(f"[{e['scenario']:>14}] offered {pt['offered_qps']:>6} "
                      f"qps: goodput {pt['goodput_qps']:>6} qps, e2e p50/p99 "
                      f"{pt['e2e_ms_p50']}/{pt['e2e_ms_p99']}ms, rejected "
                      f"{pt['rejected']}, late {pt['late_dropped']}")
        loadgen.record_sweep(entries, out_p)
        print(f"merged {len(entries)} load-sweep entries into {out_p}")
        return entries

    if args.mesh_merge:
        # record the mesh scaling entries into an EXISTING result file
        # without re-running the expensive lifecycle/sweep/anchor sections
        if not mesh_plan:
            raise SystemExit("--mesh-merge requires --mesh")
        out_p = pathlib.Path(args.out) if args.out else (
            pathlib.Path(__file__).parent / "BENCH_retrieval.json")
        if not out_p.exists():
            raise SystemExit(f"--mesh-merge needs an existing {out_p}")
        obs.reset()
        fresh = []
        for n in args.sizes:
            x = make_vectors(n)
            q = make_vectors(args.batch, seed=7)
            oracle = serving.IndexBuilder("exact", x.shape[1]).build(
                np.arange(1, n + 1), x)
            _, ref_ids = oracle.search(q, args.k)
            fresh.extend(mesh_entries(n, x, q, ref_ids))
        doc = json.loads(out_p.read_text())
        doc["results"] = [e for e in doc["results"]
                          if "@data=" not in str(e.get("kind", ""))] + fresh
        doc["config"]["mesh"] = {"specs": [s for s, _ in mesh_plan]}
        out_p.write_text(json.dumps(doc, indent=2))
        print(f"merged {len(fresh)} mesh entries into {out_p}")
        return fresh

    obs.reset()
    results = []
    lloyd_anchor = None
    if not args.quick and any(n >= 50000 for n in args.sizes):
        target = max(n for n in args.sizes if n >= 50000)
        lloyd_anchor = bench_lloyd_baseline(64, target_n=target, k=args.k)
        results.append(lloyd_anchor)
        print(f"full-Lloyd anchor: n={lloyd_anchor['n']} "
              f"nlist={lloyd_anchor['nlist']} "
              f"build={lloyd_anchor['build_s']}s")

    for n in args.sizes:
        x = make_vectors(n)
        q = make_vectors(args.batch, seed=7)
        oracle = serving.IndexBuilder("exact", x.shape[1]).build(
            np.arange(1, n + 1), x)
        _, ref_ids = oracle.search(q, args.k)
        kinds = ["exact", "ivf-flat", "ivf-pq"]
        if n > args.max_flat_n:
            kinds = ["ivf-pq"]
        for kind in kinds:
            r = {"n": n, **bench_index(kind, x, q, ref_ids, k=args.k,
                                       iters=args.iters)}
            if kind == "ivf-pq" and lloyd_anchor and n >= 50000:
                # linear in n at matched nlist (Lloyd's coarse cost is
                # O(n * nlist * d)); the nlist ratio is <= 1 for the
                # non-target sizes, keeping the estimate conservative
                ext = (lloyd_anchor["build_s"] * n / lloyd_anchor["n"]
                       * _shape_for(n)[0] / lloyd_anchor["nlist"])
                r["full_lloyd_extrapolated_s"] = round(ext, 1)
                r["build_speedup_vs_full_lloyd"] = round(
                    ext / r["build_s"], 1)
            results.append(r)
            print(f"n={n:>7} {r['kind']:>11}: qps={r['qps']:>9} "
                  f"recall@10={r['recall_at_10']:.3f} "
                  f"build={r['build_s']}s")
        if not args.quick:
            r = {"n": n, **bench_index("ivf-pq", x, q, ref_ids, k=args.k,
                                       iters=args.iters, opq=True)}
            results.append(r)
            print(f"n={n:>7} {r['kind']:>11}: qps={r['qps']:>9} "
                  f"recall@10={r['recall_at_10']:.3f} "
                  f"build={r['build_s']}s")
        results.extend(mesh_entries(n, x, q, ref_ids))
        if not args.quick and not args.no_sweep and n == 8000:
            r = bench_scan_sweep(x, q, k=args.k)
            results.append(r)
            print(f"n={n:>7}  scan_sweep: pq={r['pq_scan_ms']} "
                  f"flat={r['flat_ms']}")
        if not args.quick:
            r = bench_lifecycle(x, q, k=args.k)
            results.append(r)
            print(f"n={n:>7}   lifecycle: swap p99={r['swap_ms_p99']}ms "
                  f"publish p99={r['publish_ms_p99']}ms query p50/p99 "
                  f"quiescent={r['query_p50_ms_quiescent']}/"
                  f"{r['query_p99_ms_quiescent']}ms, during rebuild="
                  f"{r['query_p50_ms_during_rebuild']}/"
                  f"{r['query_p99_ms_during_rebuild']}ms")

    out = pathlib.Path(args.out) if args.out else (
        pathlib.Path(__file__).parent / "BENCH_retrieval.json")
    out.write_text(json.dumps(
        {"batch": args.batch, "k": args.k, "iters": args.iters,
         "config": {"pq_scan_block_n": serving_index.PQ_SCAN_BLOCK_N,
                    "pq_scan_variant": serving_index.PQ_SCAN_VARIANT,
                    "dense_probe_factor": serving_index.DENSE_PROBE_FACTOR,
                    "train_sample_coarse": 16384, "train_sample_pq": 8192,
                    **({"mesh": {"specs": [s for s, _ in mesh_plan]}}
                       if mesh_plan else {})},
         "results": results}, indent=2))
    print(f"wrote {out}")
    return results


if __name__ == "__main__":
    main()
