"""Serving launcher: two-stage batched news-recommendation service.

Architecture (paper §5.1.4 production setup, on the repro.serving
snapshot lifecycle):
  1. offline: encode the news corpus with the (Bus)LM news encoder, then
     bootstrap the lifecycle — one full ``IndexBuilder`` build over the
     corpus (exact-flat, IVF-Flat, or IVF-PQ), installed by atomic swap;
     full-precision embeddings stay in the service's
     ``EmbeddingStore`` (host + device mirror) for user encoding and
     re-rank,
  2. online: every request goes through the continuous-batching
     ``serving.RequestScheduler`` (bounded admission queue, pow2
     shape-bucketed batches over the warm executables, ``max_wait_ms``
     timeout flush, optional SLO deadlines — docs/serving_scheduler.md):
     encode users (history -> user embedding), then two-stage retrieve:
     ANN recall of k' candidates (one frozen snapshot + fresh-news delta
     view) followed by exact re-rank to top-k.  Fresh news enters via
     ``service.publish`` (pure delta append) and is absorbed by
     background rebuilds that swap in mid-loop without blocking a query
     (--rebuild-mid-loop exercises exactly that).

Two drivers feed the scheduler:
  closed-loop   ``micro_batch_loop`` submits a fixed request list and
                drains it — the CI smokes' deterministic path,
  open-loop     ``--open-loop`` fires seeded Poisson arrivals at ≥3
                offered-QPS points (``--sweep``/``--qps``), measures
                p50/p99 queued/e2e latency, goodput under ``--slo-ms``,
                reject rate, and batch occupancy, and merges the sweep
                into BENCH_retrieval.json (``--bench-out``).

All request-loop numbers flow through the process-wide ``repro.obs``
registry (``query_latency_ms{phase=queued|execute|e2e}``,
``serve_batch_size``, ``sched_*``, ...); ``ServeStats`` is a *view*
rendered from that registry after the loop, and ``--metrics-out``
snapshots the whole registry (train + publish + serve, one process =
one registry) to JSONL.

Run: python -m repro.launch.serve --requests 64 --batch 16 \
         [--config small|prod_1chip] \
         [--index ivf-pq|ivf-flat|exact] [--nprobe 8] [--k-prime 64] \
         [--rebuild-mid-loop] [--train-steps 6] [--metrics-out m.jsonl]
     python -m repro.launch.serve --open-loop --sweep 50 100 200 \
         --slo-ms 250 [--duration 2.0] [--bench-out BENCH.json]
"""
from __future__ import annotations

import argparse
import dataclasses
import pathlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import core, obs, serving
from repro.resilience import FaultPlan, faults


# recall@k the served path must reach against the exact-MIPS oracle
RECALL_THRESHOLD = 0.7


@dataclasses.dataclass
class ServeStats:
    n_requests: int
    n_batches: int
    p50_ms: float
    p99_ms: float
    recall_at_k: float        # true recall@k vs the exact-MIPS oracle
    recall_ok: bool           # recall_at_k >= the smoke threshold
    index_kind: str = "exact"
    ntotal: int = 0
    index_version: int = 0
    n_swaps: int = 0
    # --open-loop only: the BENCH-ready load-sweep entries (per-QPS-point
    # goodput / p50 / p99 / reject-rate records)
    load_sweep: list | None = None

    @classmethod
    def from_registry(cls, *, recall_at_k: float, recall_ok: bool,
                      index_kind: str, ntotal: int) -> "ServeStats":
        """Render the stats view from the obs registry — the registry is
        the single source of truth; this object is just the summary the
        smoke tests and the CLI print consume."""
        e2e = obs.histogram("query_latency_ms", phase="e2e")
        return cls(
            n_requests=int(obs.counter("serve_requests_total").value),
            n_batches=int(obs.counter("serve_batches_total").value),
            p50_ms=e2e.percentile(50), p99_ms=e2e.percentile(99),
            recall_at_k=recall_at_k, recall_ok=recall_ok,
            index_kind=index_kind, ntotal=ntotal,
            index_version=int(obs.gauge("index_snapshot_version").value),
            n_swaps=int(obs.counter("index_swap_total").value))


class Recommender:
    """Two-stage (ANN retrieve -> exact re-rank) news recommender."""

    def __init__(self, cfg: core.SpeedyFeedConfig, params, store, *, k=10,
                 index_kind: str = "ivf-pq", nprobe: int = 8,
                 k_prime: int | None = None, compact_threshold: int = 512,
                 probe_metric: str = "ip", mesh=None, service_kw=None):
        # probe_metric: the launcher serves raw MIPS over unnormalized
        # encoder embeddings — direction-concentrated, norm-heterogeneous —
        # where ranking cells by raw inner product recalls the large-norm
        # winners the spherical ("l2") ranking misses (measured: 0.69 vs
        # 0.14 coverage at nprobe=8 on the smoke corpus).  "l2" stays the
        # library default for normalized, topically-clustered corpora.
        self.cfg, self.params, self.store, self.k = cfg, params, store, k
        self.index_kind = index_kind
        self.nprobe = nprobe
        self.probe_metric = probe_metric
        # device-sharded index: CSR rows partition across the mesh's
        # devices (docs/sharding.md); None = single-device snapshots
        self.mesh = mesh
        self.k_prime = k_prime or max(4 * k, 32)
        self.compact_threshold = compact_threshold
        # extra RetrievalService knobs (resilience: build_retries,
        # degraded_after_failures, delta_hard_cap, ... — docs/resilience.md)
        self.service_kw = dict(service_kw or {})
        # chunked store growth: user encoding is jitted against the
        # device mirror's [N, d] shape, so exact growth recompiled it on
        # the request path for every small publish (open-loop churn
        # measured ~1.4 s/publish); one chunk = one recompile per 1024
        # fresh rows instead
        self.service_kw.setdefault("store_grow_chunk", 1024)
        self.service: serving.RetrievalService | None = None
        # params are arguments, not closed-over constants: a closure bakes
        # them into the executable (858 MB at PROD width, past the
        # persistent compilation cache's entry limit)
        def encode(p, t, f):
            with jax.named_scope("plm_encode"):
                return core.buslm_encode(p, cfg.plm, t, f)

        self._encode = jax.jit(encode)

        def user_encode(p, emb, hist, hist_mask):
            return core.attentive_user(p, emb[hist], hist_mask)

        self._user = jax.jit(user_encode)

    def _encode_corpus(self, *, chunk: int = 256):
        """Offline bulk encode of the whole corpus (cells: encode_bulk).

        Spans ``encode_corpus`` (the call), ``encode_chunk`` (each chunk)
        and ``encode_fetch`` (the wait for a chunk's embeddings and their
        copy to the host); per chunk, ``encode_window`` counts its corpus
        rows, their real tokens and the token slots the encoder runs (the
        padded tail's included)."""
        toks = self.store.tokens
        n = toks.shape[0]
        slots = chunk * int(np.prod(toks.shape[1:]))
        plm = self.params["plm"]
        outs = []
        with obs.span("encode_corpus"):
            for i in range(0, n, chunk):
                with obs.span("encode_chunk"):
                    tc = toks[i:i + chunk]
                    obs.counts("encode_window", rows=tc.shape[0],
                               tokens=int(np.count_nonzero(tc)),
                               token_slots=slots)
                    t = jnp.asarray(tc)
                    f = jnp.asarray(self.store.freq[i:i + chunk])
                    pad = chunk - t.shape[0]
                    if pad:             # pad the tail to the warm shape
                        t = jnp.pad(t, ((0, pad), (0, 0), (0, 0)))
                        f = jnp.pad(f, ((0, pad), (0, 0), (0, 0)))
                    out = self._encode(plm, t, f)
                    with obs.span("encode_fetch"):
                        out = np.asarray(out)
                    outs.append(out[:-pad] if pad else out)
            emb = np.concatenate(outs)
        emb[0] = 0.0              # pad news scores nothing
        return emb

    def build_index(self, *, chunk: int = 256, seed: int = 0):
        """Encode the corpus, then bootstrap the snapshot lifecycle: one
        full build over the corpus, installed by swap."""
        emb = self._encode_corpus(chunk=chunk)
        n = emb.shape[0]
        nlist = max(4, min(64, n // 32))
        devices = None
        if self.mesh is not None and self.index_kind != "exact":
            devices = list(self.mesh.devices.flat)
        builder = serving.IndexBuilder(
            self.index_kind, emb.shape[1],
            ivf=serving.IVFConfig(nlist=nlist,
                                  nprobe=min(self.nprobe, nlist),
                                  metric=self.probe_metric),
            pq=serving.pq_config_for(emb.shape[1]), seed=seed,
            devices=devices)
        self.service = serving.RetrievalService(
            builder, emb, k=self.k, k_prime=min(self.k_prime, n - 1),
            compact_threshold=self.compact_threshold, **self.service_kw)
        self.service.store.attach_device_mirror()
        # the corpus (row 0 is the pad news, never a candidate) goes
        # straight to the builder: the delta tier is for fresh news, and
        # its backpressure cap refuses a corpus larger than the cap
        self.service.swap(builder.build(np.arange(1, n), emb[1:]))
        return self.service

    def publish(self, ids, emb):
        """Fresh news straight into the serving path: store grow-and-
        scatter (host + device mirror) + delta append — the service owns
        all of it; nothing here touches an index."""
        self.service.publish(ids, emb)

    def encode_users(self, hist_batch: np.ndarray, mask: np.ndarray):
        """History -> user embedding, off the device-mirrored store."""
        return np.asarray(self._user(self.params["user"],
                                     self.service.store.device,
                                     jnp.asarray(hist_batch),
                                     jnp.asarray(mask)))

    def recommend(self, hist_batch: np.ndarray, mask: np.ndarray):
        user = self.encode_users(hist_batch, mask)
        return self.service.query(user, self.k)


def make_recommend_execute(rec: Recommender):
    """The scheduler's model-side callable: pad ``len(payloads)``
    histories up to the static batch dim ``pad_to`` (one of the
    scheduler's pow2 shape buckets — NOT ``max_batch``, so a partial
    batch lands in the smallest warm executable instead of encoding
    ``max_batch - n`` junk rows at the full shape) and run the two-stage
    pipeline.  Returns one top-k id row per payload, in order."""
    L = rec.cfg.hist_len

    def execute(payloads, pad_to):
        hist = np.zeros((pad_to, L), np.int32)
        mask = np.zeros((pad_to, L), bool)
        for i, h in enumerate(payloads):
            h = np.asarray(h)[-L:]
            hist[i, :len(h)] = h
            mask[i, :len(h)] = True
        _, ids = rec.recommend(hist, mask)
        return [ids[i] for i in range(len(payloads))]

    return execute


def micro_batch_loop(rec: Recommender, requests, *, max_batch: int,
                     max_wait_ms: float = 2.0, on_batch=None):
    """Closed-loop driver over the continuous-batching scheduler;
    returns (results, n_batches).

    Thin by design: submit the fixed request list, wait for every
    handle, drain.  Batching, shape bucketing, timeout flush, and all
    request-loop telemetry (``query_latency_ms{phase=queued|execute|
    e2e}``, ``serve_batch_size``, request/batch counters) live in
    ``serving.RequestScheduler`` — this path and the open-loop Poisson
    harness measure the same machinery.  ``on_batch(i)`` fires on the
    scheduler worker after batch i completes (the rebuild-mid-loop
    smoke publishes fresh news + kicks a background rebuild from it).
    """
    sched = serving.RequestScheduler(
        make_recommend_execute(rec), max_batch=max_batch,
        max_wait_ms=max_wait_ms, max_queue=max(len(requests), 1),
        on_batch=on_batch)
    try:
        handles = [sched.submit(h) for h in requests]
        results = [h.result(timeout=300.0) for h in handles]
    finally:
        sched.stop(drain=True)
    return results, sched.n_batches


def open_loop_harness(args, rec: Recommender, requests, *, chaos_n: int = 0):
    """Open-loop Poisson load sweep through the continuous-batching
    scheduler (docs/serving_scheduler.md).

    Sweeps the offered-QPS points (``--sweep`` / ``--qps``; default 3
    points) against one warmed scheduler under ``--slo-ms`` deadlines,
    recording p50/p99 queued/e2e latency, goodput-under-SLO, reject
    rate, and late-drops per point.  With --rebuild-mid-loop (or chaos),
    one extra point runs at the middle offered rate while a publisher +
    full-rebuild churn loop holds a build in flight — PR 5's
    rebuild-mid-loop p99 as one scenario of this harness.  The churn
    re-publishes fresh embeddings for the SAME id block (re-encoded
    news, the paper's model-drift loop), and one publish→rebuild cycle
    runs before the measured window with the bucket warmup repeated
    while the delta tier is non-empty — the hybrid over-fetch width
    (k' + |delta|, pow2) and the rebuild's train/encode shapes are
    static jit keys, so without the warm cycle the window would measure
    a compile storm, not rebuild contention.  ``chaos_n > 0`` arms the
    fault plan AFTER the warm cycle, so the injected rebuild failures
    land inside the measured window.  Returns (entries, chaos_plan)."""
    svc = rec.service
    qps_points = [float(q) for q in (
        args.sweep if args.sweep
        else ([args.qps] if args.qps else [50.0, 100.0, 200.0]))]
    sched = serving.RequestScheduler(
        make_recommend_execute(rec), max_batch=args.batch,
        max_wait_ms=args.max_wait_ms, max_queue=args.queue_depth,
        slo_ms=args.slo_ms)
    sched.attach_to(svc)          # saturated admission queue => degraded
    n_warm = sched.warmup(requests[0])
    print(f"scheduler warm: {n_warm} shape buckets {sched.buckets}, "
          f"slo={args.slo_ms}ms, queue cap {args.queue_depth}")
    extra = {"index": args.index, "ntotal": svc.ntotal}
    chaos_plan = None
    rebuild_scenario = args.rebuild_mid_loop or chaos_n > 0
    rng = np.random.default_rng(1)
    n0 = svc.store.host.shape[0]
    fresh_ids = np.arange(n0, n0 + 32)

    def fresh_rows():
        return (svc.store.host[1:33]
                + 0.01 * rng.normal(size=(32, svc.store.dim))
                ).astype(np.float32)

    try:
        if rebuild_scenario:
            # warm cycle (outside every measured window)
            rec.publish(fresh_ids, fresh_rows())     # O(append)
            sched.warmup(requests[0])                # delta non-empty path
            svc.rebuild(mode="full", block=True)
            if chaos_n > 0:
                chaos_plan = faults.arm(FaultPlan().fail(
                    "index.rebuild", calls=range(1, chaos_n + 1)))
        entries = [serving.loadgen.sweep(
            sched, requests, qps_points, duration_s=args.duration,
            slo_ms=args.slo_ms, seed=11, scenario="quiescent",
            source="serve", extra=extra)]
        if rebuild_scenario:
            stop_ev = threading.Event()

            def churn():
                while not stop_ev.is_set():
                    try:
                        rec.publish(fresh_ids, fresh_rows())
                        svc.rebuild(mode="full", block=True)
                    except Exception:
                        # retries exhausted under chaos: the view stays
                        # on the last good snapshot; keep churning
                        pass

            churn_t = threading.Thread(target=churn, name="rebuild-churn",
                                       daemon=True)
            churn_t.start()
            mid = qps_points[len(qps_points) // 2]
            entries.append(serving.loadgen.sweep(
                sched, requests, [mid], duration_s=args.duration,
                slo_ms=args.slo_ms, seed=23, scenario="during_rebuild",
                source="serve", extra=extra))
            stop_ev.set()
            churn_t.join(timeout=120.0)
    finally:
        sched.stop(drain=True)
    for e in entries:
        for pt in e["points"]:
            print(f"[{e['scenario']:>14}] offered {pt['offered_qps']:>6} "
                  f"qps: goodput {pt['goodput_qps']:>6} qps, e2e p50/p99 "
                  f"{pt['e2e_ms_p50']}/{pt['e2e_ms_p99']}ms, queued p99 "
                  f"{pt['queued_ms_p99']}ms, rejected {pt['rejected']} "
                  f"({100 * pt['reject_rate']:.1f}%), "
                  f"late {pt['late_dropped']}")
    if args.bench_out:
        p = serving.loadgen.record_sweep(entries, args.bench_out)
        print(f"merged {len(entries)} load-sweep entries into {p}")
    return entries, chaos_plan


def _probe_users(rec: Recommender, histories, probe: int):
    """Encode the probe-subset histories into user embeddings."""
    probe = min(probe, len(histories))
    L = rec.cfg.hist_len
    hist = np.zeros((probe, L), np.int32)
    mask = np.zeros((probe, L), bool)
    for i, h in enumerate(histories[:probe]):
        h = h[-L:]
        hist[i, :len(h)] = h
        mask[i, :len(h)] = True
    return rec.encode_users(hist, mask)


def measure_recall(rec: Recommender, histories, *, k: int, probe: int = 16):
    """True recall@k of the served path vs an exact-MIPS oracle over the
    full-precision store, on a probe subset of requests (replaces the old
    fill-rate check that never measured recall)."""
    probe = min(probe, len(histories))
    user = _probe_users(rec, histories, probe)
    _, got = rec.service.query(user, k)
    store = rec.service.store.host
    scores = user @ store.T
    live = np.any(store != 0.0, axis=1)      # unpublished gap rows excluded
    live[0] = False                          # pad news is never a candidate
    scores[:, ~live] = -np.inf
    ref_ids = np.argsort(-scores, axis=1)[:, :k]
    return float(np.mean([len(set(got[b]) & set(ref_ids[b])) / k
                          for b in range(probe)]))


def main(argv=None):
    from repro.launch.compile_cache import configure_compile_cache
    from repro.launch.train import (CONFIG_NAMES, make_loader,
                                    speedyfeed_config, train_speedyfeed)
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="small", choices=CONFIG_NAMES,
                    help="speedyfeed config: small (CPU), prod_1chip (PROD "
                         "widths at one chip's batch share), prod")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--index", default="ivf-pq",
                    choices=["exact", "ivf-flat", "ivf-pq"])
    ap.add_argument("--nprobe", type=int, default=16)
    ap.add_argument("--k-prime", type=int, default=64)
    ap.add_argument("--probe-metric", default="ip", choices=["ip", "l2"],
                    help="cell-probe ranking; ip recalls large-norm MIPS "
                         "winners on the launcher's unnormalized encoder "
                         "embeddings (see Recommender)")
    ap.add_argument("--autotune", action="store_true",
                    help="grid-tune (nprobe, k') against the exact-MIPS "
                         "recall oracle after the bootstrap build; the "
                         "winner is installed by atomic swap and future "
                         "rebuilds inherit it")
    ap.add_argument("--rebuild-mid-loop", action="store_true",
                    help="publish fresh news and run a background full "
                         "rebuild + atomic swap in the middle of the "
                         "request loop")
    ap.add_argument("--chaos-rebuild-failures", type=int, default=0,
                    metavar="N",
                    help="fault injection: make the first N mid-loop "
                         "rebuild attempts fail (the bootstrap build is "
                         "untouched); the service must retry through them, "
                         "go degraded, and recover — implies "
                         "--rebuild-mid-loop (docs/resilience.md)")
    ap.add_argument("--open-loop", action="store_true",
                    help="open-loop Poisson load harness: sweep offered "
                         "QPS through the continuous-batching scheduler "
                         "instead of draining a fixed request list; "
                         "records p50/p99 latency, goodput under --slo-ms, "
                         "reject rate, and batch occupancy per point "
                         "(docs/serving_scheduler.md)")
    ap.add_argument("--qps", type=float, default=None,
                    help="single offered-QPS point for --open-loop "
                         "(default: the 3-point --sweep)")
    ap.add_argument("--sweep", type=float, nargs="+", default=None,
                    metavar="QPS",
                    help="offered-QPS points for --open-loop (default "
                         "50 100 200)")
    ap.add_argument("--slo-ms", type=float, default=250.0,
                    help="per-request SLO deadline for --open-loop: past "
                         "it a queued request is late-dropped, a "
                         "completed one counts as a violation; goodput "
                         "counts only completions within it")
    ap.add_argument("--duration", type=float, default=2.0,
                    help="seconds of offered load per sweep point")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="scheduler flush timeout: a partial batch waits "
                         "at most this long for followers")
    ap.add_argument("--queue-depth", type=int, default=256,
                    help="bounded admission queue; submissions beyond it "
                         "are rejected with BackpressureError")
    ap.add_argument("--bench-out",
                    default=str(pathlib.Path(__file__).resolve().parents[3]
                                / "benchmarks" / "BENCH_retrieval.json"),
                    help="merge --open-loop sweep entries into this BENCH "
                         "json (pass an empty string to skip recording)")
    ap.add_argument("--recall-threshold", type=float,
                    default=RECALL_THRESHOLD)
    ap.add_argument("--probe", type=int, default=16,
                    help="probe-subset size for the recall oracle")
    ap.add_argument("--train-steps", type=int, default=0,
                    help="run N training steps first and serve the trained "
                         "params — train, publish, and serve metrics then "
                         "land in ONE registry snapshot")
    ap.add_argument("--metrics-out", default=None,
                    help="append a JSONL registry snapshot here at the end "
                         "(and periodically if --metrics-every > 0)")
    ap.add_argument("--metrics-every", type=float, default=0.0,
                    help="periodic in-loop snapshot cadence, seconds")
    ap.add_argument("--mesh", default=None, metavar="data=N",
                    help="shard the IVF index's CSR rows across an N-way "
                         "data mesh (data=1 / omitted = single-device "
                         "snapshots); on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    args = ap.parse_args(argv)
    from repro.launch.mesh import parse_mesh_arg
    mesh = parse_mesh_arg(args.mesh)

    # one launcher run = one registry's worth of numbers (tests invoke
    # main() in-process; without the reset a second run would report the
    # first run's counters too)
    obs.reset()
    if args.metrics_out:
        obs.configure_reporter(path=args.metrics_out,
                               every_s=args.metrics_every or 10.0)

    cfg = speedyfeed_config(args.config)
    corpus, log, store, lcfg = make_loader(cfg)
    if args.train_steps > 0:
        res = train_speedyfeed(steps=args.train_steps, cfg=cfg,
                               log_every=max(args.train_steps // 2, 1))
        params = res.state.params
        print(f"trained {res.steps_done} steps before serving "
              f"(loss {res.losses[-1]:.3f})" if res.losses else
              f"trained {res.steps_done} steps before serving")
    else:
        params, _ = core.speedyfeed_state(cfg)
    chaos_n = args.chaos_rebuild_failures
    rebuild_mid_loop = args.rebuild_mid_loop or chaos_n > 0
    service_kw = None
    if chaos_n > 0:
        # enough retries to outlast the injected failures, tight backoff,
        # and a 1-failure degraded threshold so the degraded->healthy
        # transition is guaranteed to appear in the metrics
        service_kw = dict(build_retries=max(2, chaos_n),
                          build_backoff_s=0.01,
                          degraded_after_failures=1)
    rec = Recommender(cfg, params, store, k=args.k, index_kind=args.index,
                      nprobe=args.nprobe, k_prime=args.k_prime,
                      probe_metric=args.probe_metric, mesh=mesh,
                      service_kw=service_kw)
    t0 = time.time()
    rec.build_index()
    svc = rec.service
    chaos_plan = None
    if chaos_n > 0 and not args.open_loop:
        # armed only now: the bootstrap build above ran clean; the first
        # N mid-loop rebuild attempts die instead and must be retried.
        # (--open-loop arms inside the harness instead, after its warm
        # publish→rebuild cycle, so the injected failures land in the
        # measured window rather than being eaten by the warm build.)
        chaos_plan = faults.arm(FaultPlan().fail(
            "index.rebuild", calls=range(1, chaos_n + 1)))
    print(f"index built: {store.tokens.shape[0]} news "
          f"({args.index}, ntotal={svc.ntotal}, v{svc.version}) in "
          f"{time.time()-t0:.1f}s")
    reqs = [h for h in log.histories[:args.requests]]

    if args.autotune and args.index != "exact":
        def tune_measure():
            recall = measure_recall(rec, reqs, k=args.k, probe=args.probe)
            user = _probe_users(rec, reqs, args.probe)
            t0 = time.perf_counter()          # measure_recall warmed this
            svc.query(user, args.k)           # (nprobe, k') executable
            return recall, (time.perf_counter() - t0) * 1e3
        best = serving.tune_service(
            svc, tune_measure, nprobes=(4, 8, 16, 32),
            k_primes=(max(4 * args.k, 32), args.k_prime, 2 * args.k_prime),
            target_recall=args.recall_threshold)
        rec.nprobe, rec.k_prime = best.nprobe, best.k_prime
        print(f"autotuned: nprobe={best.nprobe} k'={best.k_prime} "
              f"recall@{args.k}={best.recall:.3f} ({best.ms:.1f}ms/batch, "
              f"{len(best.trials)} configs tried)")

    on_batch = None
    if rebuild_mid_loop:
        n0 = svc.store.host.shape[0]
        rng = np.random.default_rng(1)

        def on_batch(i):
            if i != 2:            # once, early in the loop
                return
            fresh_ids = np.arange(n0, n0 + 32)
            fresh = (svc.store.host[1:33]
                     + 0.01 * rng.normal(size=(32, svc.store.dim))
                     ).astype(np.float32)
            rec.publish(fresh_ids, fresh)        # O(append) on this path
            svc.rebuild(mode="full", block=False)  # absorb off-path

    sweep_entries = None
    try:
        if args.open_loop:
            args.rebuild_mid_loop = rebuild_mid_loop   # chaos implies it
            sweep_entries, chaos_plan = open_loop_harness(
                args, rec, reqs, chaos_n=chaos_n)
        else:
            results, n_batches = micro_batch_loop(
                rec, reqs, max_batch=args.batch, on_batch=on_batch)
            if rebuild_mid_loop:
                svc.wait_for_build()
    finally:
        faults.disarm()          # tests call main() in-process
    if chaos_plan is not None:
        print(f"chaos: {chaos_plan.fired('index.rebuild')} rebuild faults "
              f"injected over {chaos_plan.calls('index.rebuild')} build "
              f"attempts; health now {svc.health()['status']}")
    recall = measure_recall(rec, reqs, k=args.k, probe=args.probe)
    stats = ServeStats.from_registry(
        recall_at_k=recall, recall_ok=recall >= args.recall_threshold,
        index_kind=args.index, ntotal=svc.ntotal)
    stats.load_sweep = sweep_entries
    if args.metrics_out:
        obs.tick(force=True)     # final full-registry snapshot
    print(f"{stats.n_requests} requests in {stats.n_batches} batches; "
          f"p50={stats.p50_ms:.1f}ms p99={stats.p99_ms:.1f}ms "
          f"recall@{args.k}={recall:.3f} "
          f"(v{stats.index_version}, {stats.n_swaps} swaps)")
    return stats


if __name__ == "__main__":
    main()
