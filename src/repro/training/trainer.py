"""Trainer — bucket-aware donated step executables + async input pipeline.

One `jax.jit`-wrapped state step with `donate_argnums=(0,)` serves every
seg-length bucket: jit's shape-keyed cache gives each bucket its own warm
executable, so a bucket-8 batch runs a bucket-8 program instead of being
padded up to the global max (which silently threw away the loader's
bucketing). Compilations are observed via a `jax.monitoring` hook and
accounted per bucket — recompile hygiene is a tested invariant, not a hope.

The step path never syncs: batches arrive device-resident from the
DevicePrefetcher, metrics stay device scalars in a MetricsBuffer and are
fetched in one transfer every `log_every` steps, and checkpoints snapshot
to host only at the checkpoint cadence.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt, obs
from repro.distributed.straggler import StepTimeMonitor
from repro.resilience import faults
from repro.resilience.supervise import NonFiniteLossError

from .prefetch import STREAM_END, DevicePrefetcher
from .state import TrainState, restore_state, save_state

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_active_counters: list = []
_counters_lock = threading.Lock()
_listener_registered = False


def _on_compile(event, duration_secs, **kw):
    if event != _COMPILE_EVENT:
        return
    # every backend compile lands in the obs registry regardless of any
    # active scoped counter — the process-wide compile tally is never lost
    obs.counter("xla_compile_events_total").inc()
    obs.histogram("xla_compile_ms").observe(duration_secs * 1e3)
    with _counters_lock:
        if _active_counters:
            _active_counters[-1].count += 1


def ensure_compile_listener():
    """Register the process-wide jax.monitoring compile listener (idempotent;
    jax.monitoring has no unregister, so exactly one ever exists)."""
    global _listener_registered
    if not _listener_registered:
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _listener_registered = True


class CompileCounter:
    """Counts XLA backend compilations while active (jax.monitoring hook).

    Attribution is scoped to the *innermost* active counter: when
    counters nest, an event increments only the most recently entered
    one (the old fan-out-to-all behavior double-counted every nested
    compile in every enclosing counter — e.g. an outer benchmark counter
    around ``Trainer.step``'s per-bucket first-step counters saw each
    bucket compile twice).  The stack is global, not thread-local, so a
    counter also observes compiles issued by other threads (serving's
    background-rebuild compile hygiene tests rely on this); nesting
    *across* threads therefore attributes to whichever counter was
    entered last, which is the documented trade for not losing
    cross-thread events.  Totals are additionally always routed to the
    obs registry (``xla_compile_events_total`` / ``xla_compile_ms``).
    """

    def __init__(self):
        self.count = 0

    def __enter__(self):
        ensure_compile_listener()
        with _counters_lock:
            _active_counters.append(self)
        return self

    def __exit__(self, *exc):
        with _counters_lock:
            _active_counters.remove(self)
        return False


class MetricsBuffer:
    """Accumulates per-step device metric dicts; fetches lazily in one
    device_get per drain so the step loop never blocks on scalars.

    ``max_pending`` bounds the live device-scalar backlog when the caller
    never drains explicitly (e.g. ``log_every=0``).  Every drained scalar
    is appended to a bounded per-key ``history`` deque (``history_len``
    entries) so step time-series survive the drain instead of collapsing
    to the last step; non-scalar entries are kept in ``last`` as host
    arrays and warned about once per key (they are excluded from history
    — previously they were dropped without a trace).  ``on_drain`` (if
    given) receives each drained chunk as a list of host metric dicts —
    the Trainer uses it to feed the obs registry's cache counters.
    """

    def __init__(self, max_pending: int = 512, history_len: int = 4096,
                 on_drain=None):
        self.max_pending = max_pending
        self.history_len = history_len
        self._on_drain = on_drain
        self._pending = []
        self._warned: set = set()
        self.losses: list = []
        self.history: dict = {}      # key -> deque of host floats
        self.last: dict = {}

    def append(self, metrics: dict):
        self._pending.append(metrics)
        if len(self._pending) >= self.max_pending:
            self.drain()

    def drain(self) -> dict:
        """Fetch everything accumulated since the last drain; returns the
        most recent step's scalar metrics (host floats)."""
        if self._pending:
            from repro.configs.base import finite_metrics
            host = jax.device_get(self._pending)
            self._pending = []
            for m in host:
                for k, v in m.items():
                    if np.ndim(v) == 0:
                        dq = self.history.get(k)
                        if dq is None:
                            dq = self.history[k] = collections.deque(
                                maxlen=self.history_len)
                        dq.append(float(v))
                    elif k not in self._warned:
                        self._warned.add(k)
                        warnings.warn(
                            f"MetricsBuffer: metric {k!r} is non-scalar "
                            f"(shape {np.shape(v)}); kept in .last but "
                            f"excluded from per-step history",
                            stacklevel=2)
            self.losses.extend(float(m["loss"]) for m in host
                               if "loss" in m)
            # finite_metrics routes NaN/Inf scalars into the obs
            # nonfinite_metrics_total counter (one-shot warning per key)
            self.last = finite_metrics(host[-1])
            if self._on_drain is not None:
                self._on_drain(host)
        return self.last


_CACHE_COUNTER_KEYS = (
    # per-step device scalars computed from core/cache.py's age math
    # (pipeline.speedyfeed_forward) -> process counters, the paper's
    # headline cache-reuse signal
    ("cache_hits", "cache_hits_total"),
    ("cache_misses", "cache_misses_total"),
    ("cache_expired", "cache_expired_total"),
    ("cache_overflow", "cache_overflow_total"),
)


# per-step device counts of the encode set and the cache, summed over each
# drain into one ``train_window`` count (obs.counts): counters, and, while
# a profiler trace is being captured, an event on the trace's clock whose
# stats a benchmark window sums
_WINDOW_KEYS = ("encoded", "encode_rows", "encode_rows_run", "enc_tokens",
                "enc_token_slots", "cache_hits", "merged_news",
                "cache_overflow")


# an expert encoder's per-step counts (pipeline.speedyfeed_forward's
# ``moe_*``), summed into one ``moe_window`` count: rows routed to the
# experts held here, grouped-matmul row slots run, and the least and most
# rows a held expert took in each step
_MOE_KEYS = ("routed", "slots", "rows_least", "rows_most")


def _feed_drain_obs(host_metrics: list):
    """MetricsBuffer drain hook: fold the drained per-step cache scalars
    into obs counters and refresh the derived hit-rate gauge (plus the
    non-finite-guard skip counter, which drains on the same cadence), and
    write the drained steps' sums as one ``train_window`` count (and one
    ``moe_window`` count for an expert encoder)."""
    obs.counts("train_window", steps=len(host_metrics),
               **{k: int(sum(int(m[k]) for m in host_metrics))
                  for k in _WINDOW_KEYS if k in host_metrics[0]})
    if "moe_routed" in host_metrics[0]:
        obs.counts("moe_window", **{
            k: int(sum(int(m[f"moe_{k}"]) for m in host_metrics))
            for k in _MOE_KEYS})
    skipped = sum(float(m.get("nonfinite_step", 0.0)) for m in host_metrics)
    if skipped:
        obs.counter("train_nonfinite_steps_total").inc(skipped)
    for key, name in _CACHE_COUNTER_KEYS:
        total = sum(float(m[key]) for m in host_metrics if key in m)
        if total:
            obs.counter(name).inc(total)
    hits = obs.counter("cache_hits_total").value
    misses = obs.counter("cache_misses_total").value
    expired = obs.counter("cache_expired_total").value
    looked = hits + misses + expired
    if looked:
        obs.gauge("cache_hit_rate").set(hits / looked)


def _trailing_nonfinite(history: dict) -> int:
    """Length of the trailing run of guard-skipped steps in the drained
    ``nonfinite_step`` history (0 when the newest drained step was fine)."""
    dq = history.get("nonfinite_step")
    if not dq:
        return 0
    n = 0
    for v in reversed(dq):
        if v > 0:
            n += 1
        else:
            break
    return n


@dataclasses.dataclass
class TrainResult:
    steps_done: int
    losses: list
    resumed_from: int | None
    wall_seconds: float
    metrics: dict
    compile_counts: dict = dataclasses.field(default_factory=dict)
    # compiles observed on a bucket's later steps (recompiles; should be {})
    warm_compiles: dict = dataclasses.field(default_factory=dict)
    bucket_steps: dict = dataclasses.field(default_factory=dict)
    host_stall_fraction: float = 0.0
    # final TrainState (device arrays) — lets a downstream launcher serve
    # the trained params without re-threading the Trainer instance
    state: object = None
    # restarts consumed by resilience.fit_supervised (0 for a plain fit)
    restarts: int = 0


class Trainer:
    """Owns the jit'd donated step function and the full fit loop.

    ``make_step(cfg)`` must return the raw step
    ``(params, opt, cache, step, rng, batch) -> (params, opt, cache,
    metrics)``; ``init_fn(cfg, key) -> TrainState`` builds the initial
    state. Both are supplied by the arch config (see
    ``training.get_trainer``).
    """

    def __init__(self, cfg, *, make_step, init_fn, donate: bool = True,
                 mesh=None, batch_specs_fn=None, nonfinite_guard: bool = True):
        self.cfg = cfg
        self._raw_step = make_step(cfg)
        self._init_fn = init_fn
        self._donate = donate
        # nonfinite_guard: when the raw step's loss comes back NaN/Inf the
        # params / optimizer moments / cache keep their pre-step values (a
        # jnp.where select inside the same executable — Adam is never fed a
        # poisoned gradient), the step counter still advances past the bad
        # batch, and the skip is reported as the ``nonfinite_step`` metric
        self._nonfinite_guard = nonfinite_guard
        self.mesh = mesh
        # (mesh, batch_like) -> PartitionSpec tree; default is the generic
        # dim-0 data-parallel layout (distributed.sharding.batch_specs)
        self._batch_specs_fn = batch_specs_fn
        if mesh is None:
            # single-device path: identical to the pre-mesh Trainer — the
            # jit exists from __init__ and nothing consults the mesh again
            self._step_jit = jax.jit(
                self._state_step, donate_argnums=(0,) if donate else ())
        else:
            # sharded path: the jit is built on the first step, once the
            # concrete state/batch pytree structure is known (in/out
            # shardings are full pytrees of NamedSharding)
            self._step_jit = None
        self.state_shardings: TrainState | None = None
        self.compile_counts: dict = {}    # bucket -> backend compiles
        self.warm_compiles: dict = {}     # bucket -> compiles after its 1st
        self.bucket_steps: dict = {}      # bucket -> steps run
        self.monitor: StepTimeMonitor | None = None   # set by fit()
        self.last_state: TrainState | None = None     # final state of fit()
        self.metrics_buffer: MetricsBuffer | None = None   # of the last fit
        # compile events flow into the obs registry for every fit, not
        # only while a CompileCounter is explicitly active
        ensure_compile_listener()

    # -- step ---------------------------------------------------------------

    def _state_step(self, state: TrainState, batch):
        rng = jax.random.fold_in(state.rng, state.step)
        params, opt, cache, metrics = self._raw_step(
            state.params, state.opt, state.cache, state.step, rng, batch)
        if self._nonfinite_guard and isinstance(metrics, dict) \
                and "loss" in metrics:
            with jax.named_scope("update"):
                ok = jnp.isfinite(metrics["loss"])

                def keep(new, old):
                    return jnp.where(ok, new, old)

                params = jax.tree.map(keep, params, state.params)
                opt = jax.tree.map(keep, opt, state.opt)
                cache = jax.tree.map(keep, cache, state.cache)
            metrics = dict(metrics)
            metrics["nonfinite_step"] = 1.0 - ok.astype(jnp.float32)
        new = TrainState(params, opt, cache, state.step + 1, state.rng)
        return new, metrics

    @property
    def state_step(self):
        """The unjitted ``(TrainState, batch) -> (TrainState, metrics)``
        step — what the dry-run machinery lowers against abstract args."""
        return self._state_step

    def init_state(self, seed: int = 0) -> TrainState:
        return self._init_fn(self.cfg, jax.random.PRNGKey(seed))

    # -- mesh placement -----------------------------------------------------

    def _ensure_state_shardings(self, state: TrainState) -> TrainState:
        """Compute (once) the TrainState NamedShardings for ``self.mesh``."""
        if self.state_shardings is None:
            from .state import state_shardings
            self.state_shardings = state_shardings(state, self.mesh)
        return self.state_shardings

    def place_state(self, state: TrainState) -> TrainState:
        """Commit a state onto the mesh (no-op without one)."""
        if self.mesh is None:
            return state
        return jax.device_put(state, self._ensure_state_shardings(state))

    def batch_shardings(self, batch):
        """NamedShardings for a batch pytree on the mesh (the prefetcher
        calls this per batch so batches arrive committed to their final
        layout)."""
        from repro.distributed import sharding as shx
        fn = self._batch_specs_fn or shx.batch_specs
        return shx.named(self.mesh, fn(self.mesh, batch))

    def _build_mesh_jit(self, state: TrainState, batch) -> TrainState:
        """First-step jit construction on the sharded path: pin the donated
        state's in/out shardings to the same placement (donation requires
        matching layouts) and replicate the scalar metrics.  Returns
        ``state`` committed to its shardings."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        state_sh = self._ensure_state_shardings(state)
        state = jax.device_put(state, state_sh)
        batch_sh = self.batch_shardings(batch)
        metrics_abs = jax.eval_shape(self._state_step, state, batch)[1]
        rep = NamedSharding(self.mesh, P())
        metrics_sh = jax.tree.map(lambda _: rep, metrics_abs)
        self._step_jit = jax.jit(
            self._state_step,
            donate_argnums=(0,) if self._donate else (),
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, metrics_sh))
        return state

    # -- step ---------------------------------------------------------------

    def step(self, state: TrainState, batch: dict, bucket=None):
        """One donated train step. ``state`` is consumed (its buffers are
        donated to the executable) — use only the returned state."""
        if self._step_jit is None:            # sharded path, first step
            state = self._build_mesh_jit(state, batch)
        if bucket is None:
            with self._ambient_mesh():
                return self._step_jit(state, batch)
        if bucket not in self.compile_counts:
            with CompileCounter() as cc, self._ambient_mesh():
                out = self._step_jit(state, batch)
            self.compile_counts[bucket] = cc.count
        else:
            # a warm bucket must never compile again; counted by the jit's
            # executables, not a CompileCounter, which would take the
            # compile away from a caller's enclosing counter
            n0 = self.executable_count()
            with self._ambient_mesh():
                out = self._step_jit(state, batch)
            if grew := self.executable_count() - n0:
                self.warm_compiles[bucket] = (self.warm_compiles.get(bucket, 0)
                                              + grew)
        self.bucket_steps[bucket] = self.bucket_steps.get(bucket, 0) + 1
        return out

    def compiled_text(self, state: TrainState, batch) -> str:
        """Optimized HLO of the step executable for these shapes — where a
        caller checks which kernels (``tpu_custom_call``) the step runs."""
        with self._ambient_mesh():
            return self._step_jit.lower(state, batch).compile().as_text()

    def _ambient_mesh(self):
        """The mesh as the ambient mesh while the step traces, so kernels
        XLA cannot partition run per device (kernels.ops.bus_attention)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def executable_count(self) -> int:
        """Number of distinct compiled executables behind the step jit."""
        return self._step_jit._cache_size()

    # -- fit ----------------------------------------------------------------

    def fit(self, make_batcher, *, steps: int, state: TrainState | None = None,
            seed: int = 0, ckpt_dir: str | None = None, ckpt_every: int = 50,
            async_ckpt: bool = True, log_every: int = 20,
            fail_at: int | None = None, prefetch_depth: int = 2,
            batch_timeout: float = 60.0, hosts: int | None = None,
            microbatches_per_host: int = 1,
            max_consecutive_nonfinite: int = 8) -> TrainResult:
        """Train for ``steps`` total steps (resuming from the latest
        *valid* checkpoint in ``ckpt_dir`` when one exists — corrupt
        snapshots are quarantined and skipped by ``checkpoint.restore``;
        if every snapshot is corrupt, training starts from scratch with a
        warning instead of crashing).

        ``make_batcher(epoch)`` -> started DynamicBatcher; epochs roll over
        inside the prefetcher. ``fail_at`` injects a crash after that many
        total steps (restart tests); the ``train.step`` resilience fault
        site fires each completed step for plan-driven chaos.

        ``max_consecutive_nonfinite``: with the non-finite guard active,
        a run of this many consecutive NaN/Inf-loss steps raises
        ``NonFiniteLossError`` (checked at the metrics drain cadence, i.e.
        every ``log_every`` steps) — ``fit_supervised`` classifies it as
        transient and rolls back to the last checkpoint.  0 disables.

        ``hosts`` (default: ``jax.process_count()``) sets the straggler
        monitor's host count; with more than one (real processes, or
        simulated hosts for single-process runs) per-step wall times are
        attributed round-robin to hosts and the monitor's ``stragglers()``/
        ``rebalance(microbatches_per_host)`` outputs surface as the
        ``straggler_hosts`` / ``microbatch_alloc{host=}`` obs gauges at the
        drain cadence.
        """
        t0 = time.time()
        cc0, bs0 = dict(self.compile_counts), dict(self.bucket_steps)
        wc0 = dict(self.warm_compiles)
        state = state if state is not None else self.init_state(seed)
        resumed = None
        if ckpt_dir and ckpt.latest_step(ckpt_dir) is not None:
            try:
                if self.mesh is not None:
                    # restore leaves directly onto their mesh placement — a
                    # single-device checkpoint lands sharded, and vice versa
                    resumed, state = restore_state(
                        ckpt_dir, state,
                        shardings=self._ensure_state_shardings(state))
                else:
                    resumed, state = restore_state(ckpt_dir, state)
            except FileNotFoundError as e:
                # every snapshot failed verification (all quarantined by
                # restore): degrade to a fresh start, don't die on resume
                warnings.warn(f"resume skipped — {e}; training from "
                              f"scratch", stacklevel=2)
        if resumed is None and self.mesh is not None:
            state = self.place_state(state)
        step = int(state.step)

        # a resumed run must not replay the pre-crash batch stream: offset
        # the loader's epoch numbering (and thus its seeds) by the restored
        # step, mirroring the pre-Trainer loop's reseed-on-restart
        epoch0 = step if resumed is not None else 0
        writer = ckpt.AsyncCheckpointer(ckpt_dir) \
            if (ckpt_dir and async_ckpt) else None
        prefetcher = DevicePrefetcher(
            lambda e: make_batcher(e + epoch0), depth=prefetch_depth,
            sharding=self.batch_shardings if self.mesh is not None
            else None).start()
        n_hosts = hosts if hosts is not None else jax.process_count()
        monitor = StepTimeMonitor(n_hosts=max(n_hosts, 1))
        buf = MetricsBuffer(on_drain=_feed_drain_obs)
        stall, de_sum, de_n = 0.0, 0.0, 0
        drain_mark, drain_step = time.perf_counter(), step
        step_ctrs: dict = {}      # bucket -> train_steps_total counter
        try:
            while step < steps:
                t_iter = tw = time.perf_counter()
                with obs.span("train_host_stall"):
                    pb = prefetcher.get(timeout=batch_timeout)
                stall += time.perf_counter() - tw
                if pb is STREAM_END:       # bounded-epoch source ran dry
                    break
                if pb is None:
                    raise RuntimeError(
                        f"no batch within {batch_timeout}s at step {step}")
                # the dispatch, named on the trace with the batch's fill
                st = pb.stats or {}
                with obs.span("train_step", bucket=str(pb.bucket),
                              trace_args={k: st[k] for k in
                                          ("users", "user_slots")
                                          if k in st}):
                    state, metrics = self.step(state, pb.arrays, pb.bucket)
                buf.append(metrics)
                if pb.stats and "data_efficiency" in pb.stats:
                    de_sum += float(pb.stats["data_efficiency"])
                    de_n += 1
                step += 1
                ctr = step_ctrs.get(pb.bucket)
                if ctr is None:
                    ctr = step_ctrs[pb.bucket] = obs.counter(
                        "train_steps_total", bucket=str(pb.bucket))
                ctr.inc()
                if monitor.n_hosts > 1:
                    # simulated multi-host: attribute per-step loop wall
                    # round-robin (real multi-process runs would record
                    # their own host id here)
                    monitor.record((step - 1) % monitor.n_hosts,
                                   time.perf_counter() - t_iter)
                obs.tick()
                if fail_at is not None and step >= fail_at:
                    raise RuntimeError("injected failure")
                faults.fire("train.step", step=step)
                if ckpt_dir and step % ckpt_every == 0:
                    save_state(ckpt_dir, step, state, writer=writer)
                if log_every and step % log_every == 0:
                    m = buf.drain()
                    if max_consecutive_nonfinite:
                        bad = _trailing_nonfinite(buf.history)
                        if bad >= max_consecutive_nonfinite:
                            raise NonFiniteLossError(
                                f"{bad} consecutive non-finite losses at "
                                f"step {step}: params held at their last "
                                f"finite values by the guard; rolling back "
                                f"to the last checkpoint",
                                step=step, consecutive=bad)
                    now = time.perf_counter()
                    if monitor.n_hosts == 1:
                        # per-step dispatch time is meaningless on the
                        # async path; feed the straggler EMA true
                        # wall/step at the (blocking) drain cadence
                        monitor.record(0, (now - drain_mark)
                                       / max(step - drain_step, 1))
                    else:
                        # multi-host: per-step times were recorded in the
                        # loop; export the control-plane decisions
                        slow = monitor.stragglers()
                        obs.gauge("straggler_hosts").set(float(len(slow)))
                        for h, a in enumerate(
                                monitor.rebalance(microbatches_per_host)):
                            obs.gauge("microbatch_alloc",
                                      host=str(h)).set(float(a))
                    drain_mark, drain_step = now, step
                    print(f"step {step}: loss={m.get('loss', 0):.4f} "
                          f"acc={m.get('ar_acc', 0):.3f} "
                          f"reused={int(m.get('reused', 0))} "
                          f"p_t={m.get('p_t', 0):.2f} "
                          f"de={de_sum / max(de_n, 1):.2f} "
                          f"[bucket {pb.bucket}]", flush=True)
        finally:
            prefetcher.stop()
            if writer:
                writer.wait()
        self.monitor = monitor
        self.last_state = state
        self.metrics_buffer = buf
        final = buf.drain()
        if de_n:      # loader-side Eq. 1 data efficiency (paper Figure 8)
            final["loader_data_efficiency"] = de_sum / de_n
        wall = time.time() - t0
        # report THIS run's deltas (the Trainer's own counters are
        # cumulative across its lifetime, e.g. warm-up + repeated fits)
        compiles = {k: v - cc0.get(k, 0) for k, v in self.compile_counts
                    .items() if v - cc0.get(k, 0) > 0}
        bsteps = {k: v - bs0.get(k, 0) for k, v in self.bucket_steps.items()
                  if v - bs0.get(k, 0) > 0}
        warm = {k: v - wc0.get(k, 0) for k, v in self.warm_compiles.items()
                if v - wc0.get(k, 0) > 0}
        return TrainResult(step, buf.losses, resumed, wall, final,
                           compiles, warm, bsteps, stall / max(wall, 1e-9),
                           state=state)
