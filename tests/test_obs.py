"""Telemetry-layer contracts: log2 histogram geometry + exact percentiles,
label-series isolation, span nesting/reentrancy across threads (the
serving tier times a background rebuild concurrently with the request
loop), the per-op overhead budget (the meter must not re-add the host
work §4 removed), scoped CompileCounter attribution, MetricsBuffer
history retention, finite_metrics NaN routing, and the exporters."""
import json
import math
import threading
import time
import warnings

import numpy as np
import pytest

from repro import obs
from repro.obs.export import Reporter, prometheus_text, write_jsonl
from repro.obs.registry import (MetricsRegistry, N_BUCKETS, bucket_le,
                                _bucket_index, series_key)


@pytest.fixture(autouse=True)
def _clean_default_registry():
    """Tests that touch the module-default registry start and end empty
    (other suites run launchers in-process and assert exact counts)."""
    obs.reset()
    obs.set_enabled(True)
    yield
    obs.reset()
    obs.set_enabled(True)


# ---------------------------------------------------------------------------
# bucket geometry + percentile accuracy
# ---------------------------------------------------------------------------

def test_bucket_geometry():
    assert bucket_le(N_BUCKETS - 1) == math.inf
    les = [bucket_le(i) for i in range(N_BUCKETS)]
    assert les == sorted(les)
    rng = np.random.default_rng(0)
    for v in np.concatenate([10.0 ** rng.uniform(-4, 5, 200),
                             [0.0, -1.0, 1e-12, 1e12]]):
        i = _bucket_index(float(v))
        assert 0 <= i < N_BUCKETS
        assert v < bucket_le(i) or i == 0
        if i > 0:
            assert v >= bucket_le(i - 1)


def test_histogram_percentiles_match_numpy():
    reg = MetricsRegistry()
    h = reg.histogram("lat_ms")
    rng = np.random.default_rng(1)
    xs = rng.lognormal(mean=2.0, sigma=1.5, size=1000)
    for x in xs:
        h.observe(float(x))
    for p in (50, 90, 95, 99, 99.9):
        assert h.percentile(p) == pytest.approx(np.percentile(xs, p),
                                                rel=0, abs=0)
    assert h.count == 1000
    assert h.sum == pytest.approx(xs.sum())
    assert sum(h.bucket_counts()) == 1000


def test_histogram_reservoir_windows_to_recent():
    reg = MetricsRegistry()
    h = reg.histogram("w", reservoir=100)
    for v in range(1000):
        h.observe(float(v))
    # ring holds the most recent 100 samples: 900..999
    assert h.percentile(50) == pytest.approx(
        np.percentile(np.arange(900, 1000), 50))
    assert h.count == 1000                  # buckets still see the stream
    assert sum(h.bucket_counts()) == 1000


def test_histogram_empty_percentile_is_nan():
    reg = MetricsRegistry()
    assert math.isnan(reg.histogram("e").percentile(99))


# ---------------------------------------------------------------------------
# series identity
# ---------------------------------------------------------------------------

def test_label_series_isolation():
    reg = MetricsRegistry()
    a = reg.counter("req_total", phase="queued")
    b = reg.counter("req_total", phase="e2e")
    plain = reg.counter("req_total")
    a.inc(3)
    b.inc()
    assert a is reg.counter("req_total", phase="queued")   # memoized
    assert a.value == 3 and b.value == 1 and plain.value == 0
    snap = reg.collect()
    assert snap['req_total{phase="queued"}'] == 3
    assert snap['req_total{phase="e2e"}'] == 1
    assert snap["req_total"] == 0


def test_series_key_sorts_labels():
    assert series_key("x", (("b", "2"), ("a", "1"))) == 'x{b="2",a="1"}'
    assert (series_key("x", tuple(sorted({"b": 2, "a": 1}.items())))
            == 'x{a="1",b="2"}')


def test_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError, match="already registered"):
        reg.histogram("x")


def test_label_named_name_is_legal():
    # span_ms uses a label literally called "name"
    reg = MetricsRegistry()
    h = reg.histogram("span_ms", name="rebuild")
    h.observe(1.0)
    assert 'span_ms{name="rebuild"}' in reg.collect()


def test_gauge_set_fn_computed_at_collect():
    reg = MetricsRegistry()
    box = {"v": 1}
    reg.gauge("depth").set_fn(lambda: box["v"])
    assert reg.collect()["depth"] == 1
    box["v"] = 7
    assert reg.collect()["depth"] == 7
    reg.gauge("bad").set_fn(lambda: 1 / 0)
    assert math.isnan(reg.collect()["bad"])


# ---------------------------------------------------------------------------
# thread safety + span nesting
# ---------------------------------------------------------------------------

def test_counter_and_histogram_under_threads():
    reg = MetricsRegistry()
    c = reg.counter("n")
    h = reg.histogram("h")

    def work():
        for i in range(1000):
            c.inc()
            h.observe(float(i % 7) + 0.5)

    ts = [threading.Thread(target=work) for _ in range(8)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert c.value == 8000
    assert h.count == 8000
    assert sum(h.bucket_counts()) == 8000


def test_span_nesting_records_each_level():
    reg = MetricsRegistry()
    with obs.span("outer", registry=reg):
        with obs.span("inner", registry=reg):
            time.sleep(0.002)
    outer = reg.histogram("span_ms", name="outer")
    inner = reg.histogram("span_ms", name="inner")
    assert outer.count == 1 and inner.count == 1
    assert outer.percentile(50) >= inner.percentile(50) >= 2.0


def test_span_reentrant_across_threads():
    """Background-rebuild + request-loop shape: spans of different names
    (and the same name) time concurrently into their own series."""
    reg = MetricsRegistry()
    stop = threading.Event()

    def rebuild():
        while not stop.is_set():
            with obs.span("rebuild", registry=reg):
                time.sleep(0.001)

    t = threading.Thread(target=rebuild)
    t.start()
    try:
        for _ in range(20):
            with obs.span("request", registry=reg):
                with obs.span("request", registry=reg, stage="rerank"):
                    time.sleep(0.0005)
    finally:
        stop.set()
        t.join()
    assert reg.histogram("span_ms", name="request").count == 20
    assert reg.histogram("span_ms", name="request",
                         stage="rerank").count == 20
    assert reg.histogram("span_ms", name="rebuild").count >= 1


def test_span_disabled_creates_nothing():
    reg = MetricsRegistry(enabled=False)
    with obs.span("x", registry=reg):
        pass
    assert reg.collect() == {}


# ---------------------------------------------------------------------------
# overhead budget (ISSUE: counter inc + span in single-digit µs, disabled
# path near-zero).  Budgets are several× the measured numbers (~1µs inc,
# ~10µs span) so a loaded CI box doesn't flake; min-of-repeats de-noises.
# ---------------------------------------------------------------------------

def _best_per_op_us(fn, n=2000, repeats=5):
    best = math.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        best = min(best, (time.perf_counter() - t0) / n)
    return best * 1e6


def test_overhead_budget():
    reg = MetricsRegistry()
    c = reg.counter("ops")
    h = reg.histogram("lat")
    assert _best_per_op_us(c.inc) < 25.0
    assert _best_per_op_us(lambda: h.observe(1.25)) < 50.0

    def spin():
        with obs.span("s", registry=reg):
            pass

    assert _best_per_op_us(spin, n=500) < 250.0

    off = MetricsRegistry(enabled=False)
    oc = off.counter("ops")
    oh = off.histogram("lat")
    assert _best_per_op_us(oc.inc) < 5.0
    assert _best_per_op_us(lambda: oh.observe(1.25)) < 5.0

    def spin_off():
        with obs.span("s", registry=off):
            pass

    assert _best_per_op_us(spin_off, n=500) < 50.0


# ---------------------------------------------------------------------------
# CompileCounter scoped attribution (regression: nested counters used to
# both count every event -> doubled compile tallies)
# ---------------------------------------------------------------------------

def test_compile_counter_nested_attribution():
    from repro.training import trainer as tr
    with tr.CompileCounter() as outer:
        tr._on_compile(tr._COMPILE_EVENT, 0.001)
        with tr.CompileCounter() as inner:
            tr._on_compile(tr._COMPILE_EVENT, 0.001)
            tr._on_compile(tr._COMPILE_EVENT, 0.001)
        tr._on_compile(tr._COMPILE_EVENT, 0.001)
    assert inner.count == 2          # innermost only, no fan-out
    assert outer.count == 2          # before + after the nested scope
    # every event still lands in the process-wide obs tally
    assert obs.counter("xla_compile_events_total").value == 4
    assert obs.histogram("xla_compile_ms").count == 4
    # other events are ignored
    tr._on_compile("/jax/other/event", 1.0)
    assert obs.counter("xla_compile_events_total").value == 4


# ---------------------------------------------------------------------------
# MetricsBuffer: bounded history + non-scalar warning (regression: drain
# kept only `loss`, silently discarding every other per-step series)
# ---------------------------------------------------------------------------

def test_metrics_buffer_history_and_nonscalar_warning():
    import jax.numpy as jnp

    from repro.training.trainer import MetricsBuffer
    buf = MetricsBuffer(history_len=8)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for i in range(12):
            buf.append({"loss": jnp.float32(i), "acc": jnp.float32(i * 2),
                        "vec": jnp.arange(3)})
        last = buf.drain()
    assert list(buf.history["loss"]) == [float(i) for i in range(4, 12)]
    assert list(buf.history["acc"]) == [float(i * 2) for i in range(4, 12)]
    assert "vec" not in buf.history
    assert np.asarray(last["vec"]).shape == (3,)
    assert [str(x.message) for x in w if "non-scalar" in str(x.message)] \
        and len([x for x in w if "non-scalar" in str(x.message)]) == 1
    assert buf.losses == [float(i) for i in range(12)]


def test_metrics_buffer_on_drain_hook():
    import jax.numpy as jnp

    from repro.training.trainer import MetricsBuffer
    got = []
    buf = MetricsBuffer(on_drain=got.extend)
    buf.append({"loss": jnp.float32(1.0)})
    buf.append({"loss": jnp.float32(2.0)})
    buf.drain()
    assert [float(m["loss"]) for m in got] == [1.0, 2.0]


# ---------------------------------------------------------------------------
# finite_metrics NaN/Inf routing
# ---------------------------------------------------------------------------

def test_finite_metrics_counts_and_warns_once():
    from repro.configs import base
    base._nonfinite_warned.discard("loss")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = base.finite_metrics({"loss": np.float32("nan"),
                                   "acc": np.float32(0.5)})
        base.finite_metrics({"loss": np.float32("inf")})
    assert math.isnan(out["loss"]) and out["acc"] == pytest.approx(0.5)
    assert obs.counter("nonfinite_metrics_total", key="loss").value == 2
    assert obs.counter("nonfinite_metrics_total", key="acc").value == 0
    assert len([x for x in w if "non-finite" in str(x.message)]) == 1


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_write_jsonl_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("req").inc(3)
    reg.histogram("lat", phase="e2e").observe(2.0)
    p = tmp_path / "m.jsonl"
    write_jsonl(str(p), registry=reg, extra={"run": "t"})
    write_jsonl(str(p), registry=reg)
    rows = [json.loads(l) for l in p.read_text().splitlines()]
    assert len(rows) == 2 and rows[0]["run"] == "t"
    m = rows[-1]["metrics"]
    assert m["req"] == 3
    assert m['lat{phase="e2e"}']["count"] == 1
    assert m['lat{phase="e2e"}']["p50"] == pytest.approx(2.0)


def test_prometheus_text_shape():
    reg = MetricsRegistry()
    reg.counter("req_total", phase="a").inc(2)
    reg.gauge("depth").set(3)
    h = reg.histogram("lat_ms")
    h.observe(0.5)
    h.observe(100.0)
    txt = prometheus_text(reg)
    assert "# TYPE req_total counter" in txt
    assert 'req_total{phase="a"} 2' in txt
    assert "# TYPE depth gauge" in txt and "depth 3" in txt
    assert "# TYPE lat_ms histogram" in txt
    assert 'lat_ms_bucket{le="+Inf"} 2' in txt      # cumulative tops out
    assert "lat_ms_count 2" in txt
    # cumulative counts are monotone over le
    cums = [int(l.rsplit(" ", 1)[1]) for l in txt.splitlines()
            if l.startswith("lat_ms_bucket")]
    assert cums == sorted(cums)


def test_reporter_cadence_and_force(tmp_path):
    reg = MetricsRegistry()
    reg.counter("n").inc()
    p = tmp_path / "r.jsonl"
    r = Reporter(path=str(p), every_s=3600.0, registry=reg)
    assert r.tick() is False and not p.exists()
    assert r.tick(force=True) is True
    assert json.loads(p.read_text().splitlines()[-1])["metrics"]["n"] == 1


def test_module_helpers_and_reset():
    obs.counter("a").inc()
    obs.gauge("g").set(2)
    obs.histogram("h").observe(1.0)
    assert set(obs.collect()) == {"a", "g", "h"}
    obs.reset()
    assert obs.collect() == {}
    obs.set_enabled(False)
    obs.counter("a").inc()
    assert obs.counter("a").value == 0 and not obs.enabled()
    obs.set_enabled(True)


# ---------------------------------------------------------------------------
# spans and counts on the profiler's clock: labels, trace-only args and
# count values become stats of host events in the trace
# ---------------------------------------------------------------------------

def _host_events(trace_dir):
    """[(name, stats dict)] of every host event of the one trace under
    ``trace_dir``, read back as the benchmark reads it."""
    from jax.profiler import ProfileData
    (path,) = list(trace_dir.glob("**/*.xplane.pb"))
    p = ProfileData.from_file(str(path))
    return [(e.name, dict(e.stats)) for plane in p.planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events]


def test_span_and_counts_land_as_trace_stats(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("encode_chunk", stage="fwd",
                      trace_args={"users": 17}):
            pass
        obs.counts("encode_window", rows=256, tokens=4321)
        obs.counts("encode_window", rows=1, tokens=7)
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    spans = [st for n, st in ev if n == "encode_chunk"]
    assert spans == [{"stage": "fwd", "users": 17}]
    assert [st for n, st in ev if n == "encode_window"] == [
        {"rows": 256, "tokens": 4321}, {"rows": 1, "tokens": 7}]
    # the registry counts the same values; trace-only args add no series
    assert obs.counter("encode_window_rows_total").value == 257
    assert obs.counter("encode_window_tokens_total").value == 4328
    assert 'span_ms{name="encode_chunk",stage="fwd"}' in obs.collect()
    assert not any("users" in k for k in obs.collect())


def test_counts_without_a_trace_and_disabled():
    reg = MetricsRegistry()
    obs.counts("w", registry=reg, a=2, b=3)
    obs.counts("w", registry=reg, a=1, b=0)
    assert reg.counter("w_a_total").value == 3
    assert reg.counter("w_b_total").value == 3
    off = MetricsRegistry(enabled=False)
    obs.counts("w", registry=off, a=2)
    assert off.collect() == {}
