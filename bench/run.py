"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the workload entry in
``BENCHMARK.json``, its cell file ``bench/cells/<cell>.json`` (driver and
traffic parameters, and the limits of the comparison that decides
``correct``), its configuration file, the driver
``bench/drivers/<driver>.py`` and, with ``--trace 1``, one reader
``bench/metrics/<metric>.py`` per per-layer metric.  Adding a cell,
configuration or metric means adding files and entries, never editing
these.

The run needs the chips its cell names: it exits 3 and prints no result
when JAX finds no TPU or too few.  Set-up (data, weights, compiles,
warm-up) is timed as ``setup_s``; the driver then measures its window
for ``--seconds`` and checks what the window produced against the plain
reference.  The compared numbers are printed with their limits as the
last lines of stderr, and the result as one JSON object on the last line
of stdout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse            # noqa: E402
import contextlib          # noqa: E402
import gc                  # noqa: E402
import importlib.util      # noqa: E402
import json                # noqa: E402
import os                  # noqa: E402
import pathlib             # noqa: E402
import shutil              # noqa: E402
import sys                 # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT = ROOT / ".bench_out"


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod            # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


class Context:
    """What the harness gives a driver: the cell, its configuration, the
    seed and window length, the device, and the window and memory hooks."""

    def __init__(self, *, workload, cell, config, seed, seconds, trace,
                 devices):
        self.workload, self.cell, self.config = workload, cell, config
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices
        self.setup_s = None
        self.window_s = None
        self.memory_peak_bytes = None
        self.trace_path = None

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it opens.  With
        tracing, the profiler records exactly this span."""
        import jax
        tdir = OUT / "trace" / self.workload["name"]
        if self.trace:
            shutil.rmtree(tdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tdir), profiler_options=opts)
        self.setup_s = time.perf_counter() - T_START
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench_window"):
                yield
        finally:
            self.window_s = time.perf_counter() - t0
            if self.trace:
                jax.profiler.stop_trace()
                found = sorted(tdir.glob("**/*.xplane.pb"))
                self.trace_path = found[-1] if found else None

    def read_memory(self):
        """Peak device memory so far, on the fullest chip; drivers call it
        after the window, before anything else runs on the device."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None
        return self.memory_peak_bytes


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def tpu_devices(n_chips: int):
    """The TPU devices, or exit 3 naming what JAX found instead."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n_chips:
        print(f"bench: the cell needs {n_chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(3)
    return devs[:n_chips]


def configure_cache():
    """JAX's persistent compilation cache inside the checkout (the
    program's ``launch/compile_cache.py``), keeping every program so that
    only a cell's first run in a checkout compiles."""
    import jax
    from repro.launch.compile_cache import configure_compile_cache
    where = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def metrics_for(bench, wl_name, kind):
    """The metric entries of one kind ("end_to_end" or "per_layer") that
    this cell reports."""
    out = []
    e2e_here = {m["name"] for m in bench["end_to_end"]
                if wl_name in m.get("workloads", [wl_name])}
    for m in bench[kind]:
        if "workloads" in m:
            if wl_name in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e_here:
            out.append(m)
    return out


class Readings:
    """What a per-layer reader gets: the driver's raw numbers, the trace
    (traced runs only), the window length and the chip's peaks."""

    def __init__(self, stats, trace, window_s, peaks):
        self.stats, self.trace = stats, trace
        self.window_s, self.peaks = window_s, peaks


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = find(bench["workloads"], args.workload, "workload")
    cfg_entry = find(bench["configs"], wl["config"], "config")
    cell = json.loads((ROOT / "bench" / "cells"
                       / f"{wl['name']}.json").read_text())
    if cell["config"] != wl["config"]:
        raise SystemExit(f"bench: cell file names config {cell['config']!r},"
                         f" BENCHMARK.json {wl['config']!r}")
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    devices = tpu_devices(int(wl["chips"]))
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program is not in {src}", file=sys.stderr)
        sys.exit(3)
    sys.path[:0] = [str(src), str(ROOT)]
    configure_cache()

    out = run_cell(bench, wl, cell, config, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   devices=devices)
    print(json.dumps(out), flush=True)
    os._exit(0)      # loader and scheduler threads are daemons; end now


def run_cell(bench, wl, cell, config, *, seed, seconds, trace, devices):
    """A run after the look for chips: the driver's set-up, window and
    comparison, then the cell's metrics.  Returns the result object and
    prints the compared numbers with their limits as the last lines of
    stderr."""
    driver = load_module(ROOT / "bench" / "drivers" / f"{cell['driver']}.py",
                         f"bench_driver_{cell['driver']}")
    ctx = Context(workload=wl, cell=cell, config=config, seed=seed,
                  seconds=seconds, trace=trace, devices=devices)
    res = driver.run(ctx)
    gc.collect()

    import jax
    from bench import peaks as peaks_mod
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    out = {"correct": all(c.ok for c in res.checks) and bool(res.checks),
           "attempted": res.attempted, "failed": res.failed}
    metrics, breakdown = {}, None
    if trace:
        from bench import trace as trace_mod
        peaks = peaks_mod.peaks_for(devices[0].device_kind)
        tr = trace_mod.load(ctx.trace_path)
        device["busy_s"] = trace_mod.busy_seconds(tr)
        device["window_s"] = tr.window_s
        breakdown = trace_mod.breakdown(tr)
        readings = Readings(res.stats, tr, ctx.window_s, peaks)
        for m in metrics_for(bench, wl["name"], "per_layer"):
            reader = load_module(ROOT / "bench" / "metrics" / f"{m['name']}.py",
                                 "bench_metric_" + m["name"].replace(".", "_"))
            v = reader.read(readings)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in metrics_for(bench, wl["name"], "end_to_end"):
            v = ctx.setup_s if m["name"] == "setup_s" else res.e2e.get(
                m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                       for c in res.checks}
    print(f"bench: {wl['name']} seed {seed}: set-up {ctx.setup_s:.3f}s, "
          f"window {ctx.window_s:.3f}s, whole run "
          f"{time.perf_counter() - T_START:.1f}s", file=sys.stderr)
    for c in res.checks:
        print(f"compared {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    return out


if __name__ == "__main__":
    main()
