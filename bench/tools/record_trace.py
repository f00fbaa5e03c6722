"""Record a small profiler trace of the main-path kernels on the chip.

    python bench/tools/record_trace.py [--out DIR]

Runs the BusLM bus-attention kernel (forward and gradient, through
``kernels.ops``) at PROD's head shapes and the masked IVF-PQ LUT scan
under the JAX profiler, copies the ``.xplane.pb`` to ``DIR`` and prints
every plane and line of the trace with a sample of event names and their
stats, so that the reduction in ``bench/trace.py`` can be written against
what the device really reports.  The trace it writes is the fixture of
``bench/tests/test_trace.py``.  Exits 1 when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import glob
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "trace"))
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        sys.exit(1)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels import ops

    M, K, S, H, D = 64, 3, 32, 12, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    q = jax.random.normal(ks[0], (M, K, S, H, D))
    k = jax.random.normal(ks[1], (M, K, S + K, H, D))
    v = jax.random.normal(ks[2], (M, K, S + K, H, D))
    mask = jax.random.bernoulli(ks[3], 0.8, (M, K, S + K)).at[:, :, 0].set(True)
    g = jax.random.normal(ks[4], (M, K, S, H, D))
    fwd = jax.jit(lambda q, k, v: ops.bus_attention(q, k, v, mask))
    grad = jax.jit(jax.grad(lambda q, k, v: (ops.bus_attention(
        q, k, v, mask) * g).sum(), argnums=(0, 1, 2)))
    B, n_sub, n_codes, N = 16, 96, 256, 8192
    lut = jax.random.normal(ks[5], (B, n_sub, n_codes))
    codes = jax.random.randint(ks[6], (B, N, n_sub), 0, n_codes).astype(
        jnp.uint8)
    valid = jax.random.bernoulli(ks[7], 0.9, (B, N))
    scan = jax.jit(lambda l, c, m: ops.pq_lut_scores(l, c, m, block_n=4096))
    jax.block_until_ready((fwd(q, k, v), grad(q, k, v),
                           scan(lut, codes, valid)))           # compile

    tmp = ROOT / ".bench_out" / "record_trace"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench_window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("host_fwd"):
                jax.block_until_ready(fwd(q, k, v))
            with jax.profiler.TraceAnnotation("host_grad"):
                jax.block_until_ready(grad(q, k, v))
            with jax.profiler.TraceAnnotation("host_scan"):
                jax.block_until_ready(scan(lut, codes, valid))
            time.sleep(0.01)
    jax.profiler.stop_trace()
    print(f"traced window {time.perf_counter() - t0:.4f}s")
    src = glob.glob(str(tmp / "**" / "*.xplane.pb"), recursive=True)[0]
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out / "kernels.xplane.pb")
    print(f"xplane {pathlib.Path(src).stat().st_size} bytes -> {out}")

    from jax.profiler import ProfileData
    p = ProfileData.from_file(src)
    for pl in p.planes:
        lines = list(pl.lines)
        print(f"PLANE {pl.name!r} lines={len(lines)} "
              f"stats={dict(list(pl.stats)[:8]) if pl.stats else {}}")
        for ln in lines:
            evs = list(ln.events)
            print(f"  LINE {ln.name!r} events={len(evs)}")
            seen = set()
            for e in evs:
                if e.name in seen or len(seen) >= 12:
                    continue
                seen.add(e.name)
                st = {k: (str(v)[:160]) for k, v in dict(e.stats).items()}
                print(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns}"
                      f" stats={json.dumps(st)[:700]}")
    print("memory_stats", json.dumps(jax.devices()[0].memory_stats())[:600])


if __name__ == "__main__":
    main()
