"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

What the TPU runtime writes (JAX 0.9, v5e): one plane per chip named
``/device:TPU:<n>`` whose ``XLA Ops`` line holds one event per executed
HLO instruction, named by the instruction's HLO text (``%bus_attention.1
= f32[512,3,12,32,64]... custom-call(...), custom_call_target=
"tpu_custom_call"``), with start and duration in nanoseconds on the same
clock as the host planes.  Host planes (``/host:CPU``) hold one line per
thread; ``jax.profiler.TraceAnnotation`` spans land there under their
names, next to the runtime's own events.

The window is the host span ``bench_window`` that the harness opens around
the measured window.
"""
from __future__ import annotations

import dataclasses
import re

WINDOW = "bench_window"
_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|"
                    r"f64)\[([0-9,]*)\]")
_FLOW = re.compile(r"[\s)}](while|conditional|call)\(")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
             "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8}


@dataclasses.dataclass
class Op:
    start: float          # ns
    dur: float            # ns
    text: str             # HLO text of the instruction

    @property
    def name(self) -> str:
        """Instruction name: ``bus_attention.1`` of ``%bus_attention.1 = ...``."""
        return self.text.split(" ", 1)[0].lstrip("%")

    @property
    def is_control_flow(self) -> bool:
        """A loop or call whose body's ops are listed on their own."""
        return _FLOW.search(self.text) is not None

    @property
    def is_kernel(self) -> bool:
        return 'custom_call_target="tpu_custom_call"' in self.text

    def shapes(self):
        """[(dtype, dims)] of the result(s) and then every operand, as the
        HLO text lists them."""
        return [(t, tuple(int(x) for x in d.split(",") if x))
                for t, d in _SHAPE.findall(self.text.split(", custom_call")[0]
                                           .split(", operand_layout")[0])]


def nbytes(shape) -> int:
    t, dims = shape
    n = 1
    for d in dims:
        n *= d
    return n * _ITEMSIZE[t]


@dataclasses.dataclass
class Trace:
    devices: dict          # plane name -> [Op] in start order
    host: list             # [(start, dur, name)] of every host event
    window: tuple          # (start, end) ns of the bench_window span
    lag: float = 0.0       # ns the device clock was moved forward

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def ops(self, device=None):
        """Device ops inside the window (of one device, or all)."""
        planes = [device] if device else sorted(self.devices)
        t0, t1 = self.window
        return [op for p in planes for op in self.devices[p]
                if op.start >= t0 and op.start + op.dur <= t1]


def load(path) -> Trace:
    """Read a trace.  The device clock runs behind the host's by a
    millisecond or two on v5e; it is moved so that no program starts on
    the device before the host enqueued it (``run_id`` ties the device's
    ``XLA Modules`` events to the host's ``DoEnqueueProgram``)."""
    from jax.profiler import ProfileData
    p = ProfileData.from_file(str(path))
    devices, host, mods, enq = {}, [], {}, {}
    for plane in p.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(Op(e.start_ns, e.duration_ns, e.name)
                               for e in line.events)
                elif line.name == "XLA Modules":
                    for e in line.events:
                        rid = dict(e.stats).get("run_id")
                        if rid is not None:
                            mods[str(rid)] = e.start_ns
            devices[plane.name] = sorted(ops, key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.append((e.start_ns, e.duration_ns, e.name))
                    if e.name == "DoEnqueueProgram":
                        rid = dict(e.stats).get("run_id")
                        if rid is not None:
                            enq[str(rid)] = e.start_ns
    lag = max([enq[r] - mods[r] for r in mods if r in enq] + [0.0])
    for ops in devices.values():
        for op in ops:
            op.start += lag
    spans = [(s, s + d) for s, d, n in host if n == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW!r} span in {path}")
    return Trace(devices, host, max(spans, key=lambda w: w[1] - w[0]), lag)


def busy_intervals(ops):
    """Union of the ops' [start, end) intervals, merged and sorted."""
    out = []
    for op in sorted(ops, key=lambda o: o.start):
        s, e = op.start, op.start + op.dur
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_seconds(trace: Trace) -> float:
    """Seconds in which some op ran on the device, averaged over the
    devices the trace holds."""
    if not trace.devices:
        return 0.0
    tot = sum(sum(e - s for s, e in busy_intervals(trace.ops(d)))
              for d in trace.devices)
    return tot * 1e-9 / len(trace.devices)


_SPAN = re.compile(r"^([a-z][a-z0-9_]*|PjitFunction\(.*\))$")


def idle_gaps(trace: Trace, device=None):
    """Idle intervals of one device inside the window, each named by what
    the host was doing at its midpoint: the innermost named span open
    then (a ``TraceAnnotation`` of the program or the benchmark, or a jit
    dispatch), else "none".  [(name, seconds)]."""
    device = device or sorted(trace.devices)[0]
    busy = busy_intervals(trace.ops(device))
    t0, t1 = trace.window
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    host = sorted((h for h in trace.host if h[2] != WINDOW and h[1] > 0
                   and _SPAN.match(h[2])), key=lambda h: h[0])
    out, open_, i = [], [], 0
    for s, e in gaps:                     # gaps in time order: one sweep
        mid = 0.5 * (s + e)
        while i < len(host) and host[i][0] <= mid:
            open_.append(host[i])
            i += 1
        # a span closed before this midpoint is closed for later ones too
        open_ = [h for h in open_ if h[0] + h[1] >= mid]
        best = min(open_, key=lambda h: h[1], default=None)
        out.append((best[2] if best else "none", (e - s) * 1e-9))
    return out


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most time (loops and calls left out: the
    ops of their bodies are listed) and the idle time by what the host
    was doing, each as [[name, seconds]], at most ``top`` entries."""
    dev = sorted(trace.devices)[0] if trace.devices else None
    per_op: dict = {}
    for op in (trace.ops(dev) if dev else []):
        if not op.is_control_flow:
            per_op[op.name] = per_op.get(op.name, 0.0) + op.dur * 1e-9
    per_gap: dict = {}
    for name, sec in (idle_gaps(trace, dev) if dev else []):
        per_gap[name] = per_gap.get(name, 0.0) + sec
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa
    return {"device_ops": [[k, v] for k, v in rank(per_op)],
            "idle_gaps": [[k, v] for k, v in rank(per_gap)]}


def kernels(trace: Trace, pattern: str, device=None):
    """Kernel (Mosaic custom-call) ops of the window whose instruction
    name contains ``pattern``."""
    return [op for op in trace.ops(device)
            if op.is_kernel and pattern in op.name]


def roofline_share(ops, cost, peaks):
    """Percent of the kernels' device time that the chip's roofline
    needs: sum over ops of max(ops / peak FLOP/s, bytes / peak bandwidth)
    over the sum of their durations.  ``cost(op) -> (ops, bytes)``; None
    when the window ran no such kernel."""
    if not ops:
        return None
    need = 0.0
    for op in ops:
        n_ops, n_bytes = cost(op)
        need += max(n_ops / peaks.bf16_flops, n_bytes / peaks.hbm_bytes_per_s)
    return 100.0 * need / (sum(op.dur for op in ops) * 1e-9)
