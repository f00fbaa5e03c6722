"""What the program writes into a traced window beside the device ops: its
host spans and counts with their stats, and the name stack of every
device op.

The program names its work on the profiler's clock (``repro.obs``):

- spans (``obs.span``: ``train_step``, ``encode_chunk``, ...), host events
  whose labels are the event's stats;
- counts (``obs.counts``: ``train_window``, ``encode_window``), zero-work
  host events whose stats count the work done since the one before;
- device scopes (``jax.named_scope``: ``plm_encode``, ``cache``,
  ``user_model``, ``loss``, ``update``), which XLA keeps in each
  instruction's metadata and the TPU runtime writes into the trace as the
  ``tf_op`` stat of the device op's event metadata, for example
  ``jit(_state_step)/transpose(jvp(plm_encode))/while/body/dot_general:``.

``bench/trace.load`` reads event names and times only, and the readers
get that reduction (``r.trace``), not the file.  ``load(trace)`` finds the
file again: the ``.xplane.pb`` under ``.bench_out/trace/`` whose
``bench_window`` span is the reduction's window.  From it, it reads the
host events inside the window with their stats (``ProfileData``), and the
device planes' event metadata with a small reader of the protobuf wire
format, since ``ProfileData`` shows event stats but not metadata stats.
Device ops are joined to their name stacks by the HLO text that
``trace.Op.text`` holds; host times are those of ``r.trace`` (its device
clock is moved, its host clock is not).  Each window is decoded once per
process.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re

from bench import trace as trace_mod

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_out" / "trace"
SCOPES = ("plm_encode", "cache", "user_model", "loss", "update")
UNSCOPED = "unscoped"

# program events: snake_case names (runtime events are CamelCase or carry
# punctuation); their stats are read, the others' are not
_PROGRAM = re.compile(r"^[a-z][a-z0-9_]*$")
_WRAPPED = re.compile(r"([^()]*)\((.*)\)")


@dataclasses.dataclass
class ProgramTrace:
    host: list        # [(start ns, dur ns, name, stats)] inside the window
    stacks: dict      # HLO text -> name stack, None where programs disagree


_CACHE: dict = {}


def load(trace, root=None) -> ProgramTrace | None:
    """The program's part of the traced window ``trace`` (a
    ``bench.trace.Trace``), or None when no trace file under ``root``
    (``TRACE_DIR``) has that window or the file cannot be read."""
    if trace is None:
        return None
    root = TRACE_DIR if root is None else root
    key = (str(root), tuple(trace.window))
    if key not in _CACHE:
        _CACHE[key] = _find(trace.window, pathlib.Path(root))
    return _CACHE[key]


def _find(window, root: pathlib.Path) -> ProgramTrace | None:
    files = sorted(root.glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime, reverse=True)
    for path in files:
        try:
            host, win = _host_events(path)
        except (OSError, ValueError, RuntimeError):
            continue
        if win != tuple(window):
            continue
        try:
            stacks = device_stacks(path.read_bytes())
        except (OSError, ValueError, IndexError):
            stacks = {}
        t0, t1 = win
        return ProgramTrace([h for h in host if t0 <= h[0] <= t1], stacks)
    return None


def _host_events(path):
    """Program events of the host planes with their stats, and the window
    as ``trace.load`` takes it (the longest ``bench_window`` span)."""
    from jax.profiler import ProfileData
    p = ProfileData.from_file(str(path))
    host, spans = [], []
    for plane in p.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name == trace_mod.WINDOW:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns))
                elif _PROGRAM.match(name):
                    host.append((e.start_ns, e.duration_ns, name,
                                 dict(e.stats)))
    win = max(spans, key=lambda w: w[1] - w[0]) if spans else None
    return host, win


# -- protobuf wire format (XSpace, tsl/profiler/protobuf/xplane.proto) ---

def _varint(b: bytes, i: int):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b: bytes, i: int, end: int):
    """(field number, value) of one message in ``b[i:end]``: an int for
    varints, a (start, end) pair for length-delimited fields."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(b: bytes, span):
    """The value (field 2) of one map entry."""
    for f, v in _fields(b, *span):
        if f == 2:
            return v
    return None


def device_stacks(b: bytes) -> dict:
    """HLO text -> name stack of every device op's event metadata in a
    serialized XSpace: the ``tf_op`` stat without its ``:<op type>``
    suffix.  Where two programs of the trace hold the same text under
    different stacks, the text maps to None."""
    out: dict = {}
    for f, plane in _fields(b, 0, len(b)):
        if f != 1:                                    # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for pf, v in _fields(b, *plane):
            if pf == 2:                               # XPlane.name
                name = _text(b, v)
            elif pf == 4:                             # event_metadata
                metas.append(v)
            elif pf == 5:                             # stat_metadata
                sm = _map_values(b, v)
                if sm is None:
                    continue
                sid, sname = None, ""
                for sf, sv in _fields(b, *sm):
                    if sf == 1:
                        sid = sv
                    elif sf == 2:
                        sname = _text(b, sv)
                stat_names[sid] = sname
        if not name.startswith("/device:"):
            continue
        tf_op = {k for k, n in stat_names.items() if n == "tf_op"}
        for entry in metas:
            em = _map_values(b, entry)
            if em is None:
                continue
            text, stack = None, None
            for ef, ev in _fields(b, *em):
                if ef == 2:                           # XEventMetadata.name
                    text = _text(b, ev)
                elif ef == 5:                         # XEventMetadata.stats
                    stack = _tf_op(b, ev, tf_op, stat_names) or stack
            if text is None or stack is None:
                continue
            if text in out and out[text] != stack:
                out[text] = None
            else:
                out[text] = stack
    return out


def _tf_op(b: bytes, stat, tf_op: set, stat_names: dict):
    """The name stack held by one XStat if it is a ``tf_op`` stat."""
    sid, value = None, None
    for sf, sv in _fields(b, *stat):
        if sf == 1:
            sid = sv
        elif sf == 5:                                 # str_value
            value = _text(b, sv)
        elif sf == 7:                                 # ref_value
            value = stat_names.get(sv)
    if sid not in tf_op or not value:
        return None
    head, sep, tail = value.rpartition(":")
    return head if sep and "/" not in tail else value


# -- what the readers compute ---------------------------------------------

def stack_names(stack) -> frozenset:
    """Every name of a name stack, each component unwrapped from
    transformations: ``transpose(jvp(plm_encode))`` gives ``transpose``,
    ``jvp`` and ``plm_encode``."""
    names = set()
    for comp in (stack or "").split("/"):
        while (m := _WRAPPED.fullmatch(comp)):
            names.add(m.group(1))
            comp = m.group(2)
        names.add(comp)
    return frozenset(names)


def scope_seconds(trace, pt: ProgramTrace) -> dict | None:
    """Device op seconds of the window by program scope (``SCOPES``, and
    ``UNSCOPED`` for ops in none of them), loops and calls left out as
    ``trace.breakdown`` leaves them out; None when no op of the window
    carries a program scope."""
    if trace is None or pt is None:
        return None
    memo: dict = {}
    out = dict.fromkeys(SCOPES + (UNSCOPED,), 0.0)
    for op in trace.ops():
        if op.is_control_flow:
            continue
        scope = memo.get(op.text, False)
        if scope is False:
            names = stack_names(pt.stacks.get(op.text))
            scope = memo[op.text] = next(
                (s for s in SCOPES if s in names), UNSCOPED)
        out[scope] += op.dur * 1e-9
    if not any(out[s] for s in SCOPES):
        return None
    return out


def scope_share(trace, scope: str) -> float | None:
    """Percent of the window's device op time in ops of one scope."""
    sec = scope_seconds(trace, load(trace))
    if sec is None:
        return None
    return 100.0 * sec[scope] / sum(sec.values())


def window_counts(pt: ProgramTrace | None, name: str) -> dict | None:
    """Sum of each stat over the window's count events ``name``; None when
    the window holds none."""
    if pt is None:
        return None
    out: dict = {}
    for _, _, n, stats in pt.host:
        if n == name:
            for k, v in stats.items():
                out[k] = out.get(k, 0) + v
    return out or None


def fill(trace, name: str, part: str, whole: str) -> float | None:
    """Percent ``sum(part) / sum(whole)`` over the window's count events
    ``name``."""
    c = window_counts(load(trace), name)
    if not c or not c.get(whole):
        return None
    return 100.0 * c.get(part, 0) / c[whole]


def idle_inside(trace, span: str) -> float | None:
    """Percent of the window in which the (first) device is idle while the
    host is inside a span ``span``; None when the window holds no such
    span or no device."""
    if trace is None or not trace.devices or trace.window_s <= 0:
        return None
    t0, t1 = trace.window
    host = trace_mod.busy_intervals(
        [trace_mod.Op(max(s, t0), min(s + d, t1) - max(s, t0), "")
         for s, d, n in trace.host if n == span and s < t1 and s + d > t0])
    if not host:
        return None
    busy = trace_mod.busy_intervals(trace.ops(sorted(trace.devices)[0]))
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    both, j = 0.0, 0
    for s, e in idle:                     # both lists sorted, disjoint
        while j < len(host) and host[j][1] <= s:
            j += 1
        k = j
        while k < len(host) and host[k][0] < e:
            both += min(e, host[k][1]) - max(s, host[k][0])
            k += 1
    return 100.0 * both * 1e-9 / trace.window_s
