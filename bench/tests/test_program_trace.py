"""The program's part of a traced window (bench/program_trace.py) and the
readers built on it: name stacks decoded from the chip fixture's device
metadata, host events and their stats from a trace recorded here on the
CPU, and every reader on traces and events made by hand."""
import importlib.util
import pathlib
import types

import pytest

from bench import program_trace as pt_mod
from bench import trace

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRAIN_READERS = ("plm_share.train", "cache_share.train",
                 "update_share.train", "encode_fill.train",
                 "encode_token_fill.train", "cache_hit_frac.train")
ENCODE_READERS = ("encode_token_fill.encode", "fetch_idle_frac.encode")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "bench" / "metrics" / f"{name}.py")
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


@pytest.fixture(scope="module")
def chip_trace():
    return trace.load(HERE / "data" / "kernels.xplane.pb")


def test_device_metadata_gives_name_stacks(chip_trace):
    pt = pt_mod.load(chip_trace, root=HERE / "data")
    assert pt is not None
    ops = {op.text: op for op in chip_trace.ops()
           if op.name == "copy_bitcast_fusion.1"}
    stacks = {op.text: pt.stacks[t] for t, op in ops.items()}
    fwd = [t for t in stacks if "S(1)" in t.split(" fusion(")[0]]
    assert len(ops) == 2 and len(fwd) == 1
    assert stacks[fwd[0]] == "jit(<lambda>)/jit(bus_attention)/transpose"
    assert set(stacks.values()) == {
        "jit(<lambda>)/jit(bus_attention)/transpose",
        "jit(<lambda>)/transpose(jvp(jit(bus_attention_bwd)))/transpose"}
    kernel = next(op for op in chip_trace.ops()
                  if op.name == "bus_attention.1")
    assert pt.stacks[kernel.text] == \
        "jit(<lambda>)/jit(bus_attention)/pallas_call"
    # the fixture's ops carry no program scope: the shares read nothing
    assert pt_mod.scope_seconds(chip_trace, pt) is None
    # a window no file under the root holds
    other = trace.Trace(chip_trace.devices, [], (0.0, 1.0))
    assert pt_mod.load(other, root=HERE / "data") is None


def test_stack_names_unwrap_transformations():
    names = pt_mod.stack_names(
        "jit(_state_step)/transpose(jvp(plm_encode))/while/body/"
        "closed_call/checkpoint/dynamic_update_slice")
    assert {"plm_encode", "transpose", "jvp", "checkpoint",
            "dynamic_update_slice"} <= names
    assert "update" not in names                  # no substring match
    assert pt_mod.stack_names(None) == frozenset({""})


def test_counts_from_a_trace_recorded_here(tmp_path, monkeypatch):
    """Host events and their stats, read from a real profiler file found
    by its window, and the count readers on them."""
    import jax
    from repro import obs
    obs.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    obs.counts("encode_window", rows=9, tokens=100, token_slots=900)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        obs.counts("encode_window", rows=1, tokens=1000, token_slots=1000)
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            with obs.span("encode_chunk", trace_args={"n": 3}):
                obs.counts("encode_window", rows=8, tokens=30,
                           token_slots=96)
            obs.counts("encode_window", rows=8, tokens=18, token_slots=96)
            obs.counts("train_window", steps=2, encoded=5, encode_rows=8,
                       enc_tokens=7, enc_token_slots=70, cache_hits=1,
                       merged_news=4)
    finally:
        jax.profiler.stop_trace()
    obs.reset()
    (path,) = list(tmp_path.glob("**/*.xplane.pb"))
    tr = trace.load(path)
    pt = pt_mod.load(tr, root=tmp_path)
    assert [(n, st) for _, _, n, st in pt.host if n == "encode_chunk"] \
        == [("encode_chunk", {"n": 3})]
    assert pt_mod.window_counts(pt, "encode_window") == {
        "rows": 16, "tokens": 48, "token_slots": 192}
    monkeypatch.setattr(pt_mod, "TRACE_DIR", tmp_path)
    r = types.SimpleNamespace(trace=tr)
    assert reader("encode_token_fill.encode").read(r) == pytest.approx(25.0)
    assert reader("encode_fill.train").read(r) == pytest.approx(62.5)
    assert reader("encode_token_fill.train").read(r) == pytest.approx(10.0)
    assert reader("cache_hit_frac.train").read(r) == pytest.approx(25.0)
    # no device in a CPU trace: the device readers read nothing
    for name in ("plm_share.train", "fetch_idle_frac.encode"):
        assert reader(name).read(r) is None


def _op(start, dur, name, tf_op):
    text = f"%{name} = f32[2]{{0}} fusion(f32[2]{{0}} %x)"
    return trace.Op(start, dur, text), (text, tf_op)


def _scoped_trace():
    """Ops of 10, 20, 30, 5, 15 and 20 ns in plm_encode (fwd and bwd),
    cache, update, user_model, loss and none; a loop that is left out."""
    made = [_op(0, 10, "a.1", "jit(s)/jvp(plm_encode)/while/body/dot"),
            _op(10, 20, "b.1", "jit(s)/transpose(jvp(plm_encode))/while/"
                "body/closed_call/checkpoint/dot"),
            _op(30, 30, "c.1", "jit(s)/jvp(cache)/gather"),
            _op(60, 5, "d.1", "jit(s)/update/select_n"),
            _op(65, 15, "e.1", "jit(s)/transpose(jvp(user_model))/dot"),
            _op(80, 20, "f.1", "jit(s)/dynamic_update_slice"),
            _op(100, 10, "g.1", "jit(s)/jvp(loss)/reduce_sum")]
    ops = [op for op, _ in made]
    ops.append(trace.Op(0, 100, "%while.3 = (s32[]) while((s32[]) %t), "
                        "condition=%c, body=%b"))
    stacks = dict(s for _, s in made)
    tr = trace.Trace({"/device:TPU:0": ops}, [(0, 200, trace.WINDOW)],
                     (0, 200))
    return tr, pt_mod.ProgramTrace([], stacks)


def test_scope_shares_on_hand_made_ops(monkeypatch):
    tr, pt = _scoped_trace()
    monkeypatch.setattr(pt_mod, "load", lambda t, root=None: pt)
    sec = pt_mod.scope_seconds(tr, pt)
    assert sec == pytest.approx({"plm_encode": 30e-9, "cache": 30e-9,
                                 "update": 5e-9, "user_model": 15e-9,
                                 "loss": 10e-9, "unscoped": 20e-9})
    r = types.SimpleNamespace(trace=tr)
    assert reader("plm_share.train").read(r) == pytest.approx(30 / 110 * 100)
    assert reader("cache_share.train").read(r) == pytest.approx(
        30 / 110 * 100)
    assert reader("update_share.train").read(r) == pytest.approx(
        5 / 110 * 100)
    # a text that two programs hold under different stacks is unscoped
    pt.stacks[tr.devices["/device:TPU:0"][0].text] = None
    assert pt_mod.scope_seconds(tr, pt)["unscoped"] == pytest.approx(30e-9)


def test_counts_on_hand_made_events(monkeypatch):
    tr = trace.Trace({}, [(0, 100, trace.WINDOW)], (0, 100))
    host = [(10, 1, "train_window", {"steps": 20, "encoded": 4000,
                                     "encode_rows": 10240,
                                     "enc_tokens": 60000,
                                     "enc_token_slots": 384000,
                                     "cache_hits": 300,
                                     "merged_news": 6000}),
            (50, 1, "train_window", {"steps": 1, "encoded": 240,
                                     "encode_rows": 512,
                                     "enc_tokens": 4000,
                                     "enc_token_slots": 23040,
                                     "cache_hits": 20, "merged_news": 400}),
            (60, 1, "encode_window", {"rows": 256, "tokens": 6000,
                                      "token_slots": 24576})]
    pt = pt_mod.ProgramTrace(host, {})
    monkeypatch.setattr(pt_mod, "load", lambda t, root=None: pt)
    r = types.SimpleNamespace(trace=tr)
    assert reader("encode_fill.train").read(r) == pytest.approx(
        100 * 4240 / 10752)
    assert reader("encode_token_fill.train").read(r) == pytest.approx(
        100 * 64000 / 407040)
    assert reader("cache_hit_frac.train").read(r) == pytest.approx(
        100 * 320 / 6400)
    assert reader("encode_token_fill.encode").read(r) == pytest.approx(
        100 * 6000 / 24576)


@pytest.mark.parametrize("name", TRAIN_READERS + ENCODE_READERS)
def test_readers_read_nothing_without_their_events(monkeypatch, name):
    """The parent program writes no scope, count or fetch span: every
    reader then returns None and does not raise."""
    tr = trace.Trace({"/device:TPU:0": [trace.Op(
        10, 10, "%a.1 = f32[2]{0} fusion(f32[2]{0} %x)")]},
        [(0, 100, trace.WINDOW), (5, 40, "encode_chunk")], (0, 100))
    pt = pt_mod.ProgramTrace([(5, 40, "encode_chunk", {})],
                             {"%a.1 = f32[2]{0} fusion(f32[2]{0} %x)":
                              "jit(f)/dot"})
    monkeypatch.setattr(pt_mod, "load", lambda t, root=None: pt)
    r = types.SimpleNamespace(trace=tr)
    assert reader(name).read(r) is None
    monkeypatch.setattr(pt_mod, "load", lambda t, root=None: None)
    assert reader(name).read(r) is None
    assert reader(name).read(types.SimpleNamespace(trace=None)) is None


def test_fetch_idle_on_hand_made_trace():
    # device busy [10, 30) and [50, 90); fetch spans [20, 60) and [85, 120)
    ops = [trace.Op(10, 20, "%a.1 = f32[2]{0} fusion(f32[2]{0} %x)"),
           trace.Op(50, 40, "%b.1 = f32[2]{0} fusion(f32[2]{0} %x)")]
    host = [(0, 100, trace.WINDOW), (20, 40, "encode_fetch"),
            (85, 35, "encode_fetch"), (30, 20, "encode_chunk")]
    tr = trace.Trace({"/device:TPU:0": ops}, host, (0, 100))
    # idle [0, 10), [30, 50), [90, 100); inside a fetch: [30, 50), [90, 100)
    r = types.SimpleNamespace(trace=tr)
    assert reader("fetch_idle_frac.encode").read(r) == pytest.approx(30.0)
    assert pt_mod.idle_inside(tr, "encode_chunk") == pytest.approx(20.0)
