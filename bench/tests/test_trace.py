"""The trace reduction (bench/trace.py) on a trace recorded on a v5e by
bench/tools/record_trace.py (data/kernels.xplane.pb: the bus-attention
kernel forward and gradient at M=64, K=3, S=32, H=12, D=64 and the masked
LUT scan at B=16, N=8192, 96 x 256 codes, twice each), and on traces made
by hand."""
import importlib.util
import pathlib
import types

import pytest

from bench import flops, peaks, trace

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "bench" / "metrics" / f"{name}.py")
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


@pytest.fixture(scope="module")
def chip_trace():
    return trace.load(HERE / "data" / "kernels.xplane.pb")


def test_window_and_kernels(chip_trace):
    t = chip_trace
    assert t.window_s == pytest.approx(0.043544771)
    assert list(t.devices) == ["/device:TPU:0"]
    bus = trace.kernels(t, "bus_attention")
    assert sorted(op.name for op in bus) == [
        "bus_attention.1", "bus_attention.1",
        "transpose_jvp_jit_bus_attention_bwd___.1",
        "transpose_jvp_jit_bus_attention_bwd___.1"]
    assert len(trace.kernels(t, "pq_lut_scores")) == 2
    # the device clock is moved so no program starts before its enqueue
    assert t.lag > 0


def test_busy_and_breakdown(chip_trace):
    t = chip_trace
    busy = trace.busy_seconds(t)
    assert 0 < busy < t.window_s
    b = trace.breakdown(t)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "pq_lut_scores.1"
    assert b["device_ops"][0][1] == pytest.approx(2 * 7.068e-3, rel=1e-3)
    gaps = dict(b["idle_gaps"])
    assert {"host_fwd", "host_grad"} <= set(gaps)
    assert sum(gaps.values()) == pytest.approx(t.window_s - busy, rel=1e-6)


def test_kernel_rooflines(chip_trace):
    pk = peaks.peaks_for("TPU v5 lite")
    op = [o for o in trace.kernels(chip_trace, "bus_attention")
          if "bwd" not in o.name][0]
    from bench.metrics import _bus
    assert _bus.cost(op) == flops.bus_attention(M=64, K=3, S=32, H=12, D=64,
                                                backward=False)
    share = trace.roofline_share(trace.kernels(chip_trace, "bus_attention"),
                                 _bus.cost, pk)
    assert 0 < share <= 100
    r = reader("bus_attn_roofline.train")
    assert r.read(types.SimpleNamespace(trace=chip_trace, peaks=pk)) == share
    assert r.read(types.SimpleNamespace(trace=None, peaks=pk)) is None


def _hand_trace():
    ops = [trace.Op(10, 10, "%a.1 = f32[2]{0} fusion(f32[2]{0} %x)"),
           trace.Op(15, 10, "%b.1 = f32[2]{0} fusion(f32[2]{0} %x)"),
           trace.Op(40, 20, "%a.1 = f32[2]{0} fusion(f32[2]{0} %x)"),
           trace.Op(95, 10, "%c.1 = f32[2]{0} fusion(f32[2]{0} %x)")]
    host = [(0, 100, "bench_window"), (24, 17, "prefetch_h2d"),
            (60, 30, "train_host_stall"), (62, 5, "ReadSyncFlag")]
    return trace.Trace({"/device:TPU:0": ops}, host, (0, 100))


def test_busy_union_and_gaps_by_hand():
    t = _hand_trace()
    # busy: [10, 25) + [40, 60); the op ending after the window is out
    assert trace.busy_seconds(t) == pytest.approx(35e-9)
    assert trace.idle_gaps(t) == [("none", pytest.approx(10e-9)),
                                  ("prefetch_h2d", pytest.approx(15e-9)),
                                  ("train_host_stall", pytest.approx(40e-9))]
    b = trace.breakdown(t)
    assert b["device_ops"] == [["a.1", pytest.approx(30e-9)],
                               ["b.1", pytest.approx(10e-9)]]


def test_loops_count_as_busy_but_not_as_ops():
    ops = [trace.Op(10, 50, "%while.3 = (s32[], f32[2]{0}) while((s32[], "
                    "f32[2]{0}) %t), condition=%c, body=%b"),
           trace.Op(12, 5, "%fusion.7 = f32[2]{0} fusion(f32[2]{0} %x), "
                    "calls=%f")]
    t = trace.Trace({"/device:TPU:0": ops}, [(0, 100, "bench_window")],
                    (0, 100))
    assert trace.busy_seconds(t) == pytest.approx(50e-9)
    assert trace.breakdown(t)["device_ops"] == [["fusion.7",
                                                 pytest.approx(5e-9)]]
