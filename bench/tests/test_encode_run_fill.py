"""``encode_run_fill.train``: the encoded rows over the rows the encoder
ran, from the window's ``train_window`` counts; nothing where the program
counts no rows run."""
import importlib.util
import pathlib
import types

import pytest

from bench import program_trace as pt_mod
from bench import trace

ROOT = pathlib.Path(__file__).resolve().parents[2]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "bench" / "metrics" / f"{name}.py")
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def _read(monkeypatch, host):
    tr = trace.Trace({}, [(0, 100, trace.WINDOW)], (0, 100))
    monkeypatch.setattr(pt_mod, "load",
                        lambda t, root=None: pt_mod.ProgramTrace(host, {}))
    return reader("encode_run_fill.train").read(
        types.SimpleNamespace(trace=tr))


def test_encode_run_fill_on_hand_made_events(monkeypatch):
    host = [(10, 1, "train_window", {"steps": 20, "encoded": 4000,
                                     "encode_rows": 10240,
                                     "encode_rows_run": 4480}),
            (50, 1, "train_window", {"steps": 1, "encoded": 240,
                                     "encode_rows": 512,
                                     "encode_rows_run": 256})]
    assert _read(monkeypatch, host) == pytest.approx(100 * 4240 / 4736)


def test_encode_run_fill_reads_nothing_without_rows_run(monkeypatch):
    """A program that runs every row counts ``encode_rows`` alone."""
    host = [(10, 1, "train_window", {"steps": 20, "encoded": 4000,
                                     "encode_rows": 10240})]
    assert _read(monkeypatch, host) is None
    assert _read(monkeypatch, []) is None
