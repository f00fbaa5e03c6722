"""``encode_run_token_fill.train``: the real tokens over the token slots
of the chunks the encoder ran, each at its own segment length, from the
window's ``train_window`` counts; nothing where the program counts no
such slots."""
import types

import pytest

from bench import program_trace as pt_mod
from bench import trace
from bench.tests.test_encode_run_fill import reader


def _read(monkeypatch, host):
    tr = trace.Trace({}, [(0, 100, trace.WINDOW)], (0, 100))
    monkeypatch.setattr(pt_mod, "load",
                        lambda t, root=None: pt_mod.ProgramTrace(host, {}))
    return reader("encode_run_token_fill.train").read(
        types.SimpleNamespace(trace=tr))


def test_encode_run_token_fill_on_hand_made_events(monkeypatch):
    host = [(10, 1, "train_window", {"steps": 20, "enc_tokens": 90000,
                                     "enc_token_slots": 380000,
                                     "enc_token_slots_run": 170000,
                                     "encode_chunks_short": 150}),
            (50, 1, "train_window", {"steps": 1, "enc_tokens": 4000,
                                     "enc_token_slots": 19000,
                                     "enc_token_slots_run": 7680,
                                     "encode_chunks_short": 9})]
    assert _read(monkeypatch, host) == pytest.approx(
        100 * 94000 / 177680)


def test_encode_run_token_fill_reads_nothing_without_slots_run(monkeypatch):
    """A program that runs every chunk at the bucket's length counts
    ``enc_token_slots`` alone."""
    host = [(10, 1, "train_window", {"steps": 20, "enc_tokens": 90000,
                                     "enc_token_slots": 380000})]
    assert _read(monkeypatch, host) is None
    assert _read(monkeypatch, []) is None
