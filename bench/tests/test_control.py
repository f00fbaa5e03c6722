"""The control of each cell -- the plain reference put in the program's
place in bfloat16 -- must fail at least one of the cell's compared numbers
against the committed limits.  Here at a small size on the CPU; the
readings at the cells' own sizes on the chip are in PERF.md, from
bench/tools/limits.py."""
import json
import pathlib

from bench.tests import small

ROOT = pathlib.Path(__file__).resolve().parents[2]


def limits(name):
    return json.loads((ROOT / "bench" / "cells"
                       / f"{name}.json").read_text())["limits"]


def test_train_control_fails():
    from bench.drivers import train
    from bench.reference import speedyfeed as ref
    name = "train.sf_prod_1chip.mind"
    c = small.cell(name)
    s = train.Setup(11, small.CONFIG, c["traffic"])
    checked, corpus, start = s.checked, s.corpus, s.start
    s.free()
    want = train.reference_steps(11, small.CONFIG, corpus, checked, start)
    got = train.reference_steps(11, small.CONFIG, corpus, checked, start,
                                nx=ref.BF16)
    checks = train.compare(got, want, limits(name))
    assert not all(ch.ok for ch in checks), checks


def test_encode_control_fails():
    from bench.drivers import encode
    from bench.reference import speedyfeed as ref
    name = "encode.sf_prod_serve.bulk"
    t = small.cell(name)["traffic"]
    corpus = encode.make_corpus(11, small.CONFIG, t)
    rows = encode.sample_rows(11, t["n_articles"], t["check_rows"])
    want = encode.reference_embeddings(11, small.CONFIG, corpus, rows,
                                       nx=ref.STATED)
    got = encode.reference_embeddings(11, small.CONFIG, corpus, rows,
                                      nx=ref.BF16)
    lim = limits(name)
    assert (encode.emb_gap(got, want) > lim["emb_gap"]
            or encode.bf16_share(got) > lim["bf16_share"])

