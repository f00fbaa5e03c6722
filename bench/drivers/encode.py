"""Bulk-encode driver: the Recommender's corpus encode
(``Recommender._encode_corpus``, what ``build_index`` runs first) at its
own chunk, over passes of a seeded corpus, repeated through the window.

End to end: ``encode_articles_per_s``, the real articles of every pass the
window ran over the window's whole time.

``correct``: a sample of the last pass's embeddings, drawn from the seed,
against the plain float32 BusLM forward at the precision the
configuration states (float32 storage, the backend's DEFAULT matmul
passes), by the relative error ||e - e_ref|| / ||e_ref|| of each sampled
article: the largest (an article answered wrongly) and the mean over the
sample (an encoder that computes in a lower precision throughout).  The
gap to the HIGHEST-precision forward is printed beside them.  Beside
them, the share of the sampled embeddings' elements that bfloat16 holds
exactly: the configuration states float32 outputs.
"""
from __future__ import annotations

import gc
import time
import types

import numpy as np

from bench import flops
from bench.drivers.common import program_config
from bench.reference import speedyfeed as ref
from bench.traffic import news


def make_corpus(seed: int, config: dict, traffic: dict):
    p = config["plm"]
    rng = news.rng_for(seed, 31)
    words = news.word_counts(traffic["n_articles"], traffic["short_frac"],
                             rng)[:, :p["n_segments"]]
    tok, freq, lengths = news.tokens_for(
        words, vocab=p["vocab"], seg_len=p["seg_len"], max_freq=p["max_freq"],
        rng=news.rng_for(seed, 32))
    pad = np.zeros((1,) + tok.shape[1:], np.int32)
    return types.SimpleNamespace(tokens=np.concatenate([pad, tok]),
                                 freq=np.concatenate([pad, freq]),
                                 lengths=np.concatenate([[0], lengths]))


def sample_rows(seed: int, n: int, k: int) -> np.ndarray:
    """k article rows (1-based) drawn from the seed."""
    return np.sort(news.rng_for(seed, 33).choice(
        np.arange(1, n + 1), size=min(k, n), replace=False))


def reference_embeddings(seed, config, corpus, rows, *, nx=ref.F32,
                         block: int = 256):
    """The plain BusLM forward over ``rows``, in blocks."""
    import jax
    p = config["plm"]
    params = ref.init_params(seed, p)["plm"]
    enc = jax.jit(lambda pp, t, f: ref.encode(
        pp, t, f, n_heads=p["n_heads"], max_freq=p["max_freq"], nx=nx))
    out = []
    for i in range(0, len(rows), block):
        r = rows[i:i + block]
        out.append(np.asarray(enc(params, corpus.tokens[r], corpus.freq[r]),
                              np.float32))
    return np.concatenate(out)


def article_gaps(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Relative error ||e - e_ref|| / ||e_ref|| of each sampled article."""
    g, w = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(g - w, axis=1) / np.linalg.norm(w, axis=1)


def emb_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest relative error of an embedding over the sample."""
    return float(np.max(article_gaps(got, want)))


def bf16_share(got: np.ndarray) -> float:
    """Share of the float32 elements that bfloat16 holds exactly (their
    low 16 bits are zero): about 2**-16 for values computed and kept in
    float32, 1 for values computed or kept in bfloat16."""
    bits = np.ascontiguousarray(got, np.float32).view(np.uint32)
    return float(np.mean((bits & 0xFFFF) == 0))


def run(ctx):
    import jax
    from bench.result import Check, Result
    from repro import obs
    from repro.launch.serve import Recommender
    config, traffic = ctx.config, ctx.cell["traffic"]
    cfg = program_config(config)
    t0 = time.perf_counter()
    corpus = make_corpus(ctx.seed, config, traffic)
    t1 = time.perf_counter()
    params = ref.init_params(ctx.seed, config["plm"])
    jax.block_until_ready(params)
    t2 = time.perf_counter()
    rec = Recommender(cfg, params, corpus)
    chunk = int(traffic["chunk"])
    rec._encode_corpus(chunk=chunk)                             # warm-up
    t3 = time.perf_counter()
    print(f"encode: set-up parts: corpus {t1 - t0:.3f}s, weights "
          f"{t2 - t1:.3f}s, one warm pass (compile or cache load "
          f"included) {t3 - t2:.3f}s", flush=True)
    n = traffic["n_articles"]
    compiles0 = obs.counter("xla_compile_events_total").value
    passes, emb = 0, None
    with ctx.window():
        t_end = time.perf_counter() + ctx.seconds
        while time.perf_counter() < t_end:
            emb = rec._encode_corpus(chunk=chunk)
            passes += 1
    ctx.read_memory()
    window_compiles = obs.counter("xla_compile_events_total").value - compiles0
    rows = sample_rows(ctx.seed, n, int(traffic["check_rows"]))
    got = emb[rows]
    del rec, params, emb
    gc.collect()
    want = reference_embeddings(ctx.seed, config, corpus, rows,
                                nx=ref.STATED)
    per = article_gaps(got, want)
    highest = emb_gap(got, reference_embeddings(ctx.seed, config, corpus,
                                                rows))
    p = config["plm"]
    per_article = flops.encoder_forward(
        d=p["d_model"], d_ff=p["d_ff"], n_layers=p["n_layers"],
        n_segments=p["n_segments"], seg_len=p["seg_len"],
        news_dim=p["news_dim"])
    articles = passes * n
    stats = {"window_s": ctx.window_s, "articles": articles,
             "passes": passes, "model_flops": articles * per_article,
             "window_compiles": window_compiles, "plm": p}
    print(f"encode: {passes} passes of {n} articles in {ctx.window_s:.3f}s;"
          f" compiles in window {window_compiles}; gap to the HIGHEST "
          f"forward {highest!r}", flush=True)
    lim = ctx.cell["limits"]
    return Result(e2e={"encode_articles_per_s": articles / ctx.window_s},
                  attempted=articles, failed=0,
                  checks=[Check("emb_gap", float(per.max()), lim["emb_gap"]),
                          Check("emb_gap_mean", float(per.mean()),
                                lim["emb_gap_mean"]),
                          Check("bf16_share", bf16_share(got),
                                lim["bf16_share"])],
                  stats=stats)
