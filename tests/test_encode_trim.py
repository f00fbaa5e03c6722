"""The encode set's length trim (core.pipeline.encode_set): each chunk of
the must-encode rows, sorted by length, runs at the shortest of the
multiples of 8 (and S) that holds its tokens.  Its embeddings, in the
plan's row order, and the gradients of a loss over them match the
encoder run on every row at the full segment length."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core import pipeline

K, S, VOCAB = 3, 32, 300


def cfg_for(budget):
    return core.make_config(vocab=VOCAB, n_layers=1, d_model=32, n_heads=4,
                            d_ff=64, n_segments=K, seg_len=S, news_dim=16,
                            n_news=300, encode_budget=budget, merged_cap=256)


def rows(lengths, seed=0):
    """[E, K, S] tokens whose rows end at ``lengths`` (one segment reaches
    it, the others stop anywhere before), and their frequencies."""
    rng = np.random.default_rng(seed)
    tok = np.zeros((len(lengths), K, S), np.int32)
    for r, n in enumerate(lengths):
        longest = rng.integers(0, K)
        for k in range(K):
            m = n if k == longest else rng.integers(0, n + 1)
            tok[r, k, :m] = rng.integers(1, VOCAB, m)
    freq = np.where(tok != 0, rng.integers(1, 8, tok.shape), 0)
    return jnp.asarray(tok), jnp.asarray(freq, jnp.int32)


def expected_counts(lengths, n_valid, G):
    """Token slots and short chunks of the trim, worked out by hand: the
    must-encode rows sorted by length, in chunks of G rows."""
    classes = np.array(pipeline.seg_len_classes(S))
    srt = np.sort(np.asarray(lengths[:n_valid]))
    slots = short = 0
    for c in range(-(-n_valid // G)):
        s = classes[np.searchsorted(classes, srt[c * G:(c + 1) * G].max())]
        slots += G * K * s
        short += s < S
    return slots, short


CASES = {
    # E, n_valid, row lengths
    "mixed": (128, 77, lambda rng, E: rng.choice([3, 8, 12, 20, 32], E)),
    "all_short": (128, 128, lambda rng, E: rng.integers(1, 9, E)),
    "all_full": (128, 128, lambda rng, E: np.full(E, S)),
    "none_valid": (128, 0, lambda rng, E: rng.integers(1, S + 1, E)),
    "one_chunk": (24, 17, lambda rng, E: rng.choice([2, 9, 15], E)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_trimmed_encode_matches_full_length(case):
    E, n_valid, draw = CASES[case]
    lengths = draw(np.random.default_rng(1), E)
    G = pipeline.encode_chunk_rows(E)
    assert (G == E) == (case == "one_chunk")
    cfg = cfg_for(E)
    tokens, freq = rows(lengths)
    params = core.init_speedyfeed(jax.random.PRNGKey(0), cfg)["plm"]
    w = jax.random.normal(jax.random.PRNGKey(1), (E, cfg.plm.news_dim))
    live = (jnp.arange(E) < n_valid)[:, None]

    def trimmed(p):
        emb, _, counts = pipeline.encode_set(p, cfg, tokens, freq,
                                             jnp.int32(n_valid))
        return jnp.sum(jnp.where(live, emb * w, 0)), (emb, counts)

    def full(p):
        emb = core.buslm_encode(p, cfg.plm, tokens, freq)
        return jnp.sum(jnp.where(live, emb * w, 0)), emb

    (loss, (emb, counts)), grads = jax.jit(
        jax.value_and_grad(trimmed, has_aux=True))(params)
    (loss_f, emb_f), grads_f = jax.jit(
        jax.value_and_grad(full, has_aux=True))(params)

    np.testing.assert_allclose(np.asarray(emb)[:n_valid],
                               np.asarray(emb_f)[:n_valid],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(loss), float(loss_f), rtol=1e-5,
                               atol=1e-6)
    top = max(float(jnp.abs(g).max()) for g in jax.tree.leaves(grads_f))
    for g, g_f in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_f)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_f),
                                   rtol=1e-4, atol=1e-6 * max(top, 1.0))

    if G == E:          # a single chunk always runs, at its longest row
        s = pipeline.seg_len_classes(S)[np.searchsorted(
            pipeline.seg_len_classes(S), max(lengths[:n_valid]))]
        slots, short = E * K * s, int(s < S)
    else:
        slots, short = expected_counts(lengths, n_valid, G)
    assert int(counts["enc_token_slots_run"]) == slots
    assert int(counts["encode_chunks_short"]) == short
    if case == "all_short":
        assert short == E // G and slots == E * K * 8
    if case == "all_full":
        assert short == 0 and slots == E * K * S
    if case == "none_valid":
        assert slots == 0 and float(jnp.abs(emb).max()) == 0.0


def test_seg_len_classes():
    assert pipeline.seg_len_classes(32) == (8, 16, 24, 32)
    assert pipeline.seg_len_classes(16) == (8, 16)
    assert pipeline.seg_len_classes(12) == (8, 12)
    assert pipeline.seg_len_classes(8) == (8,)
    assert pipeline.seg_len_classes(5) == (5,)
