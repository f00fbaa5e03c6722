"""Seeded MIND-like news traffic, vectorised.

The same model as ``repro.data.news_synth`` (Zipf popularity over a random
permutation of the news, topic-driven users with 1-3 preferred topics,
headline-style short news, lognormal click counts), drawn with numpy array
operations instead of per-news and per-user Python loops, and refined to
tokens directly: each segment is its leading CLS token plus one token per
word up to the segment length (OBoW refinement keeps at most that many
words), with each word's frequency drawn around its segment's words per
kept token.

Every seed draws the same multiset of sizes (click counts, which news are
short, word counts come from fixed quantiles) in another order.  A cell
that needs the same work from every seed draws the shape (who clicks how
many news of which length) from one fixed seed, and lets its seed relabel
the news ids and draw the tokens (``make_corpus``'s ``token_seed`` and
``relabel``).
"""
from __future__ import annotations

import dataclasses

import numpy as np

CLS = 1


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one of the benchmark's random streams."""
    return np.random.default_rng([int(seed) % (2 ** 63), stream])


def quantiles(draw_ppf, n: int, rng: np.random.Generator) -> np.ndarray:
    """n values at the mid-quantiles of a distribution, in random order."""
    q = (np.arange(n) + 0.5) / n
    return rng.permutation(draw_ppf(q))


def _lognormal_ppf(mu: float, sigma: float):
    from statistics import NormalDist
    nd = NormalDist()
    z = np.array([nd.inv_cdf(float(p)) for p in np.linspace(0, 1, 4097)[1:-1]])
    grid = np.linspace(0, 1, 4097)[1:-1]

    def ppf(q):
        return np.exp(mu + sigma * np.interp(q, grid, z))
    return ppf


def _exact_share(n: int, frac: float, rng) -> np.ndarray:
    """A boolean array with round(frac * n) True entries, shuffled."""
    out = np.zeros(n, bool)
    out[:int(round(frac * n))] = True
    return rng.permutation(out)


@dataclasses.dataclass
class Corpus:
    tokens: np.ndarray      # [N + 1, K, S] int32, row 0 the pad article
    freq: np.ndarray        # [N + 1, K, S] int32
    lengths: np.ndarray     # [N + 1] longest segment, in tokens


def word_counts(n: int, short_frac: float, rng) -> np.ndarray:
    """[n, 3] words in title, abstract and body, as news_synth draws
    them: short (headline) news from lognormal(2.0, 0.9) clipped to 3..60
    words, full articles from lognormal(6.0, 0.7) clipped to 40..3000."""
    short = _exact_share(n, short_frac, rng)
    ls = np.clip(quantiles(_lognormal_ppf(2.0, 0.9), n, rng), 3, 60)
    ll = np.clip(quantiles(_lognormal_ppf(6.0, 0.7), n, rng), 40, 3000)
    L = np.where(short, ls, ll).astype(np.int64)
    title = np.where(short, np.maximum(3, L // 3), np.maximum(4, L // 40))
    abstract = np.where(short, np.maximum(4, L // 2), np.maximum(8, L // 10))
    return np.stack([title, abstract, L], axis=1)


def tokens_for(words: np.ndarray, *, vocab: int, seg_len: int,
               max_freq: int, rng):
    """Refined [n, K, S] tokens and frequencies for [n, K] word counts."""
    n, K = words.shape
    kept = np.minimum(words, seg_len - 1)                 # words after CLS
    length = kept + 1
    pos = np.arange(seg_len)[None, None, :]
    valid = pos < length[..., None]
    tok = rng.integers(2, vocab, size=(n, K, seg_len), dtype=np.int32)
    tok[:, :, 0] = CLS
    tok = np.where(valid, tok, 0).astype(np.int32)
    per = np.maximum(words / np.maximum(kept, 1), 1.0)    # words per token
    f = 1 + rng.poisson(np.broadcast_to((per - 1.0)[..., None], tok.shape))
    f[:, :, 0] = 1
    f = np.where(valid, np.minimum(f, max_freq - 1), 0).astype(np.int32)
    return tok, f, length.max(axis=1).astype(np.int32)


def make_corpus(seed: int, *, n_news: int, vocab: int, n_segments: int,
                seg_len: int, max_freq: int, short_frac: float, rows=None,
                token_seed: int | None = None, relabel=None) -> Corpus:
    """The corpus; with ``rows`` (1-based ids) only those rows get tokens
    (the others stay the pad article: nothing reads them).  ``seed`` draws
    the news' lengths, ``token_seed`` (``seed`` if not given) their tokens,
    and ``relabel`` (a permutation of 1..n_news) moves news i to row
    ``relabel[i - 1]``."""
    words = word_counts(n_news, short_frac, rng_for(seed, 14))[:, :n_segments]
    tokens = np.zeros((n_news + 1, n_segments, seg_len), np.int32)
    freq = np.zeros_like(tokens)
    lengths = np.zeros(n_news + 1, np.int32)
    ids = np.arange(1, n_news + 1) if rows is None else np.unique(rows)
    t, f, ln = tokens_for(words[ids - 1], vocab=vocab, seg_len=seg_len,
                          max_freq=max_freq, rng=rng_for(
                              seed if token_seed is None else token_seed, 12))
    dest = ids if relabel is None else np.asarray(relabel)[ids - 1]
    tokens[dest], freq[dest], lengths[dest] = t, f, ln
    return Corpus(tokens, freq, lengths)


def make_histories(seed: int, *, n_news: int, n_topics: int, zipf_a: float,
                   n_users: int, median_clicks: float, clicks_sigma: float,
                   min_clicks: int, max_clicks: int, topic_affinity: float,
                   stream: int = 21) -> list:
    """Click histories (1-based news ids, no repeats within a user): each
    click picks a topic by the user's preference weighted by the topic's
    popularity mass, then a news of that topic by popularity -- the
    factorisation of news_synth's p(news) ~ pop * topic_weight."""
    rng = rng_for(seed, 11)
    topics = rng.integers(0, n_topics, n_news)
    ranks = rng.permutation(n_news) + 1
    pop = ranks.astype(np.float64) ** (-zipf_a)
    pop /= pop.sum()
    rng = rng_for(seed, stream)
    n = np.clip(quantiles(_lognormal_ppf(np.log(median_clicks), clicks_sigma),
                          n_users, rng), min_clicks, max_clicks).astype(int)
    n_pref = rng.integers(1, 4, n_users)
    rank = np.argsort(np.argsort(rng.random((n_users, n_topics)), axis=1),
                      axis=1)
    pref = rank < n_pref[:, None]                 # 1-3 random topics each
    tw = (1 - topic_affinity) / n_topics + np.where(
        pref, topic_affinity / n_pref[:, None], 0.0)
    mass = np.bincount(topics, weights=pop, minlength=n_topics)
    w = tw * mass[None, :]
    cum = np.cumsum(w / w.sum(1, keepdims=True), axis=1)
    draws = 4 * n                                   # room for repeats
    user = np.repeat(np.arange(n_users), draws)
    t = (rng.random(user.size)[:, None] > cum[user]).sum(1)
    t = np.minimum(t, n_topics - 1)
    by_topic = [np.flatnonzero(topics == k) for k in range(n_topics)]
    cdfs = [np.cumsum(pop[ix]) / pop[ix].sum() for ix in by_topic]
    news = np.empty(user.size, np.int64)
    u = rng.random(user.size)
    for k in range(n_topics):
        sel = t == k
        j = np.searchsorted(cdfs[k], u[sel], side="right")
        news[sel] = by_topic[k][np.minimum(j, len(by_topic[k]) - 1)]
    news += 1
    out = []
    start = np.concatenate([[0], np.cumsum(draws)])
    for i in range(n_users):
        seq = news[start[i]:start[i + 1]]
        _, first = np.unique(seq, return_index=True)
        out.append(seq[np.sort(first)][:n[i]])
    return out
