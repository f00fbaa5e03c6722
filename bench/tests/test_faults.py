"""Whole runs of each cell at a small size on the CPU (the look for chips
skipped), first sound, then with the timed path broken underneath: each
fault the cell can have must turn ``correct`` false.  On one chip there is
no exchange between chips to leave out."""
import numpy as np
import pytest

from bench.tests import small

TRAIN = "train.sf_prod_1chip.mind"
ENCODE = "encode.sf_prod_serve.bulk"
SEED = 5


@pytest.mark.parametrize("name", [TRAIN, ENCODE])
def test_sound_run_is_correct(name):
    out = small.run_small(name)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0


def _train_fault(monkeypatch, kind):
    from repro import training
    from repro.core import pipeline
    if kind == "state_unchanged":
        orig = training.Trainer._state_step

        def step(self, state, batch):
            _, metrics = orig(self, state, batch)
            return state._replace(step=state.step + 1), metrics
        monkeypatch.setattr(training.Trainer, "_state_step", step)
    elif kind == "half_batch":
        orig = pipeline.ar_loss

        def loss(mu, theta, mask, *a, **kw):
            keep = (np.arange(mask.shape[0]) % 2 == 0)[:, None]
            return orig(mu, theta, mask & keep, *a, **kw)
        monkeypatch.setattr(pipeline, "ar_loss", loss)
    elif kind == "token_altered":
        from repro.data import batching
        orig = batching.build_centralized_batch

        def build(*a, **kw):
            b = orig(*a, **kw)
            t = b["news_tokens"]
            b["news_tokens"] = np.where(t > 1, (t + 1) % 500, t)
            return b
        monkeypatch.setattr(batching, "build_centralized_batch", build)
    elif kind == "merge_altered":
        from repro.data import batching
        orig = batching.build_centralized_batch

        def build(*a, **kw):
            b = orig(*a, **kw)
            inv = b["hist_inv"]
            b["hist_inv"] = np.where(inv > 1, inv - 1, inv)
            return b
        monkeypatch.setattr(batching, "build_centralized_batch", build)


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "token_altered", "merge_altered"])
def test_train_fault_is_caught(monkeypatch, kind):
    _train_fault(monkeypatch, kind)
    out = small.run_small(TRAIN)
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("kind", ["half_batch", "answer_altered",
                                  "bf16_inside"])
def test_encode_fault_is_caught(monkeypatch, kind):
    from repro.launch import serve
    orig = serve.Recommender._encode_corpus

    def encode(self, *, chunk=256):
        emb = orig(self, chunk=chunk)
        if kind == "half_batch":          # every other row never encoded
            emb[1::2] = 0.0
        elif kind == "answer_altered":    # one row answered for another
            emb[1:] = np.roll(emb[1:], 1, axis=0)
        else:    # bfloat16 throughout, behind a last float32 projection
            emb = _bf16_inside(self, emb)
        return emb
    monkeypatch.setattr(serve.Recommender, "_encode_corpus", encode)
    out = small.run_small(ENCODE, seed=SEED)
    assert not out["correct"], out["compared"]



def _bf16_inside(rec, emb):
    """The corpus encoded by the reference in bfloat16 with its last
    projection in float32 (the outputs' bits look like float32 ones),
    from the weights of ``run_small``'s seed."""
    from bench.drivers import encode
    from bench.reference import speedyfeed as ref
    out = emb.copy()
    out[1:] = encode.reference_embeddings(
        SEED, small.CONFIG, rec.store, np.arange(1, emb.shape[0]),
        nx=ref.BF16_F32_OUT)
    return out
