"""Moonlight-16B-A3B (a DeepSeek-V3 stack) as SpeedyFeed's news encoder,
against the plain float32 reference ``bench/reference/moonlight.py`` at a
small size on the CPU, on seeded random weights: the encoder and its
gradients, the encode set's written-out backward pass, one training
step, the expert layer's shares, dropless routing, the causal latent bus
kernel, and the BusLM path beside it."""
import copy
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.reference import moonlight as ref  # noqa: E402
from bench.reference import speedyfeed as sf_ref  # noqa: E402
from repro import core  # noqa: E402
from repro.kernels import bus_attention as bus_raw  # noqa: E402
from repro.nn import moe  # noqa: E402

# every width cut, every mechanism kept: 1 dense + 2 expert layers, latent
# attention with a rope part, 16 routed experts of which 4 are held here
# (ids 4-7), top-3, 2 shared experts
Z = ref.Sizes(vocab=500, d_model=64, n_heads=4, n_layers=3, n_dense=1,
              d_ff=96, kv_lora_rank=32, qk_nope_head_dim=16,
              qk_rope_head_dim=8, v_head_dim=16, rope_theta=50_000.0,
              rms_eps=1e-5, n_experts=16, top_k=3, n_held=4, d_expert=32,
              n_shared=2, routed_scale=2.446, news_dim=24, held_start=4,
              block_rows=8)
K, S = 3, 8
# float32 on the CPU: the program matches the reference to round-off
# (1e-4 leaves room for the order of sums); the kernels' path takes its
# expert matmuls in one bf16 pass (the configuration's DEFAULT
# precision), 3 significant digits, so 2e-2 there
TOL = {"xla": 1e-4, "pallas": 2e-2}


def moe_cfg(**over):
    kw = dict(d_model=Z.d_model, d_expert=Z.d_expert, n_experts=Z.n_experts,
              top_k=Z.top_k, n_held=Z.n_held, held_start=Z.held_start,
              n_shared=Z.n_shared, routed_scale=Z.routed_scale)
    kw.update(over)
    return moe.RoutedMoEConfig(**kw)


def program_cfg(impl="xla", **over):
    dec = core.DecoderConfig(
        kv_lora_rank=Z.kv_lora_rank, qk_nope_head_dim=Z.qk_nope_head_dim,
        qk_rope_head_dim=Z.qk_rope_head_dim, v_head_dim=Z.v_head_dim,
        rope_theta=Z.rope_theta, rms_eps=Z.rms_eps, n_dense=Z.n_dense,
        moe=moe_cfg())
    kw = dict(vocab=Z.vocab, n_layers=Z.n_layers, d_model=Z.d_model,
              n_heads=Z.n_heads, d_ff=Z.d_ff, n_segments=K, seg_len=S,
              news_dim=Z.news_dim, n_news=400, encode_budget=32,
              batch_users=4, hist_len=8, merged_cap=48, n_neg=3,
              use_freq=False, decoder=dec, remat=True, attn_impl=impl)
    kw.update(over)
    return core.make_config(**kw)


def tokens(n, seed=0):
    """[n, K, S] left-aligned segments of random lengths (some empty, the
    first never)."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, S + 1, (n, K))
    lens[:, 0] = np.maximum(lens[:, 0], 1)
    t = rng.integers(1, Z.vocab, (n, K, S))
    return jnp.asarray(np.where(np.arange(S) < lens[..., None], t, 0),
                       jnp.int32)


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def worst(ga, gb):
    return max(jax.tree.leaves(jax.tree.map(rel, ga, gb)))


@pytest.fixture(scope="module")
def params():
    return ref.init_params(3, Z)


def test_program_takes_the_reference_layout(params):
    prog = jax.eval_shape(lambda: core.init_speedyfeed(
        jax.random.PRNGKey(0), program_cfg()))
    assert jax.tree.structure(prog) == jax.tree.structure(params)
    assert jax.tree.map(lambda x: x.shape, prog) == jax.tree.map(
        lambda x: x.shape, params)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_encoder_and_gradients_match_reference(params, impl):
    cfg = program_cfg(impl).plm
    t = tokens(16)
    w = jax.random.normal(jax.random.PRNGKey(1), (16, Z.news_dim))
    with jax.default_matmul_precision("highest"):
        (e, c), g = jax.value_and_grad(
            lambda p: ((lambda o: ((o[0] * w).sum(), o))(
                core.decoder_encode(p, cfg, t, impl=impl))), has_aux=True)(
            params["plm"])
        (er, rr), gr = jax.value_and_grad(
            lambda p: ((lambda o: ((o[0] * w).sum(), o))(
                ref.encode(p, t, z=Z))), has_aux=True)(params["plm"])
    assert rel(c[0], rr[0]) < TOL[impl]
    assert int(c[1]["routed"]) == int(rr[1])
    assert int(c[1]["expert_rows"].sum()) == int(rr[1])
    assert worst(g, gr) < 5 * TOL[impl]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_encode_set_backward_matches_autodiff(params, impl):
    """The encode set's written-out backward pass (chunks of 8 rows, the
    chunks past the 13 rows that need encoding skipped, the rows past them
    blanked) against autodiff of the same encoding in one piece."""
    cfg = program_cfg(impl).plm
    E, n = 32, jnp.int32(13)
    t = tokens(E, seed=1)
    w = jax.random.normal(jax.random.PRNGKey(2), (E, Z.news_dim))

    def chunked(p):
        e, cnt = core.decoder_encode_set(p, cfg, t, n, 8)
        return (e * w).sum(), cnt

    def whole(p):
        live = (jnp.arange(E) < n)[:, None, None]
        e, cnt = core.decoder_encode(p, cfg, jnp.where(live, t, 0), impl)
        return (jnp.where((jnp.arange(E) < 16)[:, None], e, 0) * w).sum(), cnt

    with jax.default_matmul_precision("highest"):
        (v1, c1), g1 = jax.value_and_grad(chunked, has_aux=True)(
            params["plm"])
        (v2, c2), g2 = jax.value_and_grad(whole, has_aux=True)(params["plm"])
    assert abs(float(v1 - v2)) <= 1e-5 * abs(float(v2))
    assert int(c1["routed"]) == int(c2["routed"]) > 0
    # the kernels' bf16 operands round the two groupings' rows apart
    assert worst(g1, g2) < (1e-5 if impl == "xla" else 1e-3)


def test_train_step_matches_reference(params):
    """One Algorithm-1 step through the Trainer's step, from the same
    weights, cache and key as the reference: the loss, the rows routed to
    the held experts and every parameter after Adam."""
    import repro.configs.speedyfeed_arch as arch
    from repro import optim, training
    cfg = program_cfg()
    rng = np.random.default_rng(4)
    M, B, L = cfg.merged_cap, cfg.batch_users, cfg.hist_len
    ids = np.zeros(M, np.int32)
    ids[1:41] = np.sort(rng.choice(np.arange(1, 400), 40, replace=False))
    batch = {"news_tokens": tokens(M, seed=5),
             "news_freq": jnp.zeros((M, K, S), jnp.int32),
             "news_ids": jnp.asarray(ids),
             "hist_inv": jnp.asarray(rng.integers(1, 41, (B, L)), jnp.int32),
             "hist_mask": jnp.asarray(np.arange(L) < rng.integers(
                 2, L + 1, (B, 1)))}
    trainer = arch.make_sf_trainer(cfg, donate=False)
    cache = core.init_cache(cfg.cache)
    step = jnp.int32(7)
    key = jax.random.PRNGKey(9)
    state = training.make_state(params, optim.adam_init(params), cache,
                                step=7, rng=key)
    with jax.default_matmul_precision("highest"):
        new, m = jax.jit(trainer.state_step)(state, batch)
        spec = sf_ref.StepSpec(
            n_heads=Z.n_heads, max_freq=32, gamma=cfg.cache.gamma,
            beta=cfg.cache.beta, encode_budget=cfg.cache.encode_budget,
            n_neg=cfg.n_neg, lr=1e-4, plm_lr_scale=0.08, grad_clip=1.0,
            b1=0.9, b2=0.999, eps=1e-8)
        def fresh(t):        # the reference step donates its state
            return jax.tree.map(jnp.array, t)

        zeros = jax.tree.map(jnp.zeros_like, params)
        out = ref.train_step(
            fresh(params), fresh(zeros), fresh(zeros), jnp.int32(0),
            fresh(cache.emb), fresh(cache.written_step), {k: batch[k] for k in
                                 ("news_ids", "hist_inv", "hist_mask")},
            batch["news_tokens"], step, jax.random.fold_in(key, step),
            spec=spec, z=Z, nx=ref.F32)
    p_ref, loss_ref, routed_ref = out[0], out[6], out[8]
    assert abs(float(m["loss"]) - float(loss_ref)) < 1e-5 * abs(
        float(loss_ref))
    assert int(m["moe_routed"]) == int(routed_ref) > 0
    change = jax.tree.map(lambda a, b: a - b, new.params, params)
    change_ref = jax.tree.map(lambda a, b: a - b, p_ref, params)
    assert worst(change, change_ref) < 1e-3


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_expert_shares_add_up_to_the_uncut_layer(impl):
    """Eight shares of the layer's 16 experts (2 held each, as 8 chips of a
    deployment hold them), each computing its own experts' part, with the
    shared experts counted once, add up to the uncut reference layer
    (every expert held)."""
    n_share, top_k = 8, 6
    full = moe_cfg(n_held=16, held_start=0, top_k=top_k)
    p = moe.init_routed_moe(jax.random.PRNGKey(5), full)
    T = 256
    x = jax.random.normal(jax.random.PRNGKey(6), (T, Z.d_model))
    valid = jax.random.uniform(jax.random.PRNGKey(7), (T,)) < 0.7
    zr = ref.Sizes(**{**Z.__dict__, "n_held": 16, "held_start": 0,
                      "top_k": top_k})
    with jax.default_matmul_precision("highest"):
        want, routed = ref._moe(p, x, valid, zr, ref.F32)
        shared = moe.swiglu(p["shared"]["w1"], p["shared"]["w3"],
                            p["shared"]["w2"], x)
        parts, rows = [], 0
        for i in range(n_share):
            cfg = moe_cfg(n_held=2, held_start=2 * i, top_k=top_k)
            mine = dict(p, **{k: p[k][2 * i:2 * i + 2]
                              for k in ("w1", "w2", "w3")})
            y, c = moe.routed_moe(mine, x, valid, cfg, impl=impl)
            parts.append(y - shared)
            rows += int(c["routed"])
    got = sum(parts) + shared
    assert rel(got, want) < TOL[impl]
    assert rows == int(routed) == int(valid.sum()) * top_k


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_routing_is_dropless_when_every_token_picks_the_same_experts(impl):
    """A correction bias that makes every token choose the same 3 experts,
    all held here: every real token's 3 rows run, none is dropped, and the
    output is the reference's."""
    cfg = moe_cfg()
    p = moe.init_routed_moe(jax.random.PRNGKey(8), cfg)
    p["router_bias"] = jnp.zeros(Z.n_experts).at[
        Z.held_start:Z.held_start + 3].set(100.0)
    T = 384
    x = jax.random.normal(jax.random.PRNGKey(9), (T, Z.d_model))
    valid = jnp.arange(T) % 5 != 0
    with jax.default_matmul_precision("highest"):
        y, c = moe.routed_moe(p, x, valid, cfg, impl=impl)
        want, routed = ref._moe(p, x, valid, Z, ref.F32)
    n_real = int(valid.sum())
    assert int(c["routed"]) == int(routed) == 3 * n_real
    assert c["expert_rows"].tolist() == [n_real] * 3 + [0]
    assert rel(y, want) < TOL[impl]


def _plain_bus(q, k, v, mask):
    """Attention of q [M,K,S,H,D] over k/v [M,K,S+K,H,D|Dv] under kv_mask
    [M,K,S+K], each segment's own keys causally."""
    S, Sk = q.shape[2], k.shape[2]
    col = jnp.arange(Sk)[None, :]
    seen = mask[:, :, None, :] & ((col >= S) | (col <= jnp.arange(S)[:, None]))
    s = jnp.einsum("mkshd,mkthd->mkhst", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(seen[:, :, None], s, -1e30)
    return jnp.einsum("mkhst,mkthd->mkshd", jax.nn.softmax(s, -1), v)


def test_causal_latent_bus_kernel_matches_plain_attention():
    """The bus kernel at query/key head dim 24 and value head dim 16 with
    the in-segment causal mask, forward and backward (interpret mode),
    against plain attention."""
    M, H, D, Dv = 8, 2, 24, 16
    ks = jax.random.split(jax.random.PRNGKey(10), 5)
    q = jax.random.normal(ks[0], (M, K, S, H, D))
    k = jax.random.normal(ks[1], (M, K, S + K, H, D))
    v = jax.random.normal(ks[2], (M, K, S + K, H, Dv))
    mask = jax.random.uniform(ks[3], (M, K, S + K)) < 0.8
    do = jax.random.normal(ks[4], (M, K, S, H, Dv))
    with jax.default_matmul_precision("highest"):
        o = bus_raw.bus_attention(q, k, v, mask, interpret=True, causal=True)
        want, vjp = jax.vjp(lambda *a: _plain_bus(*a, mask), q, k, v)
        grads = bus_raw.bus_attention_bwd(q, k, v, mask, do, interpret=True,
                                          causal=True)
        want_grads = vjp(do)
    assert o.shape == (M, K, S, H, Dv)
    assert rel(o, want) < 1e-5
    for g, w in zip(grads, want_grads):
        assert rel(g, w) < 1e-5


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_buslm_path_unchanged(impl):
    """A PROD-shaped BusLM encoder (K=3 x S=32, widths cut) beside the
    decoder stack in the same entry gives what the BusLM reference
    gives."""
    plm = {"vocab": 500, "n_layers": 2, "d_model": 64, "n_heads": 4,
           "d_ff": 128, "n_segments": 3, "seg_len": 32, "news_dim": 32,
           "max_len": 512, "max_freq": 32}
    cfg = core.make_config(**{k: plm[k] for k in (
        "vocab", "n_layers", "d_model", "n_heads", "d_ff", "n_segments",
        "seg_len", "news_dim")}, attn_impl=impl).plm
    p = sf_ref.init_params(2, plm)["plm"]
    rng = np.random.default_rng(3)
    t = jnp.asarray(np.where(np.arange(32) < rng.integers(
        1, 33, (8, 3, 1)), rng.integers(1, 500, (8, 3, 32)), 0), jnp.int32)
    f = jnp.asarray(rng.integers(0, 32, (8, 3, 32)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        e = core.buslm_encode(p, cfg, t, f, impl=impl)
        want = sf_ref.encode(p, t, f, n_heads=4, max_freq=32)
    assert rel(e, want) < 1e-4


def test_configuration_file_is_the_preset():
    """What the benchmark runs (bench/configs/moonlight_1chip.json through
    its driver) is the program's MOONLIGHT_1CHIP preset."""
    import repro.configs.speedyfeed_arch as arch
    from bench.drivers import train_moe
    c = json.loads((ROOT / "bench" / "configs"
                    / "moonlight_1chip.json").read_text())
    assert train_moe.program_config(c) == arch.MOONLIGHT_1CHIP
    assert ref.sizes(c).n_experts == 64 and ref.sizes(c).n_held == 8


def _small_cell():
    c = json.loads((ROOT / "bench" / "configs"
                    / "moonlight_1chip.json").read_text())
    c.update(hidden_size=Z.d_model, num_attention_heads=Z.n_heads,
             num_hidden_layers=Z.n_layers, intermediate_size=Z.d_ff,
             kv_lora_rank=Z.kv_lora_rank, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, router_experts=16,
             n_routed_experts=4, num_experts_per_tok=3,
             moe_intermediate_size=32, batch_users=8, merged_cap=128,
             hist_len=20)
    c["plm"] = {"vocab": 500, "n_heads": Z.n_heads, "n_segments": 3,
                "seg_len": 16, "news_dim": 32, "max_freq": 32}
    c["cache"] = {"n_news": 4000, "gamma": 20, "beta": 0.002,
                  "encode_budget": 48}
    cell = json.loads((ROOT / "bench" / "cells"
                       / "train.moonlight_1chip.mind.json").read_text())
    cell["traffic"].update(n_users=512, buckets=[12, 16], token_budget=1500,
                           warm_steps=6, check_users=32, window_steps=4)
    return c, cell


def _run_small(config, cell):
    from bench import run as harness
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = {"name": "train.moonlight_1chip.mind", "config": cell["config"],
          "chips": 1}
    return harness.run_cell(b, wl, cell, copy.deepcopy(config), seed=5,
                            seconds=2.0, trace=False, devices=jax.devices())


def test_small_cell_is_correct_and_a_capacity_fault_is_not():
    """The new cell's whole run at a small size on the CPU (the look for
    chips skipped): sound, it is ``correct`` with a window of exactly
    ``window_steps`` steps; with a capacity-bound expert layer planted in
    the program, ``routed_gap`` fails."""
    from bench.tools import limits_moe
    config, cell = _small_cell()
    out = _run_small(config, cell)
    assert out["correct"], out["compared"]
    assert out["attempted"] == 4
    assert out["compared"]["routed_gap"]["value"] == 0.0
    with limits_moe.capacity_dropping():
        bad = _run_small(config, cell)
    assert not bad["correct"]
    gap = bad["compared"]["routed_gap"]
    assert gap["value"] > gap["limit"]
