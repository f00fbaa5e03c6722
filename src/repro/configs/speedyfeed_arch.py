"""SpeedyFeed — the paper's own architecture as a first-class config
(arch #11, beyond the 10 assigned ones).

Production config: UniLMv2-base-scale PLM (12L x 768 x 12H), K=3 segments of
32 tokens (title/abstract/body after OBoW refinement, §A.2), user history
L=100, news universe 1.2M (Table 2), cache gamma=20 / beta=2e-3 (§A.3).

Cells:
  train_prod          Algorithm-1 step (centralized + cache + BusLM + AR loss)
  train_conventional  the typical-workflow baseline (per-instance encoding) —
                      the denominator of the paper's 100x claim
  encode_bulk         offline bulk news encoding (index build / serving)
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import core, optim, training
from repro.distributed import sharding as shx
from repro.nn.moe import RoutedMoEConfig
from repro.optim.adam import adam_update
from .base import (Arch, Cell, F32, I32, abstract_opt, abstract_params,
                   assert_finite, batch_sds, data_axes, opt_spec_tree, sds,
                   shard_abstract)

# paper §A.3: lr 8e-6 for the PLM, 1e-4 for everything else
SF_OPT = optim.AdamConfig(lr=1e-4, grad_clip=1.0,
                          group_lr_scales=(("plm", 0.08),))

PROD = core.make_config(
    vocab=30720,   # UniLM's 30 522 padded to /512 for vocab sharding
    n_layers=12, d_model=768, n_heads=12, d_ff=3072,
    n_segments=3, seg_len=32, news_dim=768,
    n_news=1_204_224,   # Table 2's 1 202 576 row-padded to /4096 (sharding)
    gamma=20, beta=2e-3, encode_budget=4096,
    batch_users=1024, hist_len=100, merged_cap=8192, n_neg=4, remat=True)

# One chip's data-parallel share of PROD: PROD's global batch spread over
# a deployment of 8 chips (8 x v5e, each holding the replicated model and
# the whole cache).  Every width, the vocab, K, S, L, news_dim and the
# 1,204,224 cache rows stay as published; only the three per-step batch
# sizes are cut, all by the same factor 8 (reduced: encode_budget 4096 ->
# 512, batch_users 1024 -> 128, merged_cap 8192 -> 1024).  Why 8: PROD's
# step needs 33.08 GB on one 15.75 GB v5e; compiled for a v5e with the
# Pallas bus kernel, PROD/4 needs 5.04 GB arguments + 14.22 GB temps
# (over the 85% bar of 13.39 GB) and PROD/8 needs 5.04 + 6.93 = 11.97 GB.
# The arguments are the 3.70 GB fp32 cache plus 1.3 GB of params and Adam
# moments, so no batch share can go below that.
PROD_SHARE = 8
PROD_1CHIP = dataclasses.replace(
    PROD, batch_users=PROD.batch_users // PROD_SHARE,
    merged_cap=PROD.merged_cap // PROD_SHARE,
    cache=dataclasses.replace(
        PROD.cache, encode_budget=PROD.cache.encode_budget // PROD_SHARE))

# Moonlight-16B-A3B (huggingface.co/moonshotai/Moonlight-16B-A3B,
# config.json, model_type deepseek_v3) as the news encoder, trained by the
# same Trainer, cache and encode set as PROD_1CHIP, as one chip of a
# deployment in which 8 chips share each layer: this chip holds 8 of the
# 64 routed experts (the router still scores all 64, top-6) and an eighth
# of the 163,840-row vocabulary; the attention, router and the 2 shared
# experts are replicated; the layers left out lie on further pipeline
# stages.  Every width is published: hidden 2,048, 16 heads, q/k head 192
# (128 + rope 64), v head 128, latent 512, dense SwiGLU 11,264, experts
# 1,408.  Cut: 27 layers -> 5 (the leading dense one and 4 expert
# layers), and PROD_1CHIP's per-chip batch share.  news_dim stays 768, so
# the cache, user model and loss are PROD_1CHIP's.
MOONLIGHT_SHARE = 8
MOONLIGHT_1CHIP = dataclasses.replace(
    PROD_1CHIP, plm=dataclasses.replace(
        PROD_1CHIP.plm, vocab=163_840 // MOONLIGHT_SHARE, n_layers=5,
        d_model=2048, n_heads=16, d_ff=11_264, use_freq_embedding=False,
        decoder=core.DecoderConfig(
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, rope_theta=50_000.0, rms_eps=1e-5, n_dense=1,
            moe=RoutedMoEConfig(
                d_model=2048, d_expert=1408, n_experts=64, top_k=6,
                n_held=64 // MOONLIGHT_SHARE, n_shared=2,
                routed_scale=2.446))))

CONV_BATCH = dict(users=512, hist=100, cands=2)  # conventional baseline


def make_sf_train_step(cfg: core.SpeedyFeedConfig):
    def loss_fn(params, batch, cache, step, rng):
        out = core.speedyfeed_forward(params, cfg, batch, cache, step, rng)
        return out.loss, (out.cache, out.metrics)

    gfn = jax.value_and_grad(loss_fn, has_aux=True)

    def step_fn(params, opt_state, cache, step, rng, batch):
        (loss, (new_cache, metrics)), grads = gfn(params, batch, cache,
                                                  step, rng)
        with jax.named_scope("update"):     # clipping + Adam
            params, opt_state, om = adam_update(params, grads, opt_state,
                                                SF_OPT)
        metrics = dict(metrics)
        metrics.update(om)
        metrics["loss"] = loss
        return params, opt_state, new_cache, metrics

    return step_fn


def make_conventional_step(cfg: core.SpeedyFeedConfig):
    def loss_fn(params, batch):
        return core.conventional_forward(params, cfg, batch)

    return optim.make_train_step(loss_fn, SF_OPT)


# ---------------------------------------------------------------------------
# training-runtime integration (repro.training)
# ---------------------------------------------------------------------------

def _sf_init_state(cfg, key) -> training.TrainState:
    params, cache = core.speedyfeed_state(cfg, key)
    return training.make_state(params, optim.adam_init(params), cache,
                               rng=key)


@training.register_trainer("speedyfeed")
def make_sf_trainer(cfg=None, **kw) -> training.Trainer:
    """Bucket-aware donated Trainer for Algorithm 1 (the registry entry the
    launchers use; PROD config unless overridden)."""
    # mesh runs place the merged news set replicated (it feeds a global
    # argsort) and shard the user axis — the H1 layout, not generic dim-0
    kw.setdefault("batch_specs_fn", shx.speedyfeed_batch_specs)
    return training.Trainer(cfg if cfg is not None else PROD,
                            make_step=make_sf_train_step,
                            init_fn=_sf_init_state, **kw)


def _make_conventional_state_step(cfg):
    """Adapt the conventional baseline to the TrainState step contract
    (cache travels untouched; the baseline re-encodes everything)."""
    raw = make_conventional_step(cfg)

    def step_fn(params, opt_state, cache, step, rng, batch):
        params, opt_state, metrics = raw(params, opt_state, batch)
        return params, opt_state, cache, metrics

    return step_fn


@training.register_trainer("speedyfeed_conventional")
def make_conventional_trainer(cfg=None, **kw) -> training.Trainer:
    return training.Trainer(cfg if cfg is not None else PROD,
                            make_step=_make_conventional_state_step,
                            init_fn=_sf_init_state, **kw)


def _sf_params_abs(cfg, mesh):
    # bf16 params/activations for the production dry-run (H1-4a): halves
    # the scan save/restore and matmul traffic; Adam m/v stay fp32.
    pa = abstract_params(
        lambda k: core.init_speedyfeed(k, cfg, param_dtype=jnp.bfloat16))
    if mesh is None:
        return pa, None
    specs = shx.spec_tree(pa, shx.speedyfeed_rules())
    return shard_abstract(pa, specs, mesh), specs


def _zero1_spec(leaf, n_ways: int = 16):
    """ZeRO-1: shard optimizer moments on the first dim divisible by the
    data axis; the weight update then runs 1/16th per chip and params are
    re-gathered by the replicated out_sharding (H1-4b)."""
    for i, d in enumerate(leaf.shape):
        if d % n_ways == 0:
            return P(*([None] * i + ["data"] + [None] * (leaf.ndim - i - 1)))
    return P()


def _cache_abs(cfg, mesh):
    ca = jax.eval_shape(lambda: core.init_cache(cfg.cache))
    if mesh is None:
        return ca
    spec = core.CacheState(emb=P(data_axes(mesh), None),
                           written_step=P(data_axes(mesh)))
    return shard_abstract(ca, spec, mesh)


def _train_batch_abs(cfg, mesh):
    M, K, S = cfg.merged_cap, cfg.plm.n_segments, cfg.plm.seg_len
    B, L = cfg.batch_users, cfg.hist_len
    shapes = {
        "news_tokens": ((M, K, S), I32),
        "news_freq": ((M, K, S), I32),
        "news_ids": ((M,), I32),
        "hist_inv": ((B, L), I32),
        "hist_mask": ((B, L), jnp.bool_),
    }
    out = batch_sds(mesh, shapes)
    if mesh is not None:   # merged set replicated (it feeds a global argsort)
        for k in ("news_tokens", "news_freq", "news_ids"):
            sh = shapes[k][0]
            out[k] = sds(sh, shapes[k][1], mesh, P(*([None] * len(sh))))
        # user/loss side also shards over every axis (H1-3): B=1024 user
        # rows over 256/512 chips, matching the pure-DP encoder layout
        all_ax = tuple(mesh.axis_names)
        out["hist_inv"] = sds(shapes["hist_inv"][0], I32, mesh,
                              P(all_ax, None))
        out["hist_mask"] = sds(shapes["hist_mask"][0], jnp.bool_, mesh,
                               P(all_ax, None))
    return out


def _conv_batch_abs(cfg, mesh):
    K, S = cfg.plm.n_segments, cfg.plm.seg_len
    B, L, C = CONV_BATCH["users"], CONV_BATCH["hist"], CONV_BATCH["cands"]
    shapes = {
        "hist_tokens": ((B, L, K, S), I32),
        "hist_freq": ((B, L, K, S), I32),
        "hist_mask": ((B, L), jnp.bool_),
        "cand_tokens": ((B, C, K, S), I32),
        "cand_freq": ((B, C, K, S), I32),
        "label": ((B,), I32),
        "cand_mask": ((B, C), jnp.bool_),
    }
    if mesh is None:
        return batch_sds(mesh, shapes)
    # pure-DP PLM: the instance batch shards over EVERY mesh axis
    ax = tuple(mesh.axis_names)
    return {k: sds(sh, dt, mesh, P(*([ax] + [None] * (len(sh) - 1))))
            for k, (sh, dt) in shapes.items()}


def _act_specs(mesh, kind):
    if mesh is None:
        return {}
    # pure-DP PLM: the encode set shards over EVERY mesh axis (H1-2)
    return {"encode_batch": P(tuple(mesh.axis_names), None, None)}


def _arch() -> Arch:
    cfg = PROD
    cells = {}

    def train_make(mesh):
        # the cell lowers the Trainer's own state step, so the dry-run
        # compiles exactly the executable the training runtime runs
        return make_sf_trainer(cfg).state_step

    def train_args(mesh):
        pa, specs = _sf_params_abs(cfg, mesh)
        oa = abstract_opt(pa)
        if mesh is not None:
            mspec = jax.tree.map(_zero1_spec, oa["m"],
                                 is_leaf=lambda x: hasattr(x, "shape"))
            oa = shard_abstract(
                oa, {"m": mspec, "v": mspec, "count": P()}, mesh)
        ca = _cache_abs(cfg, mesh)
        step = sds((), I32, mesh, P())
        rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        if mesh is not None:
            rng = shard_abstract(rng, P(None), mesh)
        state_abs = training.TrainState(pa, oa, ca, step, rng)
        return (state_abs, _train_batch_abs(cfg, mesh))

    enc_flops = core.plm_flops(cfg.plm, cfg.cache.encode_budget)
    cells["train_prod"] = Cell(
        arch="speedyfeed", shape="train_prod", kind="train",
        make_fn=train_make, abstract_args=train_args,
        activation_specs=functools.partial(_act_specs, kind="train"),
        meta={"model_flops": 3 * enc_flops, "donate_argnums": (0,)})

    def conv_make(mesh):
        return make_conventional_trainer(cfg).state_step

    def conv_args(mesh):
        pa, specs = _sf_params_abs(cfg, mesh)
        oa = abstract_opt(pa)
        if mesh is not None:
            oa = shard_abstract(oa, opt_spec_tree(specs), mesh)
        ca = _cache_abs(cfg, mesh)
        step = sds((), I32, mesh, P())
        rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        if mesh is not None:
            rng = shard_abstract(rng, P(None), mesh)
        state_abs = training.TrainState(pa, oa, ca, step, rng)
        return (state_abs, _conv_batch_abs(cfg, mesh))

    n_conv = CONV_BATCH["users"] * (CONV_BATCH["hist"] + CONV_BATCH["cands"])
    cells["train_conventional"] = Cell(
        arch="speedyfeed", shape="train_conventional", kind="train",
        make_fn=conv_make, abstract_args=conv_args,
        activation_specs=functools.partial(_act_specs, kind="train"),
        meta={"model_flops": 3 * core.plm_flops(cfg.plm, n_conv),
              "donate_argnums": (0,)})

    def enc_make(mesh):
        return lambda p, t, f: core.buslm_encode(p["plm"], cfg.plm, t, f)

    def enc_args(mesh, M=65536):
        pa, _ = _sf_params_abs(cfg, mesh)
        K, S = cfg.plm.n_segments, cfg.plm.seg_len
        if mesh is None:
            b = batch_sds(mesh, {"t": ((M, K, S), I32),
                                 "f": ((M, K, S), I32)})
            return (pa, b["t"], b["f"])
        ax = tuple(mesh.axis_names)   # bulk encode = DP over every axis
        return (pa, sds((M, K, S), I32, mesh, P(ax, None, None)),
                sds((M, K, S), I32, mesh, P(ax, None, None)))

    cells["encode_bulk"] = Cell(
        arch="speedyfeed", shape="encode_bulk", kind="serve",
        make_fn=enc_make, abstract_args=enc_args,
        meta={"model_flops": core.plm_flops(cfg.plm, 65536)})

    return Arch(name="speedyfeed", family="news", config=cfg, cells=cells,
                smoke=_smoke, notes="the paper's own architecture")


def _smoke():
    cfg = core.make_config(vocab=500, n_layers=2, d_model=32, n_heads=4,
                           d_ff=64, n_segments=3, seg_len=8, news_dim=16,
                           n_news=300, encode_budget=16, batch_users=4,
                           hist_len=12, merged_cap=48, n_neg=3)
    key = jax.random.PRNGKey(0)
    params, cache = core.speedyfeed_state(cfg, key)
    opt = optim.adam_init(params)
    step = jax.jit(make_sf_train_step(cfg))
    ks = jax.random.split(key, 8)
    M, K, S = cfg.merged_cap, 3, 8
    batch = {
        "news_tokens": jax.random.randint(ks[0], (M, K, S), 1, 500),
        "news_freq": jax.random.randint(ks[1], (M, K, S), 0, 8),
        "news_ids": jnp.concatenate([jnp.zeros(1, jnp.int32),
                                     jnp.arange(1, M, dtype=jnp.int32)]),
        "hist_inv": jax.random.randint(ks[2], (4, 12), 1, M),
        "hist_mask": jnp.ones((4, 12), bool),
    }
    losses = []
    for i in range(3):
        params, opt, cache, metrics = step(params, opt, cache,
                                           jnp.int32(i), ks[3 + i], batch)
        losses.append(float(metrics["loss"]))
    assert_finite(jnp.asarray(losses), "speedyfeed losses")
    return {"losses": losses,
            "reused_final": float(metrics["reused"])}


def archs():
    return [_arch()]
