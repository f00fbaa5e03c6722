"""Training driver for an expert encoder (Moonlight-16B-A3B's DeepSeek-V3
stack): Algorithm 1 through ``Trainer.fit``, as ``bench/drivers/train.py``
runs it, with three things of its own.

- The program's configuration and the plain reference are the expert
  encoder's (``bench/reference/moonlight.py``).  The expert layers'
  router weights and correction biases are drawn from the cell's
  ``shape_seed``, the rest of the weights from the run's seed: the
  routers decide how many rows reach the experts held here, and the
  grouped matmuls' time follows that count.
- The window is a fixed count of whole steps, the cell's
  ``window_steps``, in place of a deadline.  With ``loader_threads`` 1
  the loader packs the same batches in the same order in every run (the
  batches' shape comes from the cell's ``shape_seed``), so every run's
  window holds the same users, and what is left to vary is the step
  time.  The count is chosen so that the window lasts at least the
  benchmark's ``--seconds``.
- ``correct`` compares one number more: the rows the expert layers
  routed to the experts held here over the checked steps, against the
  reference's count (``routed_gap``, relative).  A layer that drops rows,
  as a capacity-bound one does, reads low.

Everything else -- the data, the checked steps (one per length bucket),
the warm-up, the loader-row check and the reading of the loss, the first
gradient and the parameters' change -- is the BusLM driver's.
"""
from __future__ import annotations

import dataclasses
import time
import types

import numpy as np

from bench import flops
from bench.drivers import train
from bench.drivers.common import leaf_diff_norms, leaf_norms
from bench.reference import moonlight as ref

def program_config(c: dict):
    """The program's SpeedyFeedConfig for an expert-encoder configuration
    file (every size as the file states it)."""
    from repro import core
    from repro.nn.moe import RoutedMoEConfig
    p, ca = c["plm"], c["cache"]
    moe = RoutedMoEConfig(
        d_model=c["hidden_size"], d_expert=c["moe_intermediate_size"],
        n_experts=c["router_experts"], top_k=c["num_experts_per_tok"],
        n_held=c["n_routed_experts"], n_shared=c["n_shared_experts"],
        routed_scale=float(c["routed_scaling_factor"]))
    dec = core.DecoderConfig(
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]), rms_eps=float(c["rms_norm_eps"]),
        n_dense=c["first_k_dense_replace"], moe=moe)
    return core.make_config(
        vocab=p["vocab"], n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        d_ff=c["intermediate_size"], n_segments=p["n_segments"],
        seg_len=p["seg_len"], news_dim=p["news_dim"], n_news=ca["n_news"],
        gamma=ca["gamma"], beta=ca["beta"], encode_budget=ca["encode_budget"],
        batch_users=c["batch_users"], hist_len=c["hist_len"],
        merged_cap=c["merged_cap"], n_neg=c["n_neg"], use_freq=False,
        remat=c["remat"], decoder=dec)


@dataclasses.dataclass
class Readings(train.Readings):
    routed: float = 0.0     # rows routed to the held experts, checked steps


def routed_counter() -> float:
    """Rows routed to the held experts so far in this process (the
    Trainer's ``moe_window`` counts)."""
    from repro import obs
    return obs.counter("moe_window_routed_total").value


class Setup(train.Setup):
    """The BusLM driver's set-up with the expert encoder's configuration
    and weights; the checked steps' routed rows are read besides."""

    def __init__(self, seed: int, config: dict, traffic: dict, *,
                 corpus=None, hist=None):
        import jax
        import jax.numpy as jnp
        import repro.configs.speedyfeed_arch  # noqa: F401 (registers)
        from repro import core, data, optim, training
        self.seed, self.config, self.traffic = seed, config, traffic
        self.cfg = cfg = program_config(config)
        if corpus is None:
            hist, corpus = train.make_data(seed, config, traffic)
        self.hist, self.corpus = hist, corpus
        self.store = types.SimpleNamespace(
            tokens=corpus.tokens, freq=corpus.freq, lengths=corpus.lengths)
        p = config["plm"]
        self.lcfg = data.LoaderConfig(
            vocab=p["vocab"], n_segments=p["n_segments"],
            seg_len=p["seg_len"], buckets=tuple(traffic["buckets"]),
            token_budget=traffic["token_budget"], b_cap=cfg.batch_users,
            m_cap=cfg.merged_cap, hist_len=cfg.hist_len)
        self.trainer = training.get_trainer("speedyfeed", cfg=cfg)
        z = ref.sizes(config)
        n, nd = config["cache"]["n_news"], p["news_dim"]
        self.router_seed = int(traffic["shape_seed"])
        params = ref.init_params(seed, z, router_seed=self.router_seed)
        cache = core.CacheState(jnp.zeros((n, nd), jnp.float32),
                                jnp.full((n,), ref.NEVER, jnp.int32))
        self.start = int(traffic["start_step"])
        self.state = training.make_state(
            params, optim.adam_init(params), cache, step=self.start,
            rng=ref.seed_key(seed, 2))
        del params
        self._fits = 0
        self.checked, losses, routed = [], [], 0.0
        for i, group in enumerate(train.check_groups(
                hist, corpus.lengths, self.lcfg.buckets,
                int(traffic["check_users"]))):
            users = [hist[u] for u in group]
            log = []
            res = self._fit(users, log, keep=True, steps=self.start + i + 1)
            losses += list(res.losses)
            routed += self.trainer.metrics_buffer.history["moe_routed"][-1]
            if i == 0:
                grad = leaf_norms(self.state.opt["m"]) / (
                    1.0 - config["optimizer"]["b1"])
            self.checked.append(train.CheckedStep(log[0][0], log[0][2],
                                                  users))
        p0 = ref.init_params(seed, z, router_seed=self.router_seed)
        change = leaf_diff_norms(self.state.params, p0)
        del p0
        self.program_readings = Readings(np.asarray(losses), grad, change,
                                         routed=routed)
        jax.block_until_ready(self.state.step)


def reference_steps(seed: int, config: dict, corpus, steps, start: int,
                    *, router_seed: int, nx=ref.F32) -> Readings:
    """The plain reference over the checked steps, from the benchmark's
    weights (the routers' from ``router_seed``), an empty cache and the
    state's random key."""
    import jax
    import jax.numpy as jnp
    spec = train.step_spec(config)
    z = ref.sizes(config)
    n, nd = config["cache"]["n_news"], config["plm"]["news_dim"]
    params = ref.init_params(seed, z, router_seed=router_seed)
    params = jax.tree.map(lambda x: x.astype(nx.jdtype), params)
    m = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    v = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    count = jnp.int32(0)
    cache = jnp.zeros((n, nd), jnp.float32)
    written = jnp.full((n,), ref.NEVER, jnp.int32)
    key = ref.seed_key(seed, 2)
    losses, grads, unmatched, routed = [], [], 0, 0.0
    for i, checked in enumerate(steps):
        b, bad = train.reference_batch(checked, config)
        unmatched += bad
        batch = {k: jnp.asarray(x) for k, x in b.items()}
        tokens = jnp.asarray(corpus.tokens[b["news_ids"]])
        step = jnp.int32(start + i)
        rng = jax.random.fold_in(key, step)
        params, m, v, count, cache, written, loss, g, r = ref.train_step(
            params, m, v, count, cache, written, batch, tokens, step, rng,
            spec=spec, z=z, nx=nx)
        losses.append(float(loss))
        routed += float(r)
        grads.append(leaf_norms(g))
        del g
    p0 = ref.init_params(seed, z, router_seed=router_seed)
    change = leaf_diff_norms(params, p0)
    return Readings(np.asarray(losses), grads[0], change,
                    np.max(np.stack(grads), axis=0), unmatched, routed)


def gaps(got: Readings, want: Readings) -> dict:
    """The BusLM driver's gaps, and the relative gap of the rows routed to
    the held experts."""
    out = train.gaps(got, want)
    out["routed_gap"] = abs(got.routed - want.routed) / max(want.routed, 1.0)
    return out


def compare(got: Readings, want: Readings, limits: dict):
    from bench.result import Check
    g = gaps(got, want)
    return [Check(name, g[name], limits[name])
            for name in ("loss_gap", "change_gap", "loader_rows",
                         "routed_gap")]


def run(ctx):
    from bench.result import Result
    from repro import obs
    config, traffic = ctx.config, ctx.cell["traffic"]
    t0 = time.perf_counter()
    hist, corpus = train.make_data(ctx.seed, config, traffic)
    t1 = time.perf_counter()
    mem = []
    s = Setup(ctx.seed, config, traffic, corpus=corpus, hist=hist)
    t2 = time.perf_counter()
    train.memory(ctx, "first_steps", mem)
    s.warm_up()
    t3 = time.perf_counter()
    train.memory(ctx, "warm", mem)
    print(f"train_moe: set-up parts: data {t1 - t0:.3f}s, weights + state "
          f"+ {len(s.checked)} checked steps {t2 - t1:.3f}s, warm-up to step "
          f"{int(s.state.step)} {t3 - t2:.3f}s; "
          f"compiles {s.trainer.compile_counts}", flush=True)
    step0 = int(s.state.step)
    n_steps = int(traffic["window_steps"])
    compiles0 = obs.counter("xla_compile_events_total").value
    routed0 = routed_counter()
    log = []
    with ctx.window():
        res = s._fit(s.hist, log, keep=False, steps=step0 + n_steps,
                     log_every=20)
    ctx.read_memory()
    train.memory(ctx, "window", mem)
    print(f"train_moe: device memory in use/peak (bytes) after "
          f"{', '.join(mem)}", flush=True)
    window_compiles = obs.counter("xla_compile_events_total").value - compiles0
    routed = routed_counter() - routed0
    if res.steps_done - step0 != n_steps:
        raise RuntimeError(f"the window ran {res.steps_done - step0} steps "
                           f"of {n_steps}")
    done = log[:n_steps]
    users = sum(u for _, u, _ in done)
    buckets = {}
    for b, _, _ in done:
        buckets[b] = buckets.get(b, 0) + 1
    got, checked, corpus = s.program_readings, s.checked, s.corpus
    del res
    s.free()
    want = reference_steps(ctx.seed, config, corpus, checked, s.start,
                           router_seed=s.router_seed)
    checks = compare(got, want, ctx.cell["limits"])
    g = gaps(got, want)
    print(f"train_moe: first-gradient gap (read, not compared) "
          f"{g['grad_gap']!r}; routed rows over the checked steps "
          f"{got.routed!r}, reference {want.routed!r}", flush=True)
    stats = {"window_s": ctx.window_s, "steps": n_steps, "users": users,
             "batch_users": config["batch_users"], "bucket_steps": buckets,
             "window_compiles": window_compiles, "plm": config["plm"],
             "config": config, "encode_rows":
             config["cache"]["encode_budget"], "user_and_loss_flops":
             flops.user_and_loss_forward(
                 batch_users=config["batch_users"],
                 hist_len=config["hist_len"],
                 news_dim=config["plm"]["news_dim"], n_neg=config["n_neg"])}
    print(f"train_moe: {n_steps} steps, {users} users in {ctx.window_s:.3f}s "
          f"({1e3 * ctx.window_s / n_steps:.1f} ms a step); routed rows in "
          f"the window {routed:.0f}; buckets {buckets}; compiles in window "
          f"{window_compiles}; checked buckets {[c.bucket for c in checked]}, "
          f"program losses {got.losses.tolist()} reference "
          f"{want.losses.tolist()}", flush=True)
    return Result(e2e={"train_users_per_s": users / ctx.window_s},
                  attempted=n_steps, failed=0, checks=checks, stats=stats)
