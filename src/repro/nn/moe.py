"""Mixture-of-Experts substrate.

The news encoder's expert layer is ``routed_moe``: DeepSeek-V3 routing
(sigmoid scores, a correction bias for the choice, top-k, renormalised
and scaled gates, shared experts) as one chip of an expert-parallel
deployment holds it -- told which experts it holds, routing over all of
them, and computing its own experts' part of the result, dropless, with
grouped matmuls over exactly the rows routed to them.

The inherited LM family (models/lm.py) keeps the capacity-dropping
switch-style layer below, with three interchangeable implementations
(``impl`` knob):
  * ``dense``  — every expert runs on every token, gated by the top-k mask.
                 O(E) FLOPs; only for tiny smoke/grad tests.
  * ``gather`` — sort-based capacity dispatch on one device (the real routing
                 algorithm; top-k -> argsort -> fixed-capacity gather ->
                 grouped GEMM -> scatter-combine). Used for CPU validation.
  * ``ep``     — expert parallelism: shard_map over the mesh; experts sharded
                 on the ``model`` axis, activations replicated over it; each
                 device runs ``gather`` restricted to its local expert slice
                 and the outputs are psum-combined (row-parallel pattern).

The routing math (softmax -> top-k -> normalized gates -> capacity drop) is
identical across implementations, so ``gather`` is the oracle for ``ep``.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from .core import normal_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    gated: bool = True          # SwiGLU experts (w1, w3, w2) vs GELU (w1, w2)
    norm_topk: bool = True      # renormalize top-k gate weights to sum to 1


def init_moe(key, cfg: MoEConfig, param_dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    p = {
        "router": normal_init(ks[0], (d, E), 0.02, param_dtype),
        "w1": normal_init(ks[1], (E, d, f), 0.02, param_dtype),
        "w2": normal_init(ks[2], (E, f, d), 0.02, param_dtype),
    }
    if cfg.gated:
        p["w3"] = normal_init(ks[3], (E, d, f), 0.02, param_dtype)
    return p


def _expert_ffn(p, x_e, cfg: MoEConfig):
    """x_e: [E, C, d] -> [E, C, d] grouped GEMMs."""
    h1 = jnp.einsum("ecd,edf->ecf", x_e, p["w1"].astype(x_e.dtype))
    if cfg.gated:
        h3 = jnp.einsum("ecd,edf->ecf", x_e, p["w3"].astype(x_e.dtype))
        h = jax.nn.silu(h1) * h3
    else:
        h = jax.nn.gelu(h1)
    return jnp.einsum("ecf,efd->ecd", h, p["w2"].astype(x_e.dtype))


def _route(p, x2d, cfg: MoEConfig):
    """x2d: [T, d] -> (gates [T,k], experts [T,k] int32, aux_loss scalar)."""
    logits = (x2d @ p["router"].astype(x2d.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                       # [T, E]
    gate, eidx = jax.lax.top_k(probs, cfg.top_k)                  # [T, k]
    if cfg.norm_topk:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # switch-style load-balancing loss
    T = x2d.shape[0]
    me = probs.mean(axis=0)                                       # [E]
    one_hot = jax.nn.one_hot(eidx[:, 0], cfg.n_experts, dtype=jnp.float32)
    ce = one_hot.mean(axis=0)
    aux = cfg.n_experts * jnp.sum(me * ce)
    return gate, eidx, aux


def moe_dense(p, x, cfg: MoEConfig):
    """All-experts path (tiny tests only). x: [..., d]."""
    shp = x.shape
    x2 = x.reshape(-1, shp[-1])
    gate, eidx, aux = _route(p, x2, cfg)
    # full gate matrix [T, E]
    gmat = jnp.zeros((x2.shape[0], cfg.n_experts), x2.dtype)
    gmat = gmat.at[jnp.arange(x2.shape[0])[:, None], eidx].set(gate.astype(x2.dtype))
    y_all = _expert_ffn(p, jnp.broadcast_to(x2, (cfg.n_experts,) + x2.shape), cfg)
    y = jnp.einsum("te,etd->td", gmat, y_all)
    return y.reshape(shp), aux


def capacity_for(tokens: int, cfg: MoEConfig) -> int:
    c = int(math.ceil(tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)   # round up to 8 for TPU-friendly tiling


def moe_gather(p, x, cfg: MoEConfig, *, expert_start: int = 0,
               n_local: int | None = None, capacity: int | None = None):
    """Sort-based capacity dispatch. x: [..., d] -> (y, aux).

    ``expert_start``/``n_local`` restrict computation to a contiguous expert
    slice whose weights are ``p['w*']`` (used by the EP path); routing is
    always computed over the full expert set.
    """
    shp = x.shape
    d = shp[-1]
    x2 = x.reshape(-1, d)
    T = x2.shape[0]
    E_local = n_local if n_local is not None else cfg.n_experts
    C = capacity if capacity is not None else capacity_for(T, cfg)

    gate, eidx, aux = _route(p, x2, cfg)
    k = cfg.top_k
    flat_e = eidx.reshape(-1)                                      # [T*k]
    flat_g = gate.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)                       # [T*k]
    sorted_e = flat_e[order]
    sorted_tok = order // k
    sorted_g = flat_g[order]
    # rank of each assignment within its expert
    counts = jnp.zeros((cfg.n_experts,), jnp.int32).at[flat_e].add(1)
    excl = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(T * k, dtype=jnp.int32) - excl[sorted_e]
    local_e = sorted_e - expert_start
    valid = (rank < C) & (local_e >= 0) & (local_e < E_local)
    slot = jnp.where(valid, local_e * C + rank, E_local * C)       # OOB -> dropped

    x_e = jnp.zeros((E_local * C, d), x2.dtype)
    x_e = x_e.at[slot].set(x2[sorted_tok], mode="drop")
    y_e = _expert_ffn(p, x_e.reshape(E_local, C, d), cfg).reshape(E_local * C, d)

    slot_read = jnp.minimum(slot, E_local * C - 1)
    contrib = jnp.take(y_e, slot_read, axis=0)
    contrib = contrib * (sorted_g * valid)[:, None].astype(contrib.dtype)
    y = jnp.zeros((T, d), x2.dtype).at[sorted_tok].add(contrib)
    return y.reshape(shp), aux


def moe_ep(p, x, cfg: MoEConfig, mesh, *, data_axes=("pod", "data"),
           model_axis="model"):
    """Expert-parallel MoE via shard_map.

    x: [B, S, d] sharded batch->data_axes, replicated over model_axis.
    Expert weights sharded over model_axis on the expert dim. Output psum'd
    over model_axis (replicated), aux loss is identical on every shard.
    """
    from jax.sharding import PartitionSpec as P

    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    data_axes = tuple(a for a in data_axes if a in axis_sizes)
    n_shards = axis_sizes[model_axis]
    assert cfg.n_experts % n_shards == 0, "experts must divide model axis"
    E_local = cfg.n_experts // n_shards
    B, S, d = x.shape
    # small-batch decode: drop data axes the batch can't shard over
    # (x stays replicated there; routing is redundantly recomputed)
    while data_axes and B % math.prod(axis_sizes[a] for a in data_axes):
        data_axes = data_axes[1:]
    T_local = (B // math.prod([axis_sizes[a] for a in data_axes], start=1)) * S
    C = capacity_for(T_local, cfg)

    pspec = {
        "router": P(),
        "w1": P(model_axis, None, None),
        "w2": P(model_axis, None, None),
    }
    if cfg.gated:
        pspec["w3"] = P(model_axis, None, None)
    xspec = P(data_axes, None, None)

    def local_fn(pl, xl):
        idx = jax.lax.axis_index(model_axis)
        y, aux = moe_gather(pl, xl, cfg, expert_start=idx * E_local,
                            n_local=E_local, capacity=C)
        y = jax.lax.psum(y, model_axis)
        if data_axes:
            aux = jax.lax.pmean(aux, data_axes)
        return y, aux

    y, aux = jax.shard_map(
        local_fn, mesh=mesh, in_specs=(pspec, xspec),
        out_specs=(xspec, P()),
    )(p, x)
    return y, aux


# ---------------------------------------------------------------------------
# the news encoder's expert layer: dropless, over the experts held here
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RoutedMoEConfig:
    """An expert layer as one chip of an expert-parallel deployment holds
    it.  The router scores all ``n_experts``; this chip holds the
    ``n_held`` experts with global ids ``held_start ...`` and computes
    their part of the result.  ``n_shared`` shared experts are one SwiGLU
    of width ``n_shared * d_expert`` that every chip computes alike."""
    d_model: int
    d_expert: int
    n_experts: int            # the router's width (published count)
    top_k: int
    n_held: int
    held_start: int = 0
    n_shared: int = 0
    routed_scale: float = 1.0

    @property
    def d_shared(self) -> int:
        return self.n_shared * self.d_expert


ROW_TILE = 128      # grouped-matmul row tile; the row buffer pads to it


def init_routed_moe(key, cfg: RoutedMoEConfig, param_dtype=jnp.float32):
    ks = jax.random.split(key, 8)
    d, f, g, fs = cfg.d_model, cfg.d_expert, cfg.n_held, cfg.d_shared
    p = {"router": normal_init(ks[0], (d, cfg.n_experts), 0.02, param_dtype),
         # noaux_tc's correction bias: it moves the choice, not the gates,
         # and takes no gradient (a balancing rule sets it in DeepSeek-V3)
         "router_bias": normal_init(ks[1], (cfg.n_experts,), 0.02,
                                    param_dtype),
         "w1": normal_init(ks[2], (g, d, f), 0.02, param_dtype),
         "w3": normal_init(ks[3], (g, d, f), 0.02, param_dtype),
         "w2": normal_init(ks[4], (g, f, d), 0.02, param_dtype)}
    if cfg.n_shared:
        p["shared"] = {"w1": normal_init(ks[5], (d, fs), 0.02, param_dtype),
                       "w3": normal_init(ks[6], (d, fs), 0.02, param_dtype),
                       "w2": normal_init(ks[7], (fs, d), 0.02, param_dtype)}
    return p


def route_sigmoid(p, x, cfg: RoutedMoEConfig):
    """x [T, d] -> (gates [T, k] float32, experts [T, k] int32).

    ``s = sigmoid(x W_r)``; the k experts of largest ``s + bias``; gates
    ``s`` over those k, renormalised to sum 1, times ``routed_scale``.
    The router runs at HIGHEST precision: the choice is discrete, and one
    bf16 pass would flip near ties against a float32 reference."""
    s = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", x.astype(jnp.float32), p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32),
                           cfg.top_k)
    gate = jnp.take_along_axis(s, idx, axis=-1)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-20)
    return gate * cfg.routed_scale, idx


def swiglu(w1, w3, w2, x):
    """SwiGLU: (silu(x W1) * x W3) W2."""
    h = jax.nn.silu(x @ w1.astype(x.dtype)) * (x @ w3.astype(x.dtype))
    return h @ w2.astype(x.dtype)


def _grouped(lhs, w, sizes, impl: str):
    """[m, k] rows grouped by ``sizes`` times each group's [k, n] -> [m, n]
    float32: the megablox kernel under "pallas", else XLA's ragged dot;
    both at the backend's DEFAULT precision."""
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.grouped_matmul(lhs, w, sizes)
    return jax.lax.ragged_dot(lhs, w.astype(lhs.dtype), sizes,
                              preferred_element_type=jnp.float32)


def routed_moe(p, x, valid, cfg: RoutedMoEConfig, *, impl: str = "xla"):
    """The expert layer's output on x [T, d] for the tokens ``valid`` [T]
    marks real (padding slots are not routed), and its counts.

    Every (token, chosen expert) pair whose expert is held here is a row
    of one grouped matmul per expert weight, sorted by expert: dropless,
    in a row buffer of T*k rows (rounded up to ``ROW_TILE``), of which
    the kernel runs only the tiles that hold routed rows.  The rows' gated
    outputs are summed back per token; the shared experts run on every
    token slot.  Counts (int32): ``routed`` rows, the grouped matmul's row
    ``slots`` run (its tiles' rows), and ``expert_rows`` [n_held]."""
    T, d = x.shape
    k, g = cfg.top_k, cfg.n_held
    gate, idx = route_sigmoid(p, x, cfg)
    local = idx - cfg.held_start
    held = (local >= 0) & (local < g) & valid[:, None]          # [T, k]
    group = jnp.where(held, local, g).reshape(-1)                 # [T*k]
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((g + 1,), jnp.int32).at[group].add(1)[:g]
    n_rows = -(-T * k // ROW_TILE) * ROW_TILE
    order = jnp.pad(order, (0, n_rows - T * k))
    routed = jnp.arange(n_rows) < sizes.sum()
    xs = jnp.where(routed[:, None], x[order // k], 0)
    h = jax.nn.silu(_grouped(xs, p["w1"], sizes, impl)) \
        * _grouped(xs, p["w3"], sizes, impl)
    y = jnp.where(routed[:, None],
                  _grouped(h.astype(x.dtype), p["w2"], sizes, impl), 0)
    # back to (token, choice) order: the inverse permutation's gather
    slot = jnp.zeros((T * k,), jnp.int32).at[order[:T * k]].set(
        jnp.arange(T * k, dtype=jnp.int32))
    w = jnp.where(held, gate, 0).astype(y.dtype)                  # [T, k]
    out = jnp.einsum("tkd,tk->td", y[slot].reshape(T, k, d), w)
    if cfg.n_shared:
        sp = p["shared"]
        out = out + swiglu(sp["w1"], sp["w3"], sp["w2"], x)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    tiles = jnp.where(sizes > 0, -(-ends // ROW_TILE) - starts // ROW_TILE, 0)
    counts = {"routed": sizes.sum(), "slots": tiles.sum() * ROW_TILE,
              "expert_rows": sizes}
    return out.astype(x.dtype), counts
