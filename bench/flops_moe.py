"""Analytic operations and bytes of the expert encoder (a DeepSeek-V3
stack, ``bench/configs/moonlight_1chip.json``): the model's forward FLOPs
per article and per routed row, and the two kernels it adds to the step,
the grouped matmul and the causal latent bus attention.

Counts are those the algorithm needs, from shapes alone, as in
``bench/flops.py``: a multiply-add is 2 operations, elementwise work and
softmax are not counted, recomputation is not counted.
"""
from __future__ import annotations


def _sizes(c: dict) -> dict:
    """The widths the counts use, from a configuration file."""
    return {"d": c["hidden_size"], "H": c["num_attention_heads"],
            "dn": c["qk_nope_head_dim"], "dr": c["qk_rope_head_dim"],
            "dv": c["v_head_dim"], "r": c["kv_lora_rank"],
            "d_ff": c["intermediate_size"], "f": c["moe_intermediate_size"],
            "E": c["router_experts"], "shared": c["n_shared_experts"],
            "n_dense": c["first_k_dense_replace"],
            "n_expert": c["num_hidden_layers"] - c["first_k_dense_replace"],
            "K": c["plm"]["n_segments"], "news_dim": c["plm"]["news_dim"]}


def attention_pairs(K: int, S: int) -> int:
    """(query, key) pairs one article's attention scores: each segment's
    causal triangle and its queries against the other segments' proxies."""
    return K * (S * (S + 1) // 2 + S * (K - 1))


def article_forward(c: dict, seg_len: int) -> float:
    """Forward operations of one article of K segments of ``seg_len``
    token slots, without the routed experts (``routed_row_forward``):
    latent attention in every layer, the dense SwiGLU layers, the router
    and the shared experts of the expert layers, the pooling and the
    projection to news_dim."""
    z, S = _sizes(c), seg_len
    d, H, dqk = z["d"], z["H"], z["dn"] + z["dr"]
    T = z["K"] * S
    attn = (2 * T * d * H * dqk                     # W_q
            + 2 * T * d * (z["r"] + z["dr"])        # W_kva
            + 2 * T * z["r"] * H * (z["dn"] + z["dv"])   # W_kvb
            + 2 * attention_pairs(z["K"], S) * H * (dqk + z["dv"])
            + 2 * T * H * z["dv"] * d)              # W_o
    dense = 3 * 2 * T * d * z["d_ff"]
    expert = 2 * T * d * z["E"] + 3 * 2 * T * d * z["shared"] * z["f"]
    pool = (2 * T * d * d + 2 * 2 * T * d + 2 * z["K"] * d * d
            + 2 * 2 * z["K"] * d + 2 * d * z["news_dim"])
    n = z["n_dense"] + z["n_expert"]
    return float(n * attn + z["n_dense"] * dense + z["n_expert"] * expert
                 + pool)


def routed_row_forward(c: dict) -> float:
    """Forward operations of one row routed to a held expert: its SwiGLU."""
    z = _sizes(c)
    return float(3 * 2 * z["d"] * z["f"])


def grouped_matmul(*, rows: float, k: int, n: int, groups: int,
                   transposed_out: bool = False, itemsize: int = 2):
    """(operations, bytes) of one grouped matmul over ``rows`` routed rows:
    a [rows, k] lhs times one of ``groups`` [k, n] weights each, read once
    each, and the [rows, n] result in float32.  ``transposed_out`` is the
    weight gradient (tgmm): [rows, k] and [rows, n] in, the [groups, k, n]
    result out in float32."""
    ops = 2.0 * rows * k * n
    if transposed_out:
        moved = itemsize * rows * (k + n) + 4 * groups * k * n
    else:
        moved = itemsize * (rows * k + groups * k * n) + 4 * rows * n
    return ops, float(moved)


def latent_bus_attention(*, M: int, K: int, S: int, H: int, D: int, Dv: int,
                         backward: bool, itemsize: int = 4):
    """(operations, bytes) of one call of the causal bus-attention kernel
    over M news, queries and keys of head dim D, values of Dv, Sk = S + K
    keys a segment.  Operations count the pairs a query sees (the causal
    triangle and the other segments' proxies); the backward pass
    recomputes the scores, then dV, dP, dQ and dK.  Bytes: every operand
    read once and every result written once."""
    pairs = M * H * attention_pairs(K, S)
    Sk = S + K
    q, o = M * K * H * S * D, M * K * H * S * Dv
    k, v = M * K * H * Sk * D, M * K * H * Sk * Dv
    mask = M * K * Sk * 4
    if backward:            # q, k, v, mask, dO in; dQ, dK, dV out
        ops = 2 * pairs * (3 * D + 2 * Dv)
        moved = itemsize * (2 * (q + k + v) + o) + mask
    else:
        ops = 2 * pairs * (D + Dv)
        moved = itemsize * (q + k + v + o) + mask
    return float(ops), float(moved)
