"""Analytic operations and bytes: the encoder, the train step, the
bus-attention kernel.

Counts are those the algorithm needs, from shapes alone: a multiply-add is
2 operations, an elementwise op or softmax is not counted (it is a few per
score against a d-long dot product), and recomputation (remat) is not
counted.  ``bench/tests/test_flops.py`` checks them against hand counts.
"""
from __future__ import annotations


def encoder_forward(*, d: int, d_ff: int, n_layers: int, n_segments: int,
                    seg_len: int, news_dim: int, use_bus: bool = True) -> float:
    """Forward operations for one news article through BusLM.

    Per layer: q on the K*S segment tokens, k and v on the K*(S+K) keys
    (segment tokens plus the bus), the output projection, Q.K^T and P.V
    over S x (S+K) per segment (summed over heads, which make up d), and
    the two FFN matmuls.  Then the two-level additive pooling and the
    output projection."""
    K, S = n_segments, seg_len
    Sk = S + K if (use_bus and K > 1) else S
    per_layer = (2 * K * S * d * d            # q
                 + 2 * 2 * K * Sk * d * d     # k, v
                 + 2 * K * S * d * d          # o
                 + 2 * 2 * K * S * Sk * d     # Q.K^T and P.V
                 + 2 * 2 * K * S * d * d_ff)  # FFN up and down
    pool = (2 * K * S * d * d + 2 * 2 * K * S * d    # token pool: proj, q, sum
            + 2 * K * d * d + 2 * 2 * K * d          # segment pool
            + 2 * d * news_dim)                      # out_proj
    return float(n_layers * per_layer + pool)


def user_and_loss_forward(*, batch_users: int, hist_len: int, news_dim: int,
                          n_neg: int) -> float:
    """Causal attentive user model over [B, L] history embeddings and the
    autoregressive loss (one positive and ``n_neg`` negatives for each of
    the L-1 predictions), forward."""
    B, L, nd = batch_users, hist_len, news_dim
    user = 2 * B * L * nd * nd + 2 * B * L * nd + 2 * B * L * nd
    loss = 2 * B * (L - 1) * nd * (1 + n_neg)
    return float(user + loss)


def train_step(*, encode_rows: int, seg_len: int, plm: dict,
               batch_users: int, hist_len: int, n_neg: int) -> float:
    """Model operations of one Algorithm-1 step: three times the forward
    of the encode set (forward + backward) and of the user model and
    loss.  The encode set is the step's static encode budget of rows."""
    enc = encoder_forward(d=plm["d_model"], d_ff=plm["d_ff"],
                          n_layers=plm["n_layers"],
                          n_segments=plm["n_segments"], seg_len=seg_len,
                          news_dim=plm["news_dim"])
    ul = user_and_loss_forward(batch_users=batch_users, hist_len=hist_len,
                               news_dim=plm["news_dim"], n_neg=n_neg)
    return 3.0 * (encode_rows * enc + ul)


def bus_attention(*, M: int, K: int, S: int, H: int, D: int,
                  backward: bool, itemsize: int = 4):
    """(operations, bytes) of one call of the fused bus-attention kernel
    over M news (Sk = S + K keys per segment).

    Forward: Q.K^T and P.V.  Backward (one fused pass): Q.K^T again (the
    kernel recomputes the tile's softmax), then dV, dP, dQ and dK.  Bytes:
    every operand read once and every result written once; the int32 mask
    row is shared by the heads of a segment."""
    Sk = S + K
    tile = 2 * S * Sk * D
    n_q = M * K * H * S * D
    n_kv = M * K * H * Sk * D
    mask = M * K * Sk * 4
    if backward:
        ops = 5 * tile * M * K * H
        moved = itemsize * (2 * n_q + 2 * n_kv) + mask \
            + itemsize * (n_q + 2 * n_kv)
    else:
        ops = 2 * tile * M * K * H
        moved = itemsize * (2 * n_q + 2 * n_kv) + mask
    return float(ops), float(moved)

