"""bench/flops.py and bench/peaks.py against hand counts."""
import pytest

from bench import flops, peaks


def test_encoder_forward_hand_count():
    # d=4, d_ff=8, 1 layer, K=2 segments of S=3 tokens, Sk=S+K=5, out 5:
    # q 2*2*3*4*4=192, k+v 2*2*2*5*4*4=640, o 192, QK+PV 2*2*2*3*5*4=480,
    # FFN 2*2*2*3*4*8=768; token pool 192+96, segment pool 64+32, out 40
    assert flops.encoder_forward(d=4, d_ff=8, n_layers=1, n_segments=2,
                                 seg_len=3, news_dim=5) == 2272 + 424


def test_encoder_forward_prod_against_program_count():
    # PROD: 1.65e10 per article (ROADMAP S3); the program's plm_flops
    # counts k/v on S keys instead of S+K and leaves out pooling
    from repro.configs.speedyfeed_arch import PROD
    from repro.core import plm_flops
    ours = flops.encoder_forward(d=768, d_ff=3072, n_layers=12, n_segments=3,
                                 seg_len=32, news_dim=768)
    assert 1.6e10 < ours < 1.75e10
    assert ours == pytest.approx(plm_flops(PROD.plm, 1), rel=0.05)


def test_train_step_is_three_forwards():
    plm = dict(d_model=4, d_ff=8, n_layers=1, n_segments=2, news_dim=5)
    one = flops.encoder_forward(d=4, d_ff=8, n_layers=1, n_segments=2,
                                seg_len=3, news_dim=5)
    ul = flops.user_and_loss_forward(batch_users=2, hist_len=3, news_dim=5,
                                     n_neg=1)
    # user: 2*2*3*25 + 2*2*3*5 * 2; loss: 2*2*2*5*2
    assert ul == 300 + 120 + 80
    assert flops.train_step(encode_rows=7, seg_len=3, plm=plm, batch_users=2,
                            hist_len=3, n_neg=1) == 3 * (7 * one + ul)


def test_bus_attention_hand_count():
    # M=K=H=1, S=2, D=3, Sk=3: one tile Q.K^T is 2*2*3*3 = 36
    ops, moved = flops.bus_attention(M=1, K=1, S=2, H=1, D=3, backward=False)
    assert ops == 72 and moved == 4 * (2 * 6 + 2 * 9) + 12
    ops, moved = flops.bus_attention(M=1, K=1, S=2, H=1, D=3, backward=True)
    assert ops == 180 and moved == 4 * (2 * 6 + 2 * 9) + 12 + 4 * (6 + 18)


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s, p.hbm_bytes) == (
        197e12, 819e9, 16e9)
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
    t, bound = peaks.roofline_seconds(197e12, 1.0, p)
    assert (t, bound) == (1.0, "compute")
    t, bound = peaks.roofline_seconds(1.0, 819e9, p)
    assert (t, bound) == (1.0, "memory")
