"""The yardstick's FLOP and byte counts against a hand count for one
phi4-mini-3.8b layer, and the peak table."""
import pytest

import _paths  # noqa: F401
from chipbench import work
from chipbench.model import Model

PHI4_1L = Model(n_layers=1, d_model=3072, n_heads=24, n_kv_heads=8,
                head_dim=128, d_ff=8192, vocab=200064, rope_theta=1e4,
                rope_fraction=1.0, norm_eps=1e-6)


def test_one_phi4_layer_by_hand():
    # q 3072x3072, k and v 3072x1024 each, o 3072x3072, gate and up
    # 3072x8192 each, down 8192x3072
    params = 3072 * 3072 * 2 + 3072 * 1024 * 2 + 3072 * 8192 * 3
    assert params == 100_663_296
    assert work.layer_weight_params(PHI4_1L) == params
    assert work.gemm_flops(PHI4_1L, 10) == 2 * 10 * params
    # one token at position 99 attends 100 keys: QK^T and PV, 24 heads
    assert work.attn_flops(PHI4_1L, [100]) == 4 * 24 * 128 * 100
    assert work.decode_flops(PHI4_1L, [99]) == (
        2 * params + 4 * 24 * 128 * 100 + 2 * 3072 * 200064)
    # a 4-token prompt: positions 0..3 attend 1+2+3+4 keys
    assert work.prefill_flops(PHI4_1L, 0, 4) == (
        2 * 4 * params + 4 * 24 * 128 * 10 + 2 * 3072 * 200064)
    # weights once, each GEMM's rows in and out once, bf16
    act = (3072 + 3072) + 2 * (3072 + 1024) + (3072 + 3072) \
        + 2 * (3072 + 8192) + (8192 + 3072)
    assert work.gemm_bytes(PHI4_1L, 1) == 2 * (params + act)


def test_chunk_attention_work():
    # queries at positions 8, 9 over a cache of 10: 9 + 10 keys
    flops, nbytes = work.chunk_attn_work(PHI4_1L, 8, 10)
    assert flops == 4 * 24 * 128 * 19
    assert nbytes == 2 * (2 * 10 * 8 * 128 + 2 * 2 * 24 * 128)


def test_roofline_bound_and_peaks():
    p = work.peaks_for("TPU v5 lite")
    assert p.bf16_flops == 197e12 and p.hbm_bytes == 819e9
    assert work.bound_s(197e12, 1.0, p) == pytest.approx(1.0)
    assert work.bound_s(1.0, 819e9, p) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        work.peaks_for("cpu")
