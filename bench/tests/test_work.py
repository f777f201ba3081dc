"""`bench/work.py` against counts made by hand."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import work  # noqa: E402


def test_estep_flops_by_hand_at_d2_k3():
    D, K = 2, 3
    per_component = (
        2 * D * D        # x @ (nu W): D*D multiply-adds
        + 2 * D          # (x W) * x, summed
        + 2 * D          # x . b
        + 2 * D          # r x into sum_x
        + D              # x * r_k
        + 2 * D * D)     # (x r_k)' x into sum_xx
    assert per_component == 30
    assert work.estep_flops(1, 1, K, D) == K * per_component == 90
    assert work.estep_flops(50, 100, K, D) == 50 * 100 * 90


def test_estep_bytes_by_hand_at_d2_k3():
    D, K, T = 2, 3, 10
    data = T * (D + 1) * 4                       # x and mask, f32
    terms = (K + K * D * D + K * D + K) * 4      # log prior, nu W, b, c
    stats = (K + K * D + K) * D * 4              # the kernel's (rows, D)
    assert data + terms + stats == 120 + 96 + 96
    assert work.estep_bytes(1, T, K, D) == 312
    assert work.estep_bytes(7, T, K, D) == 7 * 312


def test_step_flops_adds_vbm_and_combine():
    N, T, K, D = 50, 100, 3, 2
    P = K + K * (2 + D + D * D)
    assert P == 27
    assert work.step_flops(N, T, K, D) == (work.estep_flops(N, T, K, D)
                                           + N * K * 26 * 8 // 3
                                           + 2 * N * N * P)
