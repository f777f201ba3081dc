"""Operations and bytes of the VB hot path, counted from shapes.

A floating-point operation is one multiply or one add; a fused
multiply-add counts two.  Bytes are what must cross HBM at least once.
"""
from __future__ import annotations


def estep_flops(N: int, T: int, K: int, D: int) -> int:
    """The fused VBE kernel over N nodes of T points: per point and
    component, the quadratic form x' (nu W) x (2D^2 + 2D), the cross term
    x' b (2D), the first moment r x (2D) and the second moment
    (r x) x' (D + 2D^2)."""
    return N * T * K * (4 * D * D + 7 * D)


def estep_bytes(N: int, T: int, K: int, D: int, data_bytes: int = 4) -> int:
    """Reads of x (T, D) and the mask (T,) in the data dtype and of the
    per-component terms (log prior, nu W, nu W m, c) in f32, and the
    write of the statistics (R, sum r x, sum r x x'), per node."""
    terms = K + K * D * D + K * D + K
    stats = (K + K * D + K) * D
    return N * (T * (D + 1) * data_bytes + 4 * (terms + stats))


def vbm_flops(N: int, K: int, D: int) -> int:
    """Per node and component, the dense D x D linear algebra around the
    kernel: unpacking W from the message (an inverse, 2D^3), its
    log-determinant (2D^3/3), the prior's W0^-1 and the updated W (two
    inverses), and W^-1 again to pack the message (26D^3/3 in all)."""
    return N * K * (26 * D ** 3) // 3


def combine_flops(N: int, P: int) -> int:
    """The diffusion combine W @ varphi over N nodes of P parameters."""
    return 2 * N * N * P


def step_flops(N: int, T: int, K: int, D: int) -> int:
    """One dSVB iteration of one session: VBE, VBM and the combine."""
    P = K + K * (2 + D + D * D)
    return estep_flops(N, T, K, D) + vbm_flops(N, K, D) + combine_flops(N, P)
