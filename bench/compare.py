"""The comparison that decides `correct`: a served session's final
posterior against the plain reference's, run from the same inputs for
the same number of iterations.

The number compared is `phi_gap`: for each block of the Eq. 45 message
(alpha, nu, beta, the mean carrier beta m, the W^-1 carrier), the largest
absolute gap over every node and coordinate of the block, as a share of
the largest magnitude the reference holds there; then the largest of the
five.  The blocks differ by orders of magnitude (counts against
precisions), so a single norm would hear only the W^-1 block.
"""
from __future__ import annotations

import numpy as np


def block_gaps(got, want, names) -> dict:
    """{block: largest |got - want| in the block / largest |want| there}."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return {"all": float("inf")}
    names = np.asarray(names)
    out = {}
    for block in sorted(set(names.tolist())):
        cols = names == block
        scale = np.max(np.abs(want[:, cols]))
        out[block] = float(np.max(np.abs(got[:, cols] - want[:, cols]))
                           / max(scale, 1e-30))
    return out


NO_ANSWER = 1e30     # an answer missing, short of its budget or not finite


def phi_gap(got, want, names) -> float:
    gap = max(block_gaps(got, want, names).values())
    return gap if np.isfinite(gap) else NO_ANSWER
