"""The numbers that decide `correct`, each the gap between what the timed
path produced and what the plain reference computes."""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

# a leaf whose reference gradient norm is under this share of the median
# leaf's moves by round-off alone, and is left out of the leaf comparisons
NOUGHT = 1e-3


def leaf_norms(leaves: Sequence[np.ndarray]) -> np.ndarray:
    return np.asarray([float(np.linalg.norm(np.asarray(x, np.float64)))
                       for x in leaves])


def counted_leaves(ref_grad_norms: np.ndarray) -> np.ndarray:
    """Leaves whose reference gradient is not nought to rounding: at least
    NOUGHT of the median leaf's norm."""
    med = float(np.median(ref_grad_norms))
    return ref_grad_norms >= NOUGHT * med


def worst_leaf_gap(prog: Sequence[np.ndarray], ref: Sequence[np.ndarray],
                   counted: np.ndarray) -> float:
    """max over counted leaves of |‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖):
    the gap between the two norms of a leaf, not the norm of the
    difference, against the larger of that leaf's and the median leaf's
    reference norm."""
    pn, rn = leaf_norms(prog)[counted], leaf_norms(ref)[counted]
    if rn.size == 0:
        return float("nan")
    med = float(np.median(rn))
    return float(np.max(np.abs(pn - rn) / np.maximum(np.maximum(rn, med),
                                                     1e-30)))


def max_rel_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    p, r = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if p.shape != r.shape:
        return float("inf")
    return float(np.max(np.abs(p - r) / np.maximum(np.abs(r), 1e-30)))


def logit_gap(ref_logits: np.ndarray, tokens: List[int]) -> float:
    """Widest gap by which a token's reference logit lies below the
    reference's best at its position.  ref_logits (T, V)."""
    lg = np.asarray(ref_logits, np.float64)
    best = lg.max(axis=-1)
    got = lg[np.arange(len(tokens)), np.asarray(tokens)]
    return float(np.max(best - got))
