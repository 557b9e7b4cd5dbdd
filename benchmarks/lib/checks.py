"""The comparison that decides ``correct``: numbers, each beside its
limit."""

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit

    def line(self) -> str:
        return (f"check {self.name}: {self.value:.6g} (limit {self.limit:g})"
                f" {'ok' if self.ok else 'FAILED'}")


def all_ok(checks: List[Check]) -> bool:
    return bool(checks) and all(c.ok for c in checks)


def aligned(got: dict, ref: dict):
    """Two ``{path: norm}`` maps as vectors in one order; the paths must
    be the same."""
    if set(got) != set(ref):
        raise ValueError(f"the trees differ: {sorted(set(got) ^ set(ref))[:6]}")
    keys = sorted(ref)
    return (np.asarray([got[k] for k in keys], np.float64),
            np.asarray([ref[k] for k in keys], np.float64))


def leaf_gaps(got, ref) -> np.ndarray:
    """Per leaf, ``|got - ref|`` of two ``{path: norm}`` maps, measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    got, ref = aligned(got, ref)
    gap = np.abs(got - ref) / np.maximum(ref, np.median(ref))
    return np.where(np.isfinite(gap), gap, np.inf)


def gap_figures(got, ref) -> str:
    g = leaf_gaps(got, ref)
    return ("worst %.4g p99 %.4g p90 %.4g median %.4g mean %.4g total %.4g"
            % (g.max(), np.percentile(g, 99), np.percentile(g, 90),
               np.median(g), g.mean(), total_norm_gap(got, ref)))


def p99_norm_gap(got, ref) -> float:
    """The 99th percentile of ``leaf_gaps``: the worst leaf but for one
    leaf in a hundred.  The very worst swings with the seed (PERF.md
    section 2: sound runs read 0.002-0.22 by the worst leaf and
    0.0015-0.016 by this)."""
    return float(np.percentile(leaf_gaps(got, ref), 99))


def total_norm_gap(got, ref) -> float:
    """``|got - ref|`` of the norms over ALL leaves (root of the summed
    squares), against the reference's."""
    got, ref = aligned(got, ref)
    a, b = np.sqrt(np.sum(got ** 2)), np.sqrt(np.sum(ref ** 2))
    return float(abs(a - b) / b)
