"""Generator `knows_symmetric`: an undirected friendship graph whose
largest degree does not grow with the graph, emitted as KNOWS rows in both
directions — what LDBC SNB's Person-KNOWS is in shape (friendship is
symmetric there and datagen caps a person's friends), not its datagen's
degree curve nor its correlated windows.

Per person a target degree `clip(round(lognormal(mu, 1)), 1, max_degree)`
with `mu` solved so that the clipped mean is `degree`; one stub per unit
of degree, one shuffle of the stubs, consecutive stubs paired (the
configuration model), self-pairs dropped, parallel pairs kept.  Pair i is
row i (a -> b) and row i + pairs (b -> a): each direction draws its own
`w`, `f`, `city`, as `social_arrays` draws them.  A neighbour is reached
in proportion to its degree, so the mean degree met at the far end of an
edge is about e x `degree` (sigma = 1).  Every draw comes from
`default_rng(seed)` in a fixed order.  numpy only; imports nothing of the
program."""
from __future__ import annotations

import math

import numpy as np

from benchmarks.reference.generators.snb_tables import NAMES

SIGMA = 1.0


def _phi(x: float) -> float:
    return 0.5 * (1.0 + math.erf(x / math.sqrt(2.0)))


def clipped_mean(mu: float, lo: float, hi: float) -> float:
    """E[clip(X, lo, hi)] of X ~ lognormal(mu, SIGMA), in closed form."""
    a, b = (math.log(lo) - mu) / SIGMA, (math.log(hi) - mu) / SIGMA
    inside = math.exp(mu + SIGMA ** 2 / 2) * (_phi(b - SIGMA) - _phi(a - SIGMA))
    return lo * _phi(a) + inside + hi * (1.0 - _phi(b))


def mu_for(degree: float, max_degree: int) -> float:
    """The `mu` at which the clipped mean is `degree` (bisection: the mean
    rises with mu)."""
    lo, hi = -5.0, math.log(max_degree) + 5.0
    for _ in range(80):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if clipped_mean(mid, 1.0, max_degree) < degree else (lo, mid)
    return (lo + hi) / 2


def generate(sizes: dict, seed: int) -> dict:
    """-> {"n", "vertex": {}, "strings": {city: names}, "edges": {KNOWS:
    {src, dst, w, f, city}}}; row i and row i + rows/2 are the two
    directions of one friendship; `city` is an index into `strings.city`."""
    n, degree = int(sizes["persons"]), float(sizes["degree"])
    max_degree = int(sizes["max_degree"])
    if not 1 <= degree < max_degree < n:
        raise ValueError(f"knows_symmetric needs 1 <= degree < max_degree < persons, got {sizes}")
    rng = np.random.default_rng(seed)
    d = np.clip(np.rint(rng.lognormal(mu_for(degree, max_degree), SIGMA, n)), 1, max_degree)
    stubs = np.repeat(np.arange(n, dtype=np.int32), d.astype(np.int64))
    stubs = rng.permutation(stubs[:stubs.size - stubs.size % 2])
    a, b = stubs[0::2], stubs[1::2]
    keep = a != b
    pairs = int(keep.sum())
    rows = 2 * pairs
    src, dst = np.empty(rows, np.int64), np.empty(rows, np.int64)
    src[:pairs], dst[:pairs] = a[keep], b[keep]
    src[pairs:], dst[pairs:] = dst[:pairs], src[:pairs]
    return {"n": n, "vertex": {}, "strings": {"city": NAMES},
            "edges": {"KNOWS": {
                "src": src, "dst": dst,
                "w": rng.integers(0, 100, rows, dtype=np.int64),
                "f": rng.random(rows),
                "city": rng.integers(0, len(NAMES), rows, dtype=np.int64)}}}
