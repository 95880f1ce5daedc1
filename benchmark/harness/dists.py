"""Length distributions as fixed sets in a fixed, even order.

Every seed gets the SAME multiset of values: the distribution's quantiles
at the midpoints of n equal strata. Their order is low-discrepancy (the
ranks of a van der Corput sequence), so long and short values alternate
evenly and no seed draws a cluster of long requests; the seed only rotates
where in that cycle a run starts. Measured first with a seeded shuffle
(PR 24, chip): two runs of one seed agreed to 1.5 % on gap_p95_ms while
seeds differed by 12 %, and one seed's cluster doubled ttft_p95_ms. That is
the seed changing the work, not noise. Bursts belong to a traffic mix of
their own. Jax-free.
"""

from __future__ import annotations

import math
import statistics

_NORMAL = statistics.NormalDist()


def quantile(spec: dict, u: float) -> float:
    """The u-quantile (0 < u < 1) of the distribution a traffic file names."""
    dist = spec["dist"]
    if dist == "fixed":
        return float(spec["value"])
    if dist == "uniform":
        return spec["min"] + u * (spec["max"] - spec["min"])
    if dist == "lognormal":
        x = math.exp(math.log(spec["median"]) + spec["sigma"] * _NORMAL.inv_cdf(u))
        return min(max(x, spec["min"]), spec["max"])
    if dist == "exponential":  # mean 1; the caller scales
        return -math.log1p(-u)
    raise ValueError(f"unknown distribution {dist!r}")


def radical_inverse(i: int, base: int) -> float:
    """van der Corput: the digits of i in `base`, mirrored at the point."""
    inv, f = 0.0, 1.0 / base
    while i:
        i, digit = divmod(i, base)
        inv += digit * f
        f /= base
    return inv


def even_order(n: int, base: int) -> list:
    """A permutation of range(n): position j holds the rank of the j-th
    van der Corput point among the first n, so neighbours are far apart."""
    points = [radical_inverse(j + 1, base) for j in range(n)]
    order = sorted(range(n), key=points.__getitem__)
    rank = [0] * n
    for r, j in enumerate(order):
        rank[j] = r
    return rank


def stratified(spec: dict, n: int, base: int, integer: bool = True) -> list:
    """n values: the quantiles at (i + 0.5) / n, in the even order of `base`.
    Different bases give different orders, so two attributes of a request
    (prompt and output length) do not rise and fall together."""
    vals = [quantile(spec, (i + 0.5) / n) for i in range(n)]
    if integer:
        vals = [int(round(v)) for v in vals]
    return [vals[r] for r in even_order(n, base)]
