"""The few statistics the benchmark reports, in one place."""

from __future__ import annotations

import math
from typing import List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """``q`` in [0, 100], linear interpolation between closest ranks
    (numpy's default). None for an empty sample."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def pool_from_spec(spec) -> List[int]:
    """The pool a traffic file's ``prompt_chars`` / ``user_chars`` block
    describes, from the block's own ``pool_seed``."""
    import numpy as np

    return lognormal_pool(
        np.random.default_rng(int(spec["pool_seed"])), int(spec["pool"]),
        spec["median"], spec["sigma"], spec["min"], spec["max"],
        spec.get("long_every", 0), spec.get("long_min", 0),
        spec.get("long_max", 0),
    )


def lognormal_pool(
    rng, n: int, median_: float, sigma: float, lo: int, hi: int,
    long_every: int = 0, long_min: int = 0, long_max: int = 0,
) -> List[int]:
    """A fixed multiset of ``n`` heavy-tailed sizes: log-normal around
    ``median_``, clipped to [lo, hi]; every ``long_every``-th entry is
    drawn uniformly from [long_min, long_max] instead. ``rng`` is a
    ``numpy.random.Generator`` seeded from the traffic file, so every
    ``--seed`` sees the same sizes (in another order)."""
    out = []
    for i in range(n):
        if long_every and i % long_every == long_every - 1:
            out.append(int(rng.integers(long_min, long_max + 1)))
        else:
            v = float(rng.lognormal(math.log(median_), sigma))
            out.append(int(min(max(v, lo), hi)))
    return out
