"""The order statistic of the end-to-end latency metrics."""
from __future__ import annotations

import math
from typing import Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The q-th percentile by nearest rank: the smallest value with at
    least q% of the sample at or below it. The harness enters a failed
    request at the drain limit, above every finished one."""
    if not values:
        raise ValueError("empty sample")
    xs = sorted(values)
    k = max(int(math.ceil(q / 100.0 * len(xs))) - 1, 0)
    return xs[k]

