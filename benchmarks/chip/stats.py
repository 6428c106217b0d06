"""Order statistics the metrics share."""
from __future__ import annotations

from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) over ``n - 1`` ranks, as
    ``repro.serve.loadgen.percentile`` computes it."""
    if not xs:
        raise ValueError("percentile of an empty sample")
    s = sorted(xs)
    k = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
    return float(s[k])
