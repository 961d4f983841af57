"""Statistics shared by the harness and the per-layer readers."""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

#: a host-clock time is read only over spans of at least this many
#: seconds in all: the clock is off by about half a millisecond
MIN_SPAN_S = 0.25


def p95(values: Sequence[float]) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def ttft_s(reqs, seconds: float) -> List[float]:
    """Time to first token of every request due in the window [0,
    seconds): first token minus due time, or close minus due time for a
    request with no token by the close, so a stall cannot hide."""
    out = []
    for r in reqs:
        if 0 <= r.due < seconds:
            first = r.token_t[0] if r.token_t else None
            end = first if first is not None and first <= seconds \
                else seconds
            out.append(end - r.due)
    return out


def mean_step_ms(steps) -> Optional[float]:
    """Mean host-clock time of ``steps`` in ms; None when they span less
    than :data:`MIN_SPAN_S` together."""
    span = sum(s.t1 - s.t0 for s in steps)
    if not steps or span < MIN_SPAN_S:
        return None
    return span / len(steps) * 1e3
