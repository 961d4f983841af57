"""The one traffic generator: reads a traffic mix's parameters and makes
its requests from ``--seed``.

An open loop in three stretches: the warm-up before the window, the
window, and a short tail past it.  Each stretch holds ``round(rate *
length)`` requests, due at times drawn uniformly from ``--seed`` over the
stretch and sorted: a Poisson process given its count, with its bursts
and lulls.  Their prompt and output lengths are one i.i.d. draw of the
mix's clipped lognormals per stretch, made by a fixed generator, in an
order drawn from ``--seed``: every seed's window holds the same work,
at other times and in another order.  Token ids are drawn from
``--seed``.

Parameters of a mix (``bench/traffic/<name>.json``)::

    "arrivals": {"kind": "poisson", "rate_per_s": r}
    "prompt":  {"median": m, "sigma": s, "min": lo, "max": hi}   lognormal
    "output":  {"median": m, "sigma": s, "min": lo, "max": hi}   lognormal
    "warmup_s": w                 traffic served before the window opens
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import numpy as np

#: arrivals keep coming this long past the window's close
TAIL_S = 2.0
#: seed of the fixed generator that draws every stretch's lengths
SIZES_SEED = 20230901


@dataclasses.dataclass
class Req:
    """One request as the client sees it."""

    idx: int
    prompt: List[int]
    max_new: int
    due: float                        # seconds from window open
    rid: Optional[int] = None         # engine's id once submitted
    submit_t: Optional[float] = None
    token_t: List[float] = dataclasses.field(default_factory=list)
    output: List[int] = dataclasses.field(default_factory=list)
    done_t: Optional[float] = None
    failed: bool = False


def lognormal_sizes(p: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` i.i.d. draws of a clipped lognormal, as ints."""
    x = np.exp(math.log(p["median"]) + p["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), p["min"], p["max"]).astype(np.int64)


class Traffic:
    """Requests of one mix for one seed, handed out as they fall due."""

    def __init__(self, mix: dict, vocab: int, seed: int, seconds: float):
        arr = mix["arrivals"]
        if arr["kind"] != "poisson":
            raise ValueError(f"unknown arrival kind {arr['kind']!r}")
        rng = np.random.default_rng(seed)
        self.warmup_s = float(mix["warmup_s"])
        self.reqs: List[Req] = []
        stretches = ((-self.warmup_s, self.warmup_s), (0.0, seconds),
                     (seconds, TAIL_S))
        for k, (start, length) in enumerate(stretches):
            n = max(1, round(arr["rate_per_s"] * length))
            fixed = np.random.default_rng((SIZES_SEED, k))
            plens = rng.permutation(lognormal_sizes(mix["prompt"], n, fixed))
            olens = rng.permutation(lognormal_sizes(mix["output"], n, fixed))
            due = np.sort(start + rng.uniform(0.0, length, n))
            self.reqs += [Req(idx=len(self.reqs) + i,
                              prompt=rng.integers(1, vocab, int(p)).tolist(),
                              max_new=int(o), due=float(d))
                          for i, (p, o, d) in enumerate(
                              zip(plens, olens, due))]
        self._next = list(self.reqs)

    def pop_due(self, now: float) -> List[Req]:
        """Requests due at or before ``now`` (seconds from window open)."""
        out = []
        while self._next and self._next[0].due <= now:
            out.append(self._next.pop(0))
        return out

    def next_due(self) -> Optional[float]:
        return self._next[0].due if self._next else None
