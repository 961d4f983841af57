"""The one traffic generator: reads a traffic mix's parameters and makes
its requests from ``--seed``.

Two arrival kinds:

* ``poisson``, an open loop in three stretches: the warm-up before the
  window, the window, and a short tail past it.  Each stretch holds
  ``round(rate * length)`` requests, due at times drawn uniformly from
  ``--seed`` over the stretch and sorted: a Poisson process given its
  count, with its bursts and lulls.  Their prompt and output lengths are
  one i.i.d. draw of the mix's clipped lognormals per stretch, made by a
  fixed generator, in an order drawn from ``--seed``.
* ``closed``, a closed loop of ``clients`` clients that each wait for
  their reply: at the warm-up's start every client has one request due,
  and when a request finishes (:meth:`Traffic.finish`), or its submit
  fails, its client's next request falls due ``think_s`` later.  Lengths
  are drawn by the fixed generator in blocks of :data:`BLOCK` requests,
  each block in an order drawn from ``--seed``, and handed out in order
  of issue.

Either way every seed gets the same work, at other times and in another
order.  Token ids are drawn from ``--seed``, in order of issue.

Parameters of a mix (``bench/traffic/<name>.json``)::

    "arrivals": {"kind": "poisson", "rate_per_s": r}
             or {"kind": "closed", "clients": n, "think_s": t}
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
#: requests per block of lengths in a closed loop
BLOCK = 64


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
    client: Optional[int] = None      # closed loop: the client that sent it


def lognormal_sizes(p: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` i.i.d. draws of a clipped lognormal, as ints."""
    x = np.exp(math.log(p["median"]) + p["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), p["min"], p["max"]).astype(np.int64)


class Traffic:
    """Requests of one mix for one seed, handed out as they fall due."""

    def __init__(self, mix: dict, vocab: int, seed: int, seconds: float):
        arr = mix["arrivals"]
        self.kind = arr["kind"]
        self.warmup_s = float(mix["warmup_s"])
        self.reqs: List[Req] = []
        self._mix, self._vocab = mix, vocab
        self._rng = np.random.default_rng(seed)
        if self.kind == "poisson":
            self._open_loop(arr["rate_per_s"], seconds)
            self._next = list(self.reqs)
        elif self.kind == "closed":
            self._think_s = float(arr["think_s"])
            self._sizes: List[tuple] = []
            self._next = [self._issue(c, -self.warmup_s)
                          for c in range(int(arr["clients"]))]
        else:
            raise ValueError(f"unknown arrival kind {self.kind!r}")

    def _open_loop(self, rate: float, seconds: float) -> None:
        rng, mix = self._rng, self._mix
        stretches = ((-self.warmup_s, self.warmup_s), (0.0, seconds),
                     (seconds, TAIL_S))
        for k, (start, length) in enumerate(stretches):
            n = max(1, round(rate * length))
            fixed = np.random.default_rng((SIZES_SEED, k))
            plens = rng.permutation(lognormal_sizes(mix["prompt"], n, fixed))
            olens = rng.permutation(lognormal_sizes(mix["output"], n, fixed))
            due = np.sort(start + rng.uniform(0.0, length, n))
            self.reqs += [Req(idx=len(self.reqs) + i,
                              prompt=rng.integers(1, self._vocab,
                                                  int(p)).tolist(),
                              max_new=int(o), due=float(d))
                          for i, (p, o, d) in enumerate(
                              zip(plens, olens, due))]

    def _issue(self, client: int, due: float) -> Req:
        """The closed loop's next request, sent by ``client`` at ``due``."""
        if not self._sizes:
            k = len(self.reqs) // BLOCK
            fixed = np.random.default_rng((SIZES_SEED, k))
            plens = lognormal_sizes(self._mix["prompt"], BLOCK, fixed)
            olens = lognormal_sizes(self._mix["output"], BLOCK, fixed)
            order = self._rng.permutation(BLOCK)
            self._sizes = [(int(plens[i]), int(olens[i])) for i in order]
        p, o = self._sizes.pop(0)
        r = Req(idx=len(self.reqs),
                prompt=self._rng.integers(1, self._vocab, p).tolist(),
                max_new=o, due=due, client=client)
        self.reqs.append(r)
        return r

    def finish(self, req: Req, t: float) -> None:
        """``req`` finished, or its submit failed, at ``t`` (seconds from
        window open): in a closed loop its client's next request falls
        due ``think_s`` later."""
        if self.kind == "closed":
            nxt = self._issue(req.client, t + self._think_s)
            at = next((i for i, r in enumerate(self._next)
                       if r.due > nxt.due), len(self._next))
            self._next.insert(at, nxt)

    def pop_due(self, now: float) -> List[Req]:
        """Requests due at or before ``now`` (seconds from window open)."""
        out = []
        while self._next and self._next[0].due <= now:
            out.append(self._next.pop(0))
        return out

    def next_due(self) -> Optional[float]:
        return self._next[0].due if self._next else None
