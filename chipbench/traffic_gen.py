"""The one general generator of serving traffic.  A mix is a data file
of parameters under ``chipbench/traffic/``; this reads it.

Every seed gets the SAME requests at the same moments: the quantile
grid of the mix's length distributions (``shapes`` of them), paired by
one fixed shuffle and replayed in one fixed order (reshuffled each time
round by the same fixed generator).  The seed draws the token ids (and,
elsewhere, the weights), so two runs do the same work.  Why the order is
not the seed's: ``traffic/rollout16.json`` -> ``what``.
"""

from __future__ import annotations

import math

import numpy as np


def _grid(dist: dict, n: int) -> np.ndarray:
    """``n`` values at the mid-quantiles of a length distribution."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = float(dist["low"]), float(dist["high"])
    if dist["dist"] == "uniform":
        vals = lo + q * (hi - lo)
    elif dist["dist"] == "log_uniform":
        vals = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    else:
        raise SystemExit(f"chipbench: unknown length distribution "
                         f"{dist['dist']!r}")
    return np.clip(np.round(vals), lo, hi).astype(np.int64)


def shapes(traffic: dict):
    """The fixed multiset of (prompt_len, output_len), the same for
    every seed."""
    n = int(traffic["shapes"])
    prompts = _grid(traffic["prompt_len"], n)
    outputs = _grid(traffic["output_len"], n)
    pairing = np.random.default_rng(0).permutation(n)
    return list(zip(prompts.tolist(), outputs[pairing].tolist()))


class Requests:
    """An endless stream of requests: the shapes in the fixed order,
    token ids from the seed."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self._shapes = shapes(traffic)
        self._ids_rng = np.random.default_rng(seed)
        self._order_rng = np.random.default_rng(0)
        self._vocab = vocab
        self._order = []
        self.issued = 0

    def next(self):
        if not self._order:
            self._order = self._order_rng.permutation(
                len(self._shapes)).tolist()
        prompt_len, out_len = self._shapes[self._order.pop()]
        ids = self._ids_rng.integers(1, self._vocab, size=prompt_len)
        self.issued += 1
        return ids.tolist(), int(out_len)

    @property
    def longest(self):
        return (max(p for p, _ in self._shapes),
                max(o for _, o in self._shapes))
