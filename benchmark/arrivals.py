"""The open-loop arrival schedule of a serve window: Poisson gaps at the
rate the traffic file fixes, the same set of gaps for every run, put in an
order drawn from ``--seed``.

A fresh Poisson draw per seed would offer 2,750 +- 52 requests in a 25 s
window at 110 a second, and the tails follow the offered load (the sweep in
PERF.md section 6: about 1.5 % of the 95th percentile for that +- 1.9 %), so
the seed would change the work; the benchmark's contract asks that every
seed get the same arrivals in another order."""

from __future__ import annotations

import numpy as np


def arrivals_for_window(rate: float, seconds: float, seed: int,
                        base_seed: int = 0) -> np.ndarray:
    """``int(rate * seconds)`` arrivals inside ``[0, seconds)``: ONE set of
    exponential gaps (drawn from ``base_seed``, the same for every run), put
    in an order drawn from ``seed`` and scaled to fill the window. Every
    seed thus offers the same number of requests and the same set of gaps,
    with its bursts in other places."""
    n = int(rate * seconds)
    if n < 1:
        raise ValueError(f"rate {rate} x {seconds} s offers no request")
    gaps = np.random.default_rng(base_seed).exponential(1.0 / rate, size=n)
    gaps = gaps[np.random.default_rng(seed).permutation(n)]
    return np.cumsum(gaps) * (seconds / (gaps.sum() + 1.0 / rate))
