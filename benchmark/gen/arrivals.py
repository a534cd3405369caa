"""Open-loop arrival schedules: WHEN requests are due.

Arrivals are a property of the world, not of the system under test, so
every schedule here is a list of ABSOLUTE due offsets computed up front
from (parameters, seed, duration) — a pure function, which a test can
assert without sleeping.  The driver fires at those instants whether or
not earlier requests completed, and times each request from its due
time.

  fixed_gaps  Poisson-shaped gaps at a fixed rate, the SAME multiset of
              gaps for every seed, in an order the seed draws — so every
              seed offers the same number of requests over the same
              span, and the seed does not change the work
  constant    homogeneous Poisson, drawn from the seed
  ramp        linear ramp from start_rate to end_rate, then hold: the
              saturation probe (`sweep.py`)

`ArrivalProcess`, `ConstantArrivals` and `RampArrivals` are copied from
`fabric_tpu/workload/arrivals.py` (Lewis-Shedler thinning, one seeded
PRNG, two draws per candidate in time order).
"""

from __future__ import annotations

import math
import random
from typing import List


class ArrivalProcess:
    """Base: a deterministic rate profile rate(t) thinned at max_rate."""

    def __init__(self, seed: int = 0):
        self.seed = int(seed)

    def rate(self, t: float) -> float:
        raise NotImplementedError

    def max_rate(self) -> float:
        raise NotImplementedError

    def schedule(self, duration_s: float) -> List[float]:
        """Absolute due offsets in [0, duration_s), ascending."""
        lam = float(self.max_rate())
        if lam <= 0.0 or duration_s <= 0.0:
            return []
        rnd = random.Random(self.seed)
        out: List[float] = []
        t = 0.0
        while True:
            t += rnd.expovariate(lam)
            if t >= duration_s:
                return out
            if rnd.random() * lam < self.rate(t):
                out.append(t)


class ConstantArrivals(ArrivalProcess):
    """Homogeneous Poisson: constant offered rate, memoryless gaps."""

    def __init__(self, rate: float, seed: int = 0):
        super().__init__(seed)
        self._rate = float(rate)

    def rate(self, t: float) -> float:
        return self._rate

    def max_rate(self) -> float:
        return self._rate


class RampArrivals(ArrivalProcess):
    """Linear ramp from start_rate to end_rate over ramp_s, then hold."""

    def __init__(self, start_rate: float, end_rate: float,
                 ramp_s: float = 10.0, seed: int = 0):
        super().__init__(seed)
        self.start_rate = float(start_rate)
        self.end_rate = float(end_rate)
        self.ramp_s = float(ramp_s)

    def rate(self, t: float) -> float:
        if self.ramp_s <= 0.0 or t >= self.ramp_s:
            return self.end_rate
        f = t / self.ramp_s
        return self.start_rate + f * (self.end_rate - self.start_rate)

    def max_rate(self) -> float:
        return max(self.start_rate, self.end_rate)


def fixed_gaps(rate: float, seed: int, duration_s: float) -> List[float]:
    """round(rate * duration) arrivals whose gaps are the exponential
    distribution's quantiles at mean 1/rate — the same gaps for every
    seed — laid end to end in an order drawn from the seed, and scaled
    so that the last arrival falls one mean gap before the window's
    end."""
    n = int(round(rate * duration_s))
    if n <= 0:
        return []
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    random.Random(seed).shuffle(gaps)
    scale = duration_s * n / ((n + 1) * sum(gaps))
    out, t = [], 0.0
    for g in gaps:
        t += g * scale
        out.append(t)
    return out


def schedule(spec: dict, seed: int, duration_s: float) -> List[float]:
    """{"kind": "fixed_gaps", "rate": 9} -> due offsets."""
    kind = spec["kind"]
    if kind == "fixed_gaps":
        return fixed_gaps(float(spec["rate"]), seed, duration_s)
    if kind == "constant":
        return ConstantArrivals(float(spec["rate"]),
                                seed).schedule(duration_s)
    if kind == "ramp":
        return RampArrivals(float(spec["start_rate"]),
                            float(spec["end_rate"]),
                            float(spec.get("ramp_s", duration_s)),
                            seed).schedule(duration_s)
    raise ValueError(f"unknown arrival kind {kind!r}")
