"""The one generator of sweep traffic; a traffic file gives its numbers.

A closed loop of sweeps, back to back in one process.  Sweep ``i`` of a
run with seed ``n`` scales the fields the traffic file names of every
kernel report by one factor, drawn log-uniformly from the file's range
with a generator seeded by ``(n, i)``: no two sweeps of a run simulate the
same inputs, so no result, dispatch order or device-resident input block
of an earlier sweep can serve a later one.  Warm-up uses the file's own
fixed factors, which the window's draws never repeat.
"""
from __future__ import annotations

import math
from typing import Dict, Iterator

import numpy as np


class Traffic:
    def __init__(self, doc: Dict, seed: int):
        cf = doc["cost_factor"]
        if cf["dist"] != "loguniform":
            raise ValueError(f"unknown cost factor distribution "
                             f"{cf['dist']!r}")
        self.lo, self.hi = float(cf["lo"]), float(cf["hi"])
        self.fields = tuple(doc["scaled_fields"])
        self.warmup = doc["warmup"]
        self.check = doc["check"]
        # any whole number, negative or past 64 bits included
        self.seed = int(seed) % (1 << 64)

    def factor(self, i: int) -> float:
        u = np.random.default_rng([self.seed, i]).random()
        return math.exp(math.log(self.lo)
                        + u * (math.log(self.hi) - math.log(self.lo)))

    def warmup_factors(self) -> Iterator[float]:
        fs = self.warmup["factors"]
        for i in range(int(self.warmup["max_sweeps"])):
            yield float(fs[i % len(fs)])
