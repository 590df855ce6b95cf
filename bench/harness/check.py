"""The comparison that decides ``correct``.

After the window closes, every sweep of the window is held against the
plain reference (:mod:`harness.reference`) on the same inputs:

* every candidate's outcome: infeasible exactly when its fabric exceeds
  the budget, ranked otherwise (nothing pruned, failed or missing);
* the sweep ran on the jax engine throughout (no demotion);
* the whole ranking: every ranked candidate, in the order of the
  sweep's own makespans (ties in the order the candidates were given);
* for a sample of the ranked candidates drawn from the seed: the
  makespan, the busy time of every pool and the device of every compute
  task, and the order in which the sweep ranks them.  Each sweep's best
  ones are always in it.  The first ``cover`` sweeps of a run split the
  feasible candidates between them, in an order drawn from the seed, so a
  window of that many sweeps or more compares every candidate, and every
  lane the scan completes, at least once; later sweeps add ``random``
  candidates drawn from the seed.

The gaps fold into one number, ``max_gap``: the largest relative gap of a
makespan or a busy time, the share of compute tasks placed elsewhere, the
share of positions ranked differently, and 1 for any outcome, engine or
result that is wrong or missing.  The configurations state binary64 times
equal to the reference's bit for bit, so the limit is 0.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Sequence

import numpy as np

from .problem import Problem, Sweep
from .reference import Reference

#: The one compared number's limit: an exact comparison.
LIMITS = {"max_gap": 0.0}


@dataclasses.dataclass
class Gaps:
    """The largest gap of each kind, and where it was read."""

    makespan: float = 0.0
    busy: float = 0.0
    placement: float = 0.0
    rank: float = 0.0
    outcome: float = 0.0
    lanes: int = 0
    covered: int = 0
    where: str = ""

    def worst(self) -> float:
        return max(self.makespan, self.busy, self.placement, self.rank,
                   self.outcome)

    def note(self, kind: str, value: float, where: str) -> None:
        if value > getattr(self, kind):
            setattr(self, kind, value)
            if value >= self.worst():
                self.where = f"{kind} {where}"


def sample(seed: int, index: int, ranked: Sequence[str],
           feasible: Sequence[str], check: Dict[str, int]) -> List[str]:
    """The candidates of sweep ``index`` that the check reads: the sweep's
    ``top`` best, then its share of the feasible candidates while ``index``
    is below ``cover``, else ``random`` of them drawn from the seed."""
    head = list(ranked[:check["top"]])
    cover = check["cover"]
    if index < cover:
        perm = np.random.default_rng([seed, 0x5EED]).permutation(
            len(feasible))
        picked = [feasible[j] for j in sorted(perm[index::cover])]
    else:
        rng = np.random.default_rng([seed, index, 0x5EED])
        pick = rng.choice(len(feasible), size=min(check["random"],
                                                  len(feasible)),
                          replace=False)
        picked = [feasible[j] for j in sorted(pick)]
    return head + [n for n in picked if n not in head]


def _rel(got: float, want: float) -> float:
    if got == want:
        return 0.0
    if want == 0.0:
        return 1.0
    return abs(got - want) / abs(want)


def compare(problem: Problem, fields: Sequence[str], sweeps: Sequence,
            samples: Sequence[Sequence[str]], num: Callable = float) -> Gaps:
    """``sweeps[i]`` is ``(factor, Sweep)``; ``samples[i]`` the names the
    check reads in it."""
    gaps = Gaps()
    seen = set()
    order = {c.name: i for i, c in enumerate(problem.cands)}
    by_name = {c.name: c for c in problem.cands}
    want_status = {c.name: "ok" if problem.feasible(c) else "infeasible"
                   for c in problem.cands}
    for i, ((factor, sw), names) in enumerate(zip(sweeps, samples)):
        at = f"sweep {i}"
        if sw.engine != "jax" or sw.demotions:
            gaps.note("outcome", 1.0, f"{at}: engine {sw.engine}, "
                                      f"{sw.demotions} demotion(s)")
        wrong = [n for n, s in want_status.items()
                 if sw.statuses.get(n) != s]
        if wrong:
            gaps.note("outcome", 1.0, f"{at}: {len(wrong)} outcome(s) "
                                      f"wrong, e.g. {wrong[0]}")
        ok = [n for n, st in sw.statuses.items() if st == "ok"]
        if set(ok) <= set(sw.makespans):
            whole = sorted(ok, key=lambda n: (sw.makespans[n], order[n]))
            off = sum(1 for a, b in zip(sw.ranked, whole) if a != b)
            if len(sw.ranked) != len(whole):
                off = max(off, 1)
            gaps.note("rank", off / max(len(whole), 1), f"{at} (whole)")
        else:
            gaps.note("outcome", 1.0, f"{at}: a ranked candidate without "
                                      f"a makespan")
        ref = Reference(problem, problem.scaled_reports(factor, fields), num)
        spans = {}
        for name in names:
            r = ref.run(by_name[name])
            spans[name] = r.makespan
            gaps.lanes += 1
            where = f"{at} {name}"
            sim = sw.sims.get(name)
            got = sw.makespans.get(name)
            if sim is None or got is None:
                gaps.note("outcome", 1.0, f"{where}: no result")
                continue
            gaps.note("makespan", _rel(got, r.makespan), where)
            if set(sim.busy) != set(r.busy):
                gaps.note("busy", 1.0, f"{where}: pools {sorted(sim.busy)}")
            else:
                for pool, b in r.busy.items():
                    gaps.note("busy", _rel(sim.busy[pool], b),
                              f"{where} {pool}")
            moved = sum(1 for u, k in r.placements.items()
                        if sim.placements.get(u) != k)
            if len(sim.placements) != len(r.placements):
                moved = max(moved, 1)
            gaps.note("placement", moved / max(len(r.placements), 1), where)
        seen.update(spans)
        got_order = [n for n in sw.ranked if n in spans]
        want_order = sorted(spans, key=lambda n: (spans[n], order[n]))
        off = sum(1 for a, b in zip(got_order, want_order) if a != b)
        if len(got_order) != len(want_order):
            off = max(off, 1)
        gaps.note("rank", off / max(len(want_order), 1), at)
    gaps.covered = len(seen)
    return gaps


def readings(gaps: Gaps) -> Dict[str, Dict[str, float]]:
    """The compared numbers beside their limits, as the result line and
    the last lines of standard error give them."""
    return {"max_gap": {"value": gaps.worst(), "limit": LIMITS["max_gap"]}}


def correct(gaps: Gaps) -> bool:
    return all(v["value"] <= v["limit"] for v in readings(gaps).values())


def control(problem: Problem, traffic, n_sweeps: int) -> Gaps:
    """The control's reading: the reference computed in binary32, put in
    the program's place for ``n_sweeps`` sweeps of the traffic, sampled
    and compared as a run's sweeps are."""
    order = {c.name: j for j, c in enumerate(problem.cands)}
    feasible = [c for c in problem.cands if problem.feasible(c)]
    sweeps, samples = [], []
    for i in range(n_sweeps):
        f = traffic.factor(i)
        ref32 = Reference(problem, problem.scaled_reports(f, traffic.fields),
                          np.float32)
        res = {c.name: ref32.run(c) for c in feasible}
        ranked = sorted(res, key=lambda n: (res[n].makespan, order[n]))
        samples.append(sample(traffic.seed, i, ranked,
                              [c.name for c in feasible], traffic.check))
        sweeps.append((f, Sweep(
            statuses={c.name: "ok" if c.name in res else "infeasible"
                      for c in problem.cands},
            makespans={n: r.makespan for n, r in res.items()},
            ranked=ranked, sims=res, engine="jax", demotions=0,
            lockstep_lanes=0, simulated=len(res))))
    return compare(problem, traffic.fields, sweeps, samples)
