"""Open-loop arrival schedules, in wall-clock seconds, from a mix's file.

One general generator reads every traffic file (``bench/traffic/<mix>.json``).
A mix gives:

* ``rate_rps``: requests per second offered, over all tenants;
* ``lead_in_s``: seconds of the same schedule before the window opens, so
  that the window opens on the queue and slots the schedule itself fills;
* ``tail_s``: seconds of the schedule after the window closes, served while
  the harness waits for the first token of every request due in the window;
* ``prompt_len`` / ``output_len``: a lognormal given by its ``median`` and
  ``sigma``, clipped to [``min``, ``max``];
* ``cycle_s``: the period of the schedule (below), the cell's window length;
* ``pattern_seed``: the seed of the cycle's arrangement, part of the mix.

The rate is split evenly over the configuration's tenants.  ``arrivals`` is
``poisson`` (open loop) or ``backlog`` (every request due at the start of
the lead-in: a saturating queue, for finding the knee).

Every seed gets the same work in another order.  Each tenant's stream is a
cycle of ``cycle_s`` seconds repeated without end: ``round(rate *
cycle_s)`` arrivals whose gaps are the stratified quantiles of the
exponential at the tenant's rate, scaled to fill the cycle exactly, and
whose prompt and output lengths are the stratified quantiles of their
lognormals, each list in an order drawn once from ``pattern_seed``.  Within
a cycle the arrivals bunch and thin out as a Poisson stream's do, bursts
included.  The run's seed picks the arrival of the cycle that opens the
window (the window opens halfway through the gap before it) and draws the
token ids.  So a window of ``cycle_s`` seconds holds every arrival of the
cycle once, on every seed, in a rotated order; and the lead-in, the end of
the previous cycle, fills the queue and the slots as the cycle itself does.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    due: float                  # seconds from the window's start
    tenant: str
    prompt: np.ndarray          # int32 token ids
    max_new: int


@dataclasses.dataclass(frozen=True)
class Schedule:
    arrivals: Tuple[Arrival, ...]                    # sorted by due time
    start: float                # first due time (minus the lead-in)
    end: float                  # last time the schedule covers


def stratified_lognormal(n: int, spec: dict) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of the clipped
    lognormal, ascending."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def stratified_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at the quantiles (i + 0.5) / n,
    ascending."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _rng(*key: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(k) for k in key]))


def cycle(mix: dict, rate: float, stream: int
          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """One tenant's cycle: (due times in [0, cycle_s), the gap before each,
    prompt lengths, output lengths), fixed by the mix alone."""
    period = float(mix["cycle_s"])
    n = int(round(rate * period))
    rng = _rng(mix["pattern_seed"], stream)
    gaps = rng.permutation(stratified_gaps(n, rate))
    gaps *= period / gaps.sum()
    due = np.cumsum(gaps) - gaps[0]
    plen = rng.permutation(stratified_lognormal(n, mix["prompt_len"]))
    olen = rng.permutation(stratified_lognormal(n, mix["output_len"]))
    return due, gaps, plen, olen


def generate(mix: dict, tenants: Sequence[str], vocab: int, seed: int,
             seconds: float) -> Schedule:
    """The schedule of one run: lead-in, ``seconds`` of window, tail."""
    lead, tail = float(mix["lead_in_s"]), float(mix["tail_s"])
    rate = mix["rate_rps"] / len(tenants)
    timed: List[Tuple[float, str, int, int]] = []
    for i, tenant in enumerate(tenants):
        if mix["arrivals"] == "backlog":
            n = int(mix["backlog"] / len(tenants))
            order = _rng(seed, i)
            plen = order.permutation(stratified_lognormal(n, mix["prompt_len"]))
            olen = order.permutation(stratified_lognormal(n, mix["output_len"]))
            timed += [(-lead, tenant, int(p), int(o))
                      for p, o in zip(plen, olen)]
            continue
        due, gaps, plen, olen = cycle(mix, rate, i)
        period = float(mix["cycle_s"])
        first = int(_rng(seed, i).integers(len(due)))
        offset = due[first] - gaps[first] / 2
        k0 = int(np.floor((offset - lead) / period)) - 1
        k1 = int(np.ceil((offset + seconds + tail) / period)) + 1
        for k in range(k0, k1):
            for d, p, o in zip(k * period + due - offset, plen, olen):
                if -lead <= d < seconds + tail:
                    timed.append((float(d), tenant, int(p), int(o)))
    timed.sort(key=lambda a: (a[0], a[1]))
    ids = _rng(seed, 1 << 20)
    arrivals = tuple(Arrival(d, tenant, ids.integers(0, vocab, size=p,
                                                     dtype=np.int32), o)
                     for d, tenant, p, o in timed)
    return Schedule(arrivals, -lead, seconds + tail)
