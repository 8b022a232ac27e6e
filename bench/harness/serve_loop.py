"""The timed path: an open-loop client driving ``ComposedServer`` in one
thread, every token stamped on the host clock.

Each pass of the loop (1) submits every request whose due time has passed,
(2) calls ``server.step()`` and stamps what it returned.  When the server
has no work it sleeps until the next due time.  Each of these calls is
wrapped in a ``jax.profiler.TraceAnnotation`` (``bench.submit``,
``bench.step``, ``bench.wait``), so that in a traced run a gap on the device
can be put down to what the host was doing.

``step()`` returns the decode tokens; a request's first token comes from its
prefill, which runs inside the ``step()`` that admits it.  Admission is
first-in first-out, so the requests admitted by a step are the oldest
``queued_before - queue_depth`` of that tenant's queue, and their first
token is stamped when that ``step()`` returns.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import numpy as np

from harness.traffic import Schedule

clock = time.perf_counter


@dataclasses.dataclass
class Record:
    """One request as the client saw it (times on ``clock``)."""

    tenant: str
    rid: int
    due: float
    prompt: np.ndarray
    max_new: int
    submit: float = math.nan
    times: List[float] = dataclasses.field(default_factory=list)

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def first(self) -> float:
        return self.times[0] if self.times else math.nan

    @property
    def finished(self) -> bool:
        return len(self.times) >= self.max_new


@dataclasses.dataclass
class Run:
    records: List[Record]
    window: tuple                  # (start, end) on clock


def annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


def serve(server, schedule: Schedule, seconds: float,
          hooks: Sequence[Tuple[float, Callable[[], None]]] = ()) -> Run:
    """Run ``schedule`` against ``server``: lead-in, a window of
    ``seconds``, then serve on until every request due in the window has
    its first token (at most the schedule's tail).  Each hook (t, fn) runs
    once, as the loop passes t seconds from the window's start."""
    origin = clock() - schedule.start      # window start on the clock
    w0, w1 = origin, origin + seconds
    stop_at = origin + schedule.end
    pending = collections.deque(sorted(hooks, key=lambda h: h[0]))
    arrivals = collections.deque(schedule.arrivals)
    waiting: Dict[str, collections.deque] = {
        t: collections.deque() for t in server.engines}
    records: Dict[tuple, Record] = {}
    in_window: List[Record] = []
    while True:
        now = clock()
        while pending and now >= origin + pending[0][0]:
            pending.popleft()[1]()
        # submit before the look at the end, so that a request due in the
        # window is counted even when a step runs past the window's close
        while arrivals and origin + arrivals[0].due <= now:
            a = arrivals.popleft()
            with annotate("bench.submit"):
                rid = server.submit(a.tenant, a.prompt,
                                    max_new_tokens=a.max_new)
            rec = Record(a.tenant, rid, origin + a.due, a.prompt, a.max_new,
                         submit=clock())
            records[(a.tenant, rid)] = rec
            waiting[a.tenant].append(rec)
            if w0 <= rec.due < w1:
                in_window.append(rec)
        if now >= w1 and (now >= stop_at or all(r.times for r in in_window)):
            break
        if any(e.has_work for e in server.engines.values()):
            with annotate("bench.step"):
                out = server.step()
            t = clock()
            for tenant, q in waiting.items():
                admitted = len(q) - server.engines[tenant].queue_depth
                for _ in range(admitted):
                    q.popleft().times.append(t)
                for rid, _tok in out.get(tenant, ()):
                    records[(tenant, rid)].times.append(t)
            continue
        nxt = [stop_at, w1 if now < w1 else math.inf]
        if pending:
            nxt.append(origin + pending[0][0])
        if arrivals:
            nxt.append(origin + arrivals[0].due)
        with annotate("bench.wait"):
            time.sleep(max(min(nxt) - clock(), 0.0))
    return Run(list(records.values()), (w0, w1))
