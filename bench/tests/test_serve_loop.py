"""The client loop counts every request due in the window, also one whose
due time falls inside a step that runs past the window's close."""
import time

import numpy as np

from harness import serve_loop
from harness.traffic import Arrival, Schedule

STEP_S = 0.4


class _Engine:
    def __init__(self):
        self.queue_depth = 0
        self.busy_steps = 0

    @property
    def has_work(self):
        return self.queue_depth > 0 or self.busy_steps > 0


class _Server:
    """Admits its whole queue at each step, which takes ``STEP_S``."""

    def __init__(self):
        self.engines = {"t": _Engine()}
        self.rids = 0

    def submit(self, tenant, prompt, max_new_tokens):
        eng = self.engines[tenant]
        eng.queue_depth += 1
        eng.busy_steps = 3
        self.rids += 1
        return self.rids

    def step(self):
        time.sleep(STEP_S)
        eng = self.engines["t"]
        eng.queue_depth = 0
        eng.busy_steps -= 1
        return {}


def test_request_due_during_the_last_step_is_counted():
    prompt = np.zeros(4, np.int32)
    # steps run 0-0.4, 0.4-0.8, 0.8-1.2: the second arrival falls due inside
    # the third, which ends after the one-second window has closed
    sched = Schedule((Arrival(0.0, "t", prompt, 4),
                      Arrival(0.85, "t", prompt, 4)), 0.0, 3.0)
    run = serve_loop.serve(_Server(), sched, 1.0)
    w0, w1 = run.window
    due = [r for r in run.records if w0 <= r.due < w1]
    assert len(due) == 2
    assert all(r.times for r in due)
    late = max(due, key=lambda r: r.due)
    assert late.first - late.due > 1.2 - 0.85
