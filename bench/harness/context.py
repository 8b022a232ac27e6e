"""What a per-layer metric reader is handed: the run's records, the
program's counters over the window, the reduced trace and the yardstick's
tables."""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from harness import spec


@dataclasses.dataclass
class Context:
    config: dict
    records: list                      # serve_loop.Record
    window: Tuple[float, float]        # (start, end) on the client's clock
    # program counters over the window: histogram name -> (count, sum),
    # and "builds" -> programs built
    host: Dict[str, object]
    peaks: Dict[str, float]
    trace: Optional[object] = None     # trace.reduce.Trace

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    def due_in_window(self) -> List:
        return [r for r in self.records if self.in_window(r.due)]

    def decode_kv_lengths(self, interval: Tuple[float, float]) -> List[int]:
        """KV rows attended by each decode token returned in ``interval``:
        a request's k-th token after its first (k >= 1) comes from a decode
        step over prompt + k rows."""
        lo, hi = interval
        return [r.prompt_len + k for r in self.records
                for k, t in enumerate(r.times) if k and lo <= t < hi]

    def roofline(self, kernel: str):
        return spec.roofline(kernel)
