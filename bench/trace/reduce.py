"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the benchmark's device
numbers.

Read with ``jax.profiler.ProfileData`` (JAX alone, no TensorBoard).  A TPU
trace holds one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops`` line
has one event per operation the chip ran, and whose ``XLA Modules`` line has
one event per program execution; the host plane (``/host:CPU``) holds the
client's ``jax.profiler.TraceAnnotation`` spans (``bench.step``,
``bench.submit``, ``bench.wait``, ``bench.recompose``) on its Python
thread, on the same clock.

Op events nest: a layer loop's ``while`` op spans the ops of its body.
From these:

* busy: the union of a chip's op intervals; idle share is 1 - busy /
  window, the window being the traced span;
* time per operation, of the innermost ops only (so nothing counts twice),
  named by the HLO instruction and its result shape, summed over chips;
* time per Pallas kernel (``tpu_custom_call`` ops, named by the kernel),
  and time of the program executions that ran each kernel;
* each idle gap on a chip put down to the client span open at its middle
  and, inside it, the innermost host event then running.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
CLIENT_PREFIX = "bench."
CUSTOM_CALL = "tpu_custom_call"
TOP = 10


@dataclasses.dataclass
class Trace:
    window_s: float                   # traced span
    busy_s: float                     # busy union, mean over chips
    host_interval: Tuple[float, float]   # the traced span on the client clock
    devices: int
    ops: Dict[str, float]             # innermost op -> seconds, over chips
    kernels: Dict[str, float]         # Pallas kernel -> seconds, over chips
    # Pallas kernel -> seconds of the program executions that ran it
    programs_with: Dict[str, float]
    gaps: Dict[str, float]            # host activity -> idle seconds, over chips

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of the Pallas kernel ``kernel``."""
        return self.kernels.get(kernel, 0.0)

    def program_s(self, kernel: str) -> float:
        """Device seconds of the program executions that ran ``kernel``."""
        return self.programs_with.get(kernel, 0.0)

    def breakdown(self) -> Dict[str, List]:
        top = lambda d: [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:TOP]]
        return {"device_ops": top(self.ops), "idle_gaps": top(self.gaps)}


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class _HostSpans:
    """The client's spans and the host events on its thread, for finding
    what the host did at an instant."""

    def __init__(self, events: Sequence[Tuple[float, float, str]]):
        self.client = sorted(e for e in events
                             if e[2].startswith(CLIENT_PREFIX))
        self.inner = sorted(e for e in events
                            if not e[2].startswith(CLIENT_PREFIX))
        self._starts = [e[0] for e in self.inner]

    def at(self, t: float) -> str:
        outer = next((n for a, b, n in self.client if a <= t < b), None)
        if outer is None:
            return "outside client spans"
        i = bisect.bisect_right(self._starts, t)
        best: Optional[Tuple[float, str]] = None
        for a, b, n in self.inner[max(0, i - 64):i]:
            if a <= t < b and (best is None or b - a < best[0]):
                best = (b - a, n)
        return outer if best is None else f"{outer} > {best[1]}"


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def short_name(hlo: str) -> str:
    """``%copy.100 = bf16[32,12]{1,0:T(8,128)} copy(...)`` ->
    ``copy.100 bf16[32,12]``; a tuple result is written ``(tuple)``."""
    lhs, _, rhs = hlo.partition(" = ")
    if not rhs:
        return hlo[:120]
    shape = "(tuple)" if rhs.startswith("(") else re.sub(
        r"\{[^}]*\}", "", rhs.split(" ", 1)[0])
    return f"{lhs.lstrip('%')} {shape}"[:120]


def kernel_name(hlo: str) -> str:
    """The Pallas kernel a ``tpu_custom_call`` op runs: its instruction
    name without the numeric suffix."""
    return re.sub(r"\.\d+$", "", hlo.partition(" = ")[0].lstrip("%"))


def innermost(events):
    """The events that contain no other event of the same line."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    for i, (a, b, n) in enumerate(events):
        if i + 1 < len(events) and events[i + 1][0] < b and \
                events[i + 1][1] <= b:
            continue
        out.append((a, b, n))
    return out


def reduce_file(path: str, host_interval: Tuple[float, float],
                devices: int) -> Trace:
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    chips: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[float, float, str]] = []
    for plane in prof.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            if OPS_LINE in lines:
                chips[plane.name] = lines
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                evs = _events(ln)
                if any(n.startswith(CLIENT_PREFIX) for _, _, n in evs):
                    host.extend(evs)
    chips = dict(sorted(chips.items())[:devices])
    everything = [e for c in chips.values() for e in c[OPS_LINE]] + host
    if not everything:
        raise ValueError(f"no device or client events in {path}")
    lo = min(e[0] for e in everything)
    hi = max(e[1] for e in everything)
    spans = _HostSpans(host)
    ops: Dict[str, float] = collections.Counter()
    kernels: Dict[str, float] = collections.Counter()
    programs_with: Dict[str, float] = collections.Counter()
    gaps: Dict[str, float] = collections.Counter()
    busy = 0.0
    for lines in chips.values():
        starts: Dict[str, List[float]] = collections.defaultdict(list)
        for a, b, n in innermost(lines[OPS_LINE]):
            ops[short_name(n)] += (b - a) * 1e-9
            if CUSTOM_CALL in n:
                kernels[kernel_name(n)] += (b - a) * 1e-9
                starts[kernel_name(n)].append(a)
        for k, ts in starts.items():
            ts.sort()
            for a, b, _ in lines.get(MODULES_LINE, ()):
                i = bisect.bisect_left(ts, a)
                if i < len(ts) and ts[i] < b:
                    programs_with[k] += (b - a) * 1e-9
        merged = union([(a, b) for a, b, _ in lines[OPS_LINE]])
        busy += sum(b - a for a, b in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps[spans.at((a + b) / 2)] += (b - a) * 1e-9
    n = max(len(chips), 1)
    return Trace((hi - lo) * 1e-9, busy / n, host_interval, len(chips),
                 dict(ops), dict(kernels), dict(programs_with), dict(gaps))


def reduce_dir(trace_dir: str, host_interval: Tuple[float, float],
               devices: int) -> Trace:
    """Reduce the one ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found "
                         f"{len(paths)}")
    return reduce_file(paths[0], host_interval, devices)
