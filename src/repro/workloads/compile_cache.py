"""Ahead-of-time executable cache for the serving path.

FILCO's real-time reconfiguration only pays off when switching compositions
is cheap; the Reconfigurable Stream Network line of work gets there by
pre-staging per-configuration programs.  The serving analog: every composed
sub-mesh shape is a distinct XLA program, and the post-recomposition
recompile (0.7-2.3 s measured) dwarfs state migration (~10 ms).  This cache
holds compiled executables keyed by (function kind, mesh fingerprint,
shape extras) so the fabric can compile a candidate composition's decode and
prefill programs *before* committing the switch — the first step on the new
composition then hits a warm executable.

jax.jit's dispatch cache cannot be warmed this way: ``.lower().compile()``
returns an executable but does not populate the dispatch path (measured: the
first traced call after an AOT compile still pays full compile time).  So
the engine calls the compiled executables directly and this cache is the
source of truth.

Thread-safe: the fabric may warm a candidate composition from a background
thread while the main thread keeps serving.  Builds happen outside the lock
(XLA compilation is thread-safe and releases the GIL); a lost race costs one
duplicate compile, never a wrong executable.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Callable, Hashable, Optional


class ExecutableCache:
    """A small LRU of AOT-compiled executables.

    The key space is bounded in practice — one decode program per composed
    mesh a tenant has run on, plus one prefill program per (mesh, padded
    prompt length) bucket — but a long-lived fabric bouncing through many
    compositions should not hoard dead executables, hence the LRU cap.
    """

    def __init__(self, capacity: int = 32):
        self.capacity = int(capacity)
        self.builds = 0                 # cold compiles performed (telemetry)
        self.hits = 0
        self._lock = threading.Lock()
        self._exe: OrderedDict[Hashable, Any] = OrderedDict()

    def get(self, key: Hashable) -> Optional[Any]:
        with self._lock:
            exe = self._exe.get(key)
            if exe is not None:
                self._exe.move_to_end(key)
                self.hits += 1
            return exe

    def contains(self, key: Hashable) -> bool:
        with self._lock:
            return key in self._exe

    def get_or_build(self, key: Hashable, builder: Callable[[], Any]) -> Any:
        exe = self.get(key)
        if exe is not None:
            return exe
        exe = builder()                 # outside the lock: compiles are slow
        self._insert(key, exe)
        return exe

    def ensure(self, key: Hashable, builder: Callable[[], Any]) -> int:
        """Warm path: build & insert iff missing.  Returns builds done (0/1)."""
        if self.contains(key):
            return 0
        self._insert(key, builder())
        return 1

    def snapshot(self) -> dict:
        """Telemetry view: cache-wide cold builds, warm hits, occupancy.
        Consumed by the fabric's metrics snapshot (the registry's
        ``exec_cache_*`` gauges) — per-engine build attribution stays with
        :attr:`EngineTelemetry.compile_builds`."""
        with self._lock:
            return {"builds": self.builds, "hits": self.hits,
                    "size": len(self._exe), "capacity": self.capacity}

    def items(self) -> list:
        """Snapshot of the cached (key, executable) pairs, least recently
        used first (inspection: e.g. which kernels a program compiled)."""
        with self._lock:
            return list(self._exe.items())

    def _insert(self, key: Hashable, exe: Any) -> None:
        with self._lock:
            if key not in self._exe:
                self.builds += 1
            self._exe[key] = exe
            self._exe.move_to_end(key)
            while len(self._exe) > self.capacity:
                self._exe.popitem(last=False)
