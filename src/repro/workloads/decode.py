"""Transformer decode engine: batched serving with continuous batching and a
FlexArena-backed slot allocator (the PR-1/2 ``ServeEngine``, now one workload
class among several — see ``repro.workloads.base``).

The FILCO connection: serving-time KV/workspace memory is exactly the
diverse-workload storage problem the FMU solves — requests of wildly
different prompt lengths share one flat arena through runtime views instead
of per-request padded buffers.  The engine tracks per-request views in a
host-side FlexArena whose capacity mirrors the device cache pool, so
admission control (can this prompt fit?) is the paper's Fig. 5(b) check.

Decode state on device is a fixed pool of batch slots (functional pytree);
prefill fills a slot, decode steps advance all live slots in lock-step
(continuous batching: slots join/leave between steps).

Three properties make the engine a real-time-recomposable accelerator
(paper §1/§2.1) rather than just a batcher:

* **Tensor parallelism per composition.**  Given ``rules`` (normally
  ``serve_rules()``), params and the pooled KV cache shard over the
  sub-mesh's model axis — more CUs mean less per-device work, so the
  recomposition policy's predicted gains are measured gains.  Leaves whose
  dims don't divide the mesh fall back to replication per-leaf (never an
  error).  ``reshard_to`` is then a sharded→sharded ``device_put``.
* **AOT-warmable executables.**  Decode and prefill run from explicitly
  managed compiled executables keyed by (config fingerprint, mesh
  fingerprint, shapes), so the fabric can pre-compile a candidate
  composition before committing a switch (``warm_compile``) and the
  post-move step skips the XLA recompile stall.  The cache may be shared
  fabric-wide: same-config tenants then reuse each other's programs.
* **Pipelined decode dispatch.**  When termination is length-based
  (``eos_id < 0``), step *k*'s decode is dispatched from device-resident
  step *k-1* tokens before the host reads them, so per-step host
  bookkeeping overlaps device execution instead of serializing on
  ``device_get``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.arena import (AllocationError, FlexArena, PagedArena,
                              ROLE_ACT)
from repro.core.composer import mesh_fingerprint
from repro.core.dse import DesignPoint
from repro.distribution import partitioning as part
from repro.models.model import Model
from repro.obs import Telemetry
from repro.workloads.base import (DecayedLengthEstimator, EngineTelemetry,
                                  sanitize_check, sanitize_guard)
from repro.workloads.compile_cache import ExecutableCache

PyTree = Any

# Ragged decode programs are specialized on a static KV upper bound (the max
# live per-row length, rounded up).  Rounding to this block keeps the number
# of distinct decode executables per config at most max_len / KV_BOUND_BLOCK.
KV_BOUND_BLOCK = 32


def _env_use_kernels() -> bool:
    """Default for ``ServeConfig.use_kernels``: on unless REPRO_USE_KERNELS
    is set to an off value (escape hatch for A/B runs and the kernel-off
    benchmark leg)."""
    return os.environ.get("REPRO_USE_KERNELS", "1").lower() not in (
        "0", "false", "off")


def _env_paged_kv() -> bool:
    """Default for ``ServeConfig.paged_kv``: on unless REPRO_PAGED_KV is set
    to an off value (escape hatch for the slot-granular baseline leg of the
    SLO-attainment benchmark)."""
    return os.environ.get("REPRO_PAGED_KV", "1").lower() not in (
        "0", "false", "off")


def _round_block(n: int) -> int:
    return -(-max(n, 1) // KV_BOUND_BLOCK) * KV_BOUND_BLOCK


def _mesh_of(sub) -> Optional[Mesh]:
    """Accept a Mesh, a composer SubAccelerator, or None."""
    if sub is None or isinstance(sub, Mesh):
        return sub
    return sub.mesh


def _rules_fp(rules: Optional[part.ShardingRules]):
    """Hashable identity of a rule set for executable-cache keys: two
    same-config engines under different rules (replicated vs TP) lower
    different programs and must never share a compiled executable."""
    if rules is None:
        return None
    return tuple(sorted(rules.rules.items()))


@dataclasses.dataclass
class Request:
    """One submitted request's host-side lifecycle record (``tokens`` is
    the prompt for decode/ssm engines, the source sequence — token ids or
    precomputed (S, d_model) frame embeddings — for enc-dec)."""

    rid: int
    tokens: np.ndarray                  # prompt
    max_new_tokens: int
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    view: Any = None                    # arena view (admission accounting)
    done: bool = False
    # tokens scheduled for emission (prefill first token + dispatched decode
    # steps).  Runs ahead of len(out_tokens) by the in-flight step under
    # pipelined decode; equal to it otherwise.
    scheduled: int = 0
    # enc-dec forced decoding: target-prefix token ids prepended (after BOS)
    # to the decoder prompt; None decodes from BOS alone
    prefix: Optional[np.ndarray] = None
    # perf_counter() at submit — SLO telemetry (queue wait, TTFT).  Rides
    # the request record so a dp rebalance that adopts a queued request
    # keeps its original arrival time.  0.0 = unknown (synthetic request).
    submitted_s: float = 0.0


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Per-tenant serving dimensions (they shape the compiled programs, so
    they are part of every executable-cache key)."""

    max_slots: int = 4                 # concurrent decode slots
    max_len: int = 128                 # per-slot cache capacity (tokens)
    eos_id: int = 0
    greedy: bool = True
    prefill_bucket: int = 32           # prompts padded up to this length
    # overlap decode dispatch with host bookkeeping (applies when eos_id < 0,
    # i.e. termination is length-based and known at dispatch time)
    pipeline_decode: bool = True
    # enc-dec tenants: per-slot cross-attention source-cache capacity in
    # source frames (0 -> max_len); submit()'s tokens are the SOURCE sequence
    max_src_len: int = 0
    # decoder start token for enc-dec jobs (the decoder prompt is [bos])
    bos_id: int = 1
    # sequence-length program buckets for batched encode phases
    # (EncoderEngine jobs / EncDecEngine sources): compile one program per
    # bucket, run each job in the smallest fitting one.  () = capacity only.
    len_buckets: Tuple[int, ...] = ()
    # structural ceiling one engine's step program may batch to: apply()
    # clamps slot resizes here no matter the grant width.  Past this point
    # a grant only buys throughput via data-parallel replicas (the
    # ReplicaGroup dp axis), not a wider batch.
    slot_cap: int = 64
    # ragged Pallas decode kernels on the hot path: decode attention reads
    # only the live KV prefix (per-row true lengths, empty slots skipped)
    # instead of the padded max_len cache, and SSM steps run the fused
    # single-step scan.  Token streams are bit-identical either way (pinned
    # by tests/test_ragged_decode.py).  Default on; REPRO_USE_KERNELS=0
    # flips the default for A/B benchmarking without code changes.  Part of
    # every executable-cache key (the lowered decode program differs).
    use_kernels: bool = dataclasses.field(default_factory=_env_use_kernels)
    # paged KV admission arena: fixed-size pages over the FlexArena
    # substrate.  Admission reserves only the pages covering the prompt and
    # caches grow page-at-a-time, instead of pinning len(prompt)+max_new
    # rows for the request's whole lifetime.  kv_arena_frac scales the
    # arena budget against the per-slot worst case for BOTH arena kinds
    # (paged and slot-granular run at the same HBM budget, so benchmark
    # arms compare fairly); under paging, page exhaustion during growth
    # preempts the largest-remaining request (device state saved
    # host-side, resumed bit-identically once pages free).  Host-side
    # accounting only — compiled programs are unaffected, so none of
    # these is part of the executable-cache key.
    paged_kv: bool = dataclasses.field(default_factory=_env_paged_kv)
    kv_page_rows: int = 16             # rows (tokens) per page
    kv_arena_frac: float = 1.0         # arena budget / dense worst case


@dataclasses.dataclass
class _Inflight:
    """One dispatched decode step whose tokens the host hasn't read yet."""

    nxt: Any                            # device (B,) int32
    entries: List[Tuple[int, Request, bool]]   # (slot, request, finishing)
    pipelined: bool


class DecodeEngine(EngineTelemetry):
    """Batched transformer decode on a composed sub-accelerator (the
    ``decode`` workload class) — continuous batching over a pooled slot
    cache, FlexArena admission control, tensor parallelism per composition,
    AOT-warmable executables and pipelined decode dispatch (see the module
    docstring; the Engine-protocol contract is docs/workloads.md)."""

    workload_class = "decode"

    def __init__(self, model: Model, params: PyTree, cfg: ServeConfig,
                 mesh=None, rules: Optional[part.ShardingRules] = None,
                 exec_cache: Optional[ExecutableCache] = None,
                 obs: Optional[Telemetry] = None):
        self.model = model
        self.cfg = cfg
        # telemetry handle: histograms/spans for this engine's hot path.
        # Always present (a private registry when the fabric didn't pass
        # one) so instrumentation below never branches on None; recording
        # is a no-op when the handle is disabled.
        self._obs = obs if obs is not None else Telemetry()
        self.rules = rules
        self._rules_eff = rules or part.ShardingRules(rules={})
        self.reshard_count = 0
        # tensor-parallel degree over the granted sub-mesh: None = the whole
        # grant (the pre-DSE default); the serving-side DSE Stage 1 sets it
        # per design point via apply(point.tp)
        self._tp: Optional[int] = None
        self._granted = None               # last granted sub-mesh (unsliced)
        self._recent_lens = DecayedLengthEstimator()
        self._per_token_elems = self._per_token_cache_elems()
        self.arena = self._make_arena()
        self._queue: List[Request] = []
        self._active: Dict[int, Request] = {}
        # preempted requests parked host-side: (Request, exported cache
        # block) — pages/slot released, resumed by _admit when space frees
        self._parked: List[Tuple[Request, PyTree]] = []
        self.preempt_count = 0
        # finished rid -> emitted tokens; bounded so a long-running engine
        # doesn't grow host memory with every request ever served
        self._finished: Dict[int, List[int]] = {}
        self.finished_cap = 10_000
        self._next_rid = 0
        self._free_slots = list(range(cfg.max_slots))

        # sharding plans: treedef + per-leaf (shape, dtype, logical spec),
        # captured before strip() so any composed sub-mesh's shardings and
        # lowering avals can be derived without re-annotating live state
        self._param_plan = part.ShardingPlan.of(params)
        self.params = part.strip(params)
        if rules is not None and not self._param_plan.annotated:
            raise ValueError(
                "tensor-parallel serving needs annotated params: pass "
                "model.init(...) without strip() when rules are given")
        cache_ann = self._init_cache_ann(cfg.max_slots)
        self._cache_plan = part.ShardingPlan.of(cache_ann)
        self.cache = part.strip(cache_ann)
        # one reusable single-slot prefill cache: prefill is functional, so
        # the prototype is never mutated — no init_cache(1, ...) per request
        single_ann = self._init_cache_ann(1)
        self._single_plan = part.ShardingPlan.of(single_ann)
        self._single = part.strip(single_ann)
        self._slot_axes = model.cache_slot_axes(self.cache)

        # AOT executables per (kind, config fp, mesh fp, shape).  The cache
        # may be fabric-shared (same-config tenants reuse programs), so every
        # key carries this engine's config fingerprint — model config plus
        # the serve dims that shape the compiled program.
        self._exec = exec_cache if exec_cache is not None else ExecutableCache()
        self._own_builds = 0
        # the memo fills from both the serving loop and the prewarm
        # thread (warm_compile pricing candidate slot counts)
        self._plan_lock = threading.Lock()
        self._plan_memo: Dict[int, part.ShardingPlan] = {
            cfg.max_slots: self._cache_plan}
        self._cfg_key = self._config_key(cfg.max_slots)
        # seed the bucketed prompt length only for archs that actually pad
        # to it; SSM/hybrid archs prefill at exact lengths (see
        # _prefill_into_slot), and warm_compile must not burn seconds per
        # candidate composition on a program that never dispatches
        self._prefill_lens = ({self._bucketed(cfg.prefill_bucket)}
                              if model.cfg.ssm is None else set())

        self._inflight: Optional[_Inflight] = None
        self._inject: Dict[int, int] = {}   # slot -> first token since last dispatch
        self._emit_buf: List[Tuple[int, int]] = []

        self.mesh: Optional[Mesh] = None
        self.reshard_to(mesh)          # commit params+cache to the sub-mesh
        self.reshard_count = 0         # construction placement isn't a move

    # ------------------------------------------------------------------
    # admission-accounting / cache-shape hooks (overridden by the SSM
    # engine, whose per-slot state is constant-size rather than
    # length-proportional, and by the enc-dec engine, which adds the
    # per-slot cross-attention source cache)
    # ------------------------------------------------------------------
    def _init_cache_ann(self, batch: int):
        """Annotated decode-cache pytree for ``batch`` slots (pooled cache
        and the reusable single-slot prefill cache are both built here)."""
        return self.model.init_cache(batch, self.cfg.max_len)

    def _per_token_cache_elems(self) -> int:
        """Per-layer per-token KV elements (admission accounting)."""
        mc = self.model.cfg
        if mc.mla is not None:
            per_tok = mc.mla.kv_lora_rank + mc.mla.qk_rope_head_dim
        elif mc.attention_free:
            per_tok = 0
        else:
            per_tok = 2 * mc.num_kv_heads * mc.resolved_head_dim
        return max(per_tok, 1) * mc.num_layers

    def _arena_capacity(self) -> int:
        return self.cfg.max_slots * self.cfg.max_len * self._per_token_elems

    def _slot_rows(self, req: Request) -> int:
        """Arena rows a request occupies while holding a slot."""
        return len(req.tokens) + req.max_new_tokens

    def _row_cap(self) -> int:
        """Per-slot arena row capacity (mirrors the device cache rows)."""
        return self.cfg.max_len

    def _page_rows(self) -> int:
        return max(1, min(self.cfg.kv_page_rows, self._row_cap()))

    def _arena_pages(self) -> int:
        """Paged-arena page budget: the dense per-slot worst case scaled by
        ``kv_arena_frac``, floored at one slot's worth so any admissible
        request can always run alone (growth can never wedge)."""
        per_slot = -(-self._row_cap() // self._page_rows())
        frac = max(min(self.cfg.kv_arena_frac, 1.0), 0.0)
        want = int(round(frac * self.cfg.max_slots * per_slot))
        return max(want, per_slot, 1)

    def _make_arena(self, min_pages: int = 0):
        """Admission arena for the current config: paged (fixed-size pages,
        grow-at-a-time) or the PR-1 slot-granular FlexArena.  Both honor
        ``kv_arena_frac`` — the paired benchmark arms (paged vs dense)
        compare at the SAME HBM budget — floored at one slot's worst case
        so an admissible request can always run alone.  ``min_pages``
        floors the page budget when a rebuild must re-admit live tables
        (adoption bursts may briefly exceed the configured budget)."""
        if not self.cfg.paged_kv:
            frac = max(min(self.cfg.kv_arena_frac, 1.0), 0.0)
            per_slot = self._row_cap() * self._per_token_elems
            floor = min_pages * self._page_rows() * self._per_token_elems
            return FlexArena(max(int(round(frac * self._arena_capacity())),
                                 per_slot, floor, 1))
        return PagedArena(max(self._arena_pages(), min_pages),
                          self._page_rows(), self._per_token_elems)

    @property
    def _paged(self) -> bool:
        return isinstance(self.arena, PagedArena)

    def _live_rows(self, req: Request) -> int:
        """Rows a paged request's table must cover for the next dispatch:
        current KV occupancy plus the row that dispatch writes."""
        return min(self._dec_len(req) + 1, self._row_cap())

    def _arena_rows(self, req: Request) -> int:
        """Arena rows to reserve for a request entering a slot: its current
        coverage under paging, the len+budget worst case otherwise."""
        return self._live_rows(req) if self._paged else self._slot_rows(req)

    def _oversized(self, req: Request) -> bool:
        """True when the request could never fit a slot (hard reject)."""
        return self._slot_rows(req) > self.cfg.max_len

    def _config_key(self, slots: int, buckets=None) -> Tuple:
        """Shared-executable-cache config fingerprint at a (possibly
        prospective) slot count — warm_compile prices candidate design
        points before they are applied.  ``buckets`` is unused here (decode
        has no encode phase); the enc-dec engine extends the key with it."""
        del buckets
        return (self.workload_class, self.model.cfg, slots,
                self.cfg.max_len, _rules_fp(self.rules),
                self.cfg.use_kernels)

    def _plan_for_slots(self, slots: int) -> part.ShardingPlan:
        """ShardingPlan of the pooled cache at ``slots`` — abstract-eval'd
        (no device allocation), memoized; lets warm_compile lower programs
        for a candidate slot count without building the pool."""
        with self._plan_lock:
            if slots not in self._plan_memo:
                ann = jax.eval_shape(lambda: self._init_cache_ann(slots))
                self._plan_memo[slots] = part.ShardingPlan.of(ann)
            return self._plan_memo[slots]

    # ------------------------------------------------------------------
    def reshard_to(self, sub) -> None:
        """Migrate this engine — params AND live decode state — onto a new
        sub-accelerator (FILCO real-time recomposition, §1/§2.1).

        The engine is purely functional on device: everything it owns is the
        params pytree and the two cache pytrees, so growing, shrinking or
        moving its composition is one sharded→sharded device_put of each,
        with every leaf's sharding refit to the target mesh under the
        engine's rules.  Host-side state (queues, slots, arena views) is
        untouched.  Token streams are preserved across any grow/shrink/unify
        sequence: replicated engines are bit-identical, tensor-parallel ones
        greedy-decode the same tokens (the property tests/test_fabric.py
        pins across 1/2/4-way TP).
        """
        self._harvest()                 # inflight tokens live on the old mesh
        with self._obs.span("reshard"):
            self._granted = _mesh_of(sub)
            # the engine computes on the grant restricted to its TP degree
            # (the serving DSE's per-tenant design knob); None = whole grant
            mesh = part.tp_submesh(self._granted, self._tp)
            self.mesh = mesh
            # hot-path executable-cache key: recomputing the device-id tuple
            # per dispatch is a per-step O(devices) loop on a pod-scale mesh
            self._mesh_fp = mesh_fingerprint(mesh)
            if mesh is not None:
                rules = self._rules_eff
                self.params = jax.device_put(
                    self.params, self._param_plan.shardings(mesh, rules))
                self.cache = jax.device_put(
                    self.cache, self._cache_plan.shardings(mesh, rules))
                self._single = jax.device_put(
                    self._single, self._single_plan.shardings(mesh, rules))
        self.reshard_count += 1
        self._obs.inc("reshards")

    def sync(self) -> None:
        """Block until this engine's device state (params + pooled cache) is
        ready — recomposition migration timing and post-move stall probing."""
        jax.block_until_ready((self.params, self.cache))

    # ------------------------------------------------------------------
    # live design-point reconfiguration (serving DSE Stage 1's knobs)
    # ------------------------------------------------------------------
    def design(self) -> Dict[str, Any]:
        """The engine's currently applied design point (the runtime knobs
        the serving DSE optimizes): TP degree (None = whole grant), slot
        count, encode bucket ladder (None for classes without one)."""
        return {"tp": self._tp, "slots": self.cfg.max_slots, "buckets": None}

    def apply(self, sub=None,
              point: Optional[DesignPoint] = None) -> Dict[str, Any]:
        """Apply a design-point delta live — the engine-side half of the
        serving DSE's Stage-1 → fabric loop.  ``point`` carries the knobs
        (``None`` fields = keep the current setting):

        * ``sub``          — migrate onto a new sub-accelerator (reshard_to);
        * ``point.tp``     — tensor-parallel degree over the grant: params
          and pooled state reshard onto the first ``tp`` model-axis columns;
        * ``point.slots``  — resize the pooled decode cache: live slots are
          migrated (exact device-side copy) into the new pool, so pinned
          streams are bit-identical across the resize; never shrinks below
          the current occupancy (live streams are migrated, not evicted);
        * ``point.buckets`` — swap the encode-program ladder (encoder /
          enc-dec subclasses; numerics-safe because encodes are
          bucket-invariant);
        * ``point.dp``     — ignored here: replica count is a *group* knob,
          consumed by :class:`~repro.serve.fabric.ReplicaGroup` before it
          fans the per-replica point out to its engines.

        Every step re-enters the shared AOT executable cache under the new
        config/mesh fingerprint, so a preceding ``warm_compile`` with the
        same point makes the first post-apply step stall-free.  Returns the
        knobs actually applied (slot clamps included).
        """
        point = point if point is not None else DesignPoint(cus=0)
        self._harvest()                 # in-flight tokens shaped by old pool
        applied: Dict[str, Any] = {}
        if point.tp is not None and point.tp != (self._tp or 0):
            self._tp = max(int(point.tp), 1)
            applied["tp"] = self._tp
        if sub is not None or "tp" in applied:
            # commit the (new) grant under the (new) degree
            self.reshard_to(sub if sub is not None else self._granted)
        if point.slots is not None and int(point.slots) != self.cfg.max_slots:
            applied["slots"] = self._resize_slots(int(point.slots))
        b = self._apply_buckets(point.buckets)
        if b is not None:
            applied["buckets"] = b
        return applied

    def _apply_buckets(self, buckets):
        """Bucket-ladder hook: plain decode has no encode phase."""
        del buckets
        return None

    def _resize_slots(self, slots: int) -> int:
        """Resize the pooled slot cache live, migrating every live slot.

        The new pool is allocated (sharded on the current mesh), each live
        slot's cache rows are copied device-side into the lowest new slot
        ids (an exact copy — decode rows are batch-independent, so pinned
        streams stay bit-identical), and the host-side slot bookkeeping and
        admission arena are rebuilt at the new capacity.  Shrinking clamps
        at the live occupancy: streams are migrated, never evicted.
        """
        live = sorted(self._active)
        cap = max(self.cfg.slot_cap, 1)
        slots = max(min(int(slots), cap), len(live), 1)
        if slots == self.cfg.max_slots:
            return slots
        with self._obs.timed("slot_migration", "slot_migration_s",
                             src=self.cfg.max_slots, dst=slots,
                             live=len(live)):
            self._do_resize_slots(slots, live)
        return slots

    def _do_resize_slots(self, slots: int, live: List[int]) -> None:
        mapping = {old: new for new, old in enumerate(live)}
        new_ann = self._init_cache_ann(slots)
        new_plan = part.ShardingPlan.of(new_ann)
        new_cache = part.strip(new_ann)
        if self.mesh is not None:
            new_cache = jax.device_put(
                new_cache, new_plan.shardings(self.mesh, self._rules_eff))
        axes = self.model.cache_slot_axes(new_cache)
        if live:
            # one pass per leaf: gather the live slots' rows from the old
            # pool (exact copy — bit-identical streams) and write them as
            # a block into the lowest new slot ids; free slots keep their
            # freshly initialized values
            new_cache = _migrate_slots(new_cache, self.cache, live, axes)
        self.cache = new_cache
        self._cache_plan = new_plan
        self._slot_axes = axes
        self.cfg = dataclasses.replace(self.cfg, max_slots=slots)
        with self._plan_lock:
            self._plan_memo[slots] = new_plan
        self._cfg_key = self._config_key(slots)
        # host bookkeeping follows the migrated slots
        self._active = {mapping[s]: r for s, r in self._active.items()}
        for s, req in self._active.items():
            req.slot = s
        self._inject = {mapping[s]: v for s, v in self._inject.items()
                        if s in mapping}
        self._free_slots = list(range(len(live), slots))
        # admission arena mirrors the new pool capacity; live views re-admit
        # (len(live) <= slots and per-request rows <= per-slot rows; a paged
        # rebuild floors the page budget at the live tables' need, so the
        # re-allocation cannot fail)
        self._readmit_live_views()

    def _readmit_live_views(self) -> None:
        """Rebuild the admission arena and re-alloc every live request's
        view/page table at its current size."""
        pr = self._page_rows()
        need = sum(-(-self._arena_rows(r) // pr)
                   for r in self._active.values())
        arena = self._make_arena(min_pages=need)
        for req in self._active.values():
            req.view = arena.alloc(self._arena_rows(req),
                                   self._per_token_elems, ROLE_ACT)
        self.arena = arena

    # ------------------------------------------------------------------
    # cross-replica live migration (ReplicaGroup dp retune): a retiring
    # replica's requests move to a sibling engine by exact cache-row copy —
    # never by re-prefilling, whose different reduction order could flip an
    # argmax and break the bit-identical-streams contract
    # ------------------------------------------------------------------
    def _export_slot(self, slot: int) -> PyTree:
        """One slot's cache rows as a host-side block (slot dim kept at
        size 1, so the block write-back is a plain dynamic_update_slice);
        leaves without a slot axis export a scalar placeholder."""
        idx = jnp.asarray([slot], jnp.int32)

        def take(ax, leaf):
            if ax < 0:
                return np.zeros((), np.int32)
            return np.asarray(jax.device_get(jnp.take(leaf, idx, axis=ax)))

        return jax.tree.map(take, self._slot_axes, self.cache)

    def evacuate(self) -> Tuple[List[Tuple[Request, PyTree]], List[Request]]:
        """Strip this engine of ALL work so sibling replicas can adopt it
        (ReplicaGroup dp shrink).  Returns ``(live, queued)``: ``live`` is
        ``[(Request, host cache block)]`` for every active slot, ``queued``
        the unadmitted requests.  The engine is left idle; its finished
        records stay readable via ``results()``."""
        self._harvest()
        live = []
        for slot in sorted(self._active):
            req = self._active[slot]
            live.append((req, self._export_slot(slot)))
            self.arena.free_view(req.view)
        self._active.clear()
        self._inject.clear()
        self._free_slots = list(range(self.cfg.max_slots))
        # preempted requests ride along with their saved cache blocks: the
        # adopter restores them exactly like an exported live slot
        live.extend(self._parked)
        self._parked = []
        queued, self._queue = self._queue, []
        return live, queued

    def _rebuild_arena(self, extra_rows: int = 0) -> None:
        """Re-admit every live view into a fresh arena (defragmentation:
        adoption allocs land in an arena shaped by a different admission
        history than a freshly resized pool's).  ``extra_rows`` reserves
        headroom for a request about to be adopted."""
        pr = self._page_rows()
        need = sum(-(-self._arena_rows(r) // pr)
                   for r in self._active.values())
        need += -(-extra_rows // pr)
        arena = self._make_arena(min_pages=need)
        for req in self._active.values():
            req.view = arena.alloc(self._arena_rows(req),
                                   self._per_token_elems, ROLE_ACT)
        self.arena = arena

    def adopt_request(self, req: Request, block: PyTree) -> int:
        """Adopt a live request evacuated from a sibling replica: assign a
        fresh rid (engine rids are per-engine; the ReplicaGroup owns the
        stable group-level rid), write its cache block into a free slot and
        resume decoding exactly where the source replica stopped (the last
        emitted token is host-injected, as after any harvest)."""
        self._harvest()
        if not self._free_slots:
            # callers size the pool before adopting; this is the backstop
            self._resize_slots(self.cfg.max_slots + 1)
        try:
            view = self.arena.alloc(self._arena_rows(req),
                                    self._per_token_elems, ROLE_ACT)
        except AllocationError:
            self._rebuild_arena(extra_rows=self._arena_rows(req))
            view = self.arena.alloc(self._arena_rows(req),
                                    self._per_token_elems, ROLE_ACT)
        rid = self._next_rid
        self._next_rid += 1
        req.rid, req.view = rid, view
        req.slot = self._free_slots.pop(0)
        dev = jax.tree.map(lambda ax, b: b if ax < 0 else jnp.asarray(b),
                           self._slot_axes, block)
        self.cache = _write_slot(self.cache, dev, req.slot, self._slot_axes)
        if self.mesh is not None:
            # the AOT decode executable requires its exact input shardings;
            # the eager block write above may have disturbed them
            self.cache = jax.device_put(
                self.cache,
                self._cache_plan.shardings(self.mesh, self._rules_eff))
        self._active[req.slot] = req
        if req.out_tokens:
            self._inject[req.slot] = req.out_tokens[-1]
        return rid

    def adopt_queued(self, req: Request) -> int:
        """Adopt a queued (unadmitted) request from a sibling replica:
        fresh engine rid, no recent-lengths double count (the group already
        observed the submission once)."""
        rid = self._next_rid
        self._next_rid += 1
        req.rid = rid
        req.slot, req.view = -1, None
        self._queue.append(req)
        return rid

    def export_queued(self) -> List[Request]:
        """Hand back the unadmitted queue (ReplicaGroup queue rebalance on
        a dp grow); live slots stay put."""
        queued, self._queue = self._queue, []
        return queued

    # ------------------------------------------------------------------
    # preemption: park a victim's device state host-side (the dp-retune
    # export/adopt machinery turned inward), release its slot and pages,
    # resume later with a bit-identical continuation.  Triggered by page
    # exhaustion during growth (_ensure_capacity) and by the fabric's
    # SLO scheduler (preempt_one).
    # ------------------------------------------------------------------
    def _release_slot(self, slot: int, req: Request) -> None:
        """Single exit point returning a finished/preempted/rejected
        request's slot AND its arena reservation together — every path that
        gives up a slot goes through here, so slot and arena accounting can
        never diverge (arena bytes return to zero once every request
        drains; pinned by tests/test_paged_arena.py)."""
        if req.view is not None:
            self.arena.free_view(req.view)
            req.view = None
        if slot in self._active:
            del self._active[slot]
        self._inject.pop(slot, None)
        self._free_slots.append(slot)
        req.slot = -1

    def preempt_slot(self, slot: int) -> Optional[int]:
        """Preempt the request in ``slot``: harvest any in-flight step, save
        the slot's cache rows host-side, free its pages + slot, and park it
        for re-admission.  Continuation is bit-identical: the saved block is
        an exact device copy and the last emitted token is host-injected on
        resume, exactly as a dp retune's adopt_request does."""
        self._harvest()
        req = self._active.get(slot)
        if req is None:
            return None
        block = self._export_slot(slot)
        self._release_slot(slot, req)
        self._parked.append((req, block))
        self.preempt_count += 1
        self._obs.inc("preemptions")
        return req.rid

    def _victim_slot(self) -> Optional[int]:
        """Deterministic preemption victim: the active request with the most
        remaining budget (its pages stay pinned longest); newest rid breaks
        ties.  None when nothing is preemptible."""
        best = None
        for slot, req in self._active.items():
            rem = req.max_new_tokens - req.scheduled
            if rem <= 0:
                continue
            key = (rem, req.rid, slot)
            if best is None or key > best[0]:
                best = (key, slot)
        return best[1] if best is not None else None

    def preempt_one(self) -> Optional[int]:
        """SLO-scheduler entry point: preempt the policy victim.  Returns
        its rid, or None when no active request is preemptible."""
        self._harvest()
        slot = self._victim_slot()
        if slot is None:
            return None
        return self.preempt_slot(slot)

    def _ensure_capacity(self) -> None:
        """Grow each live slot's page table to cover the next dispatch.
        Page exhaustion preempts the largest-remaining victim until the
        growth fits; the arena floor (one slot's worst case) guarantees a
        lone request always fits, so this never wedges."""
        if not self._paged:
            return
        for slot in sorted(self._active):
            req = self._active.get(slot)
            if req is None or req.view is None:
                continue
            need = self._live_rows(req)
            while True:
                try:
                    self.arena.grow(req.view, need)
                    break
                except AllocationError:
                    victim = self._victim_slot()
                    if victim is None:
                        break   # everything is finishing this step
                    self.preempt_slot(victim)
                    if victim == slot:
                        break   # the grower itself was the best victim

    def _resume_parked(self) -> None:
        """Re-admit preempted requests (exact state restore) while a slot
        and their pages are available.  Runs after the queue loop in
        ``_admit``: fresh arrivals keep admission priority so an SLO-forced
        preemption cannot thrash with its own victim."""
        harvested = False
        while self._parked and self._free_slots:
            req, block = self._parked[0]
            try:
                view = self.arena.alloc(self._arena_rows(req),
                                        self._per_token_elems, ROLE_ACT)
            except AllocationError:
                break
            if not harvested:
                self._harvest()   # cache write-back wants a settled pool
                harvested = True
            self._parked.pop(0)
            req.view = view
            req.slot = self._free_slots.pop(0)
            dev = jax.tree.map(lambda ax, b: b if ax < 0 else jnp.asarray(b),
                               self._slot_axes, block)
            self.cache = _write_slot(self.cache, dev, req.slot,
                                     self._slot_axes)
            if self.mesh is not None:
                # the AOT decode executable requires its exact input
                # shardings; the eager block write may have disturbed them
                self.cache = jax.device_put(
                    self.cache,
                    self._cache_plan.shardings(self.mesh, self._rules_eff))
            self._active[req.slot] = req
            if req.out_tokens:
                self._inject[req.slot] = req.out_tokens[-1]
            self._obs.inc("preempt_resumes")

    # ------------------------------------------------------------------
    # compiled executables (build counting: EngineTelemetry)
    # ------------------------------------------------------------------
    def _vec_aval(self, mesh, dtype, shape):
        if mesh is None:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P()))

    def _decode_fn(self, params, cache, prev_tokens, inject_vals,
                   inject_mask, live_mask, *, kv_bound=None, src_bound=None):
        # next input token per slot: host-injected (fresh prefill / sync
        # mode) or the previous step's device-resident output (pipelined)
        toks = jnp.where(inject_mask, inject_vals, prev_tokens)[:, None]
        logits, cache = self.model.decode_step(
            params, cache, toks, use_kernels=self.cfg.use_kernels,
            kv_bound=kv_bound, src_bound=src_bound, live_mask=live_mask)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(live_mask, nxt, 0)
        return nxt, cache

    # ------------------------------------------------------------------
    # ragged-kernel decode bounds: with use_kernels on, decode attention
    # reads only cache[:, :kv_bound].  The bound — max live per-row length
    # rounded up to KV_BOUND_BLOCK — is baked into the executable as a
    # static slice, so the engine lowers at most max_len/KV_BOUND_BLOCK
    # decode programs per config; retunes and dp replicas reuse them
    # stall-free through the shared ExecutableCache.
    # ------------------------------------------------------------------
    def _dec_len(self, req: Request) -> int:
        """Host-side mirror of a slot's KV occupancy for the *next*
        dispatch: attention reads ``pos + 1 = len(prompt) + scheduled``
        entries (the enc-dec engine overrides for its decoder prompt)."""
        return len(req.tokens) + req.scheduled

    def _kv_bound(self) -> int:
        longest = max((self._dec_len(r) for r in self._active.values()),
                      default=1)
        return min(_round_block(longest), self.cfg.max_len)

    def _decode_bounds(self) -> Tuple[int, ...]:
        """Static KV bounds of the decode program about to be dispatched:
        ``()`` when the padded path is active (or the arch holds no KV
        cache), ``(kv_bound,)`` for self-attention; the enc-dec engine adds
        the cross-attention source bound."""
        if not self.cfg.use_kernels or self.model.cfg.attention_free:
            return ()
        return (self._kv_bound(),)

    def _full_bounds(self) -> Tuple[int, ...]:
        """Worst-case bounds (full cache capacity), warmed alongside the
        current ones so long-running slots never hit a cold build."""
        if not self.cfg.use_kernels or self.model.cfg.attention_free:
            return ()
        return (self.cfg.max_len,)

    def _next_bounds(self) -> Tuple[int, ...]:
        """The current bounds bumped one block per axis (clamped to
        capacity) — warmed ahead so live lengths growing across the next
        block boundary dispatch a pre-built program."""
        return tuple(min(b + KV_BOUND_BLOCK, cap) for b, cap
                     in zip(self._decode_bounds(), self._full_bounds()))

    def _covering_bounds(self, bounds: Tuple[int, ...]) -> list:
        """All block-quantized bounds that dominate ``bounds`` elementwise
        (excluding itself), smallest total slack first — the fallback
        ladder when the exact bound was never warmed."""
        axes = [range(b, cap + 1, KV_BOUND_BLOCK)
                for b, cap in zip(bounds, self._full_bounds())]
        cands = sorted(itertools.product(*axes), key=lambda t: (sum(t), t))
        return [t for t in cands if t != tuple(bounds)]

    def _prefill_fn(self, params, pool_cache, single, tokens, true_len, slot):
        """Prefill one prompt into the reusable single-slot cache and write
        it into the pool at `slot` — one fused dispatch per admission."""
        logits, filled = self.model.prefill(params, {"tokens": tokens},
                                            single, true_len=true_len)
        pool = _write_slot(pool_cache, filled, slot, self._slot_axes)
        first = jnp.argmax(logits[0]).astype(jnp.int32)
        return first, pool

    def _build_decode(self, mesh, slots: Optional[int] = None,
                      bounds: Tuple[int, ...] = ()):
        B = slots or self.cfg.max_slots
        plan = self._plan_for_slots(B)
        rules = self._rules_eff
        kwargs = {}
        if mesh is not None:
            kwargs["out_shardings"] = (
                NamedSharding(mesh, P()),
                plan.shardings(mesh, rules))
        # bounds bind as keywords so donate_argnums=(1,) keeps pointing at
        # the cache positional
        step = functools.partial(
            self._decode_fn, **dict(zip(("kv_bound", "src_bound"), bounds)))
        fn = jax.jit(step, donate_argnums=(1,), **kwargs)
        # trace under the sub-mesh so the decode kernels wrap themselves in
        # shard_map (XLA cannot partition a Mosaic kernel on its own)
        ctx = (jax.sharding.use_abstract_mesh(mesh.abstract_mesh)
               if mesh is not None else contextlib.nullcontext())
        with ctx:
            lowered = fn.lower(
                self._param_plan.avals(mesh, rules),
                plan.avals(mesh, rules),
                self._vec_aval(mesh, jnp.int32, (B,)),
                self._vec_aval(mesh, jnp.int32, (B,)),
                self._vec_aval(mesh, jnp.bool_, (B,)),
                self._vec_aval(mesh, jnp.bool_, (B,)))
        return lowered.compile()

    def _build_prefill(self, mesh, nb: int, slots: Optional[int] = None):
        plan = self._plan_for_slots(slots or self.cfg.max_slots)
        rules = self._rules_eff
        kwargs = {}
        if mesh is not None:
            kwargs["out_shardings"] = (
                NamedSharding(mesh, P()),
                plan.shardings(mesh, rules))
        fn = jax.jit(self._prefill_fn, donate_argnums=(1,), **kwargs)
        return fn.lower(
            self._param_plan.avals(mesh, rules),
            plan.avals(mesh, rules),
            self._single_plan.avals(mesh, rules),
            self._vec_aval(mesh, jnp.int32, (1, nb)),
            self._vec_aval(mesh, jnp.int32, ()),
            self._vec_aval(mesh, jnp.int32, ()),
        ).compile()

    def _decode_exec(self, mesh, bounds: Tuple[int, ...] = ()):
        key = ("decode", self._cfg_key, self._mesh_fp, bounds)
        if bounds and not self._exec.contains(key):
            # a bound whose program was never pre-built (live lengths grew
            # past the warm set between warm_compile calls): dispatch the
            # smallest WARM bound covering it — full capacity is always
            # warm — instead of compiling on the serving path; the exact
            # program arrives with the next warm_compile
            for cand in self._covering_bounds(bounds):
                ck = ("decode", self._cfg_key, self._mesh_fp, cand)
                if self._exec.contains(ck):
                    bounds, key = cand, ck
                    break
        return self._exec.get_or_build(
            key, self._counted(
                lambda: self._build_decode(mesh, bounds=bounds)))

    def _prefill_exec(self, mesh, nb: int):
        key = ("prefill", self._cfg_key, self._mesh_fp, nb)
        self._prefill_lens.add(nb)
        return self._exec.get_or_build(
            key, self._counted(lambda: self._build_prefill(mesh, nb)))

    def warm_compile(self, sub,
                     point: Optional[DesignPoint] = None) -> int:
        """Pre-compile this engine's decode + known prefill executables for
        a *candidate* sub-accelerator, without moving any state.  Called by
        the fabric before committing a recomposition (possibly from a
        background thread) so the first step on the new composition hits a
        warm executable.  ``point`` warms a candidate *design point*
        (prospective slot count / TP degree / bucket ladder — the serving
        DSE's Stage-1 knobs; ``dp`` is consumed by the ReplicaGroup, which
        warms every replica slice) rather than the engine's current
        configuration.  Returns the number of cold builds performed."""
        point = point if point is not None else DesignPoint(cus=0)
        with self._obs.timed("warm_compile", "warm_compile_s") as sp:
            mesh = part.tp_submesh(
                _mesh_of(sub), point.tp if point.tp is not None else self._tp)
            B = point.slots or self.cfg.max_slots
            key = self._config_key(B)
            fp = mesh_fingerprint(mesh)
            # warm the decode program at the bounds about to dispatch, one
            # block above them (live lengths grow between warm_compile
            # calls) AND at full cache capacity, so neither the first
            # post-switch step nor a later long slot hits a cold build on
            # the new composition
            built = 0
            for bounds in sorted({self._decode_bounds(), self._next_bounds(),
                                  self._full_bounds()}):
                built += self._exec.ensure(
                    ("decode", key, fp, bounds),
                    self._counted(lambda bounds=bounds:
                                  self._build_decode(mesh, B, bounds)))
            # snapshot: the serving thread appends new prefill lengths while
            # a background prewarm iterates
            for nb in sorted(tuple(self._prefill_lens)):
                built += self._exec.ensure(
                    ("prefill", key, fp, nb),
                    self._counted(
                        lambda nb=nb: self._build_prefill(mesh, nb, B)))
            if sp is not None:
                sp["builds"] = built
        return built

    # ------------------------------------------------------------------
    # load metrics consumed by the recomposition policy
    @property
    def queue_depth(self) -> int:
        """Requests awaiting admission (count)."""
        return len(self._queue)

    @property
    def active_count(self) -> int:
        """Live decode slots (count)."""
        return len(self._active)

    @property
    def preempted_depth(self) -> int:
        """Preempted requests parked host-side awaiting re-admission."""
        return len(self._parked)

    @property
    def has_work(self) -> bool:
        """True while the queue, slots, parked preemptions or an in-flight
        dispatch hold work."""
        return bool(self._queue or self._active or self._inflight
                    or self._parked)

    def pending_tokens(self) -> int:
        """Decode steps of work still owed: remaining tokens of active and
        parked (preempted) requests plus full budgets of queued ones."""
        owed = sum(req.max_new_tokens - req.scheduled
                   for req in self._active.values())
        owed += sum(req.max_new_tokens - req.scheduled
                    for req, _ in self._parked)
        owed += sum(req.max_new_tokens + len(req.tokens)
                    for req in self._queue)
        return max(owed, 0)

    def queue_head_wait_s(self, now: Optional[float] = None) -> float:
        """Seconds the oldest queued request has been waiting (0.0 when the
        queue is empty) — the SLO scheduler's TTFT-risk signal."""
        stamps = [r.submitted_s for r in self._queue if r.submitted_s > 0.0]
        if not stamps:
            return 0.0
        return max((now if now is not None else time.perf_counter())
                   - min(stamps), 0.0)

    def arena_utilization(self) -> float:
        """KV-arena pressure, 0..1 (admission-accounting fill fraction)."""
        return self.arena.utilization()

    def recent_lengths(self) -> Tuple[int, ...]:
        """Recently submitted prompt/source lengths, exponentially decayed
        toward the newest traffic (a weighted resample, not a flat window) —
        the observed-traffic signal the serving DSE's Stage-1 bucket-ladder
        search optimizes against."""
        return self._recent_lens.lengths()

    def stats(self) -> Dict[str, Any]:
        """Load/telemetry snapshot: queue depth (requests), live slots,
        owed decode steps, arena pressure (0..1), migrations performed,
        cold executable builds and the applied design point."""
        return {
            "workload_class": self.workload_class,
            "queue_depth": self.queue_depth,
            "active": self.active_count,
            "pending_tokens": self.pending_tokens(),
            "arena_utilization": round(self.arena_utilization(), 4),
            "preempted": self.preempted_depth,
            "preemptions": self.preempt_count,
            "reshard_count": self.reshard_count,
            "compile_builds": self.compile_builds,
            "design": self.design(),
        }

    # ------------------------------------------------------------------
    def submit(self, tokens, max_new_tokens: int = 16) -> int:
        """Queue one request; returns its rid.  Requests never vanish:
        ones that could never fit a slot are rejected-but-recorded."""
        rid = self._next_rid
        self._next_rid += 1
        toks = np.asarray(tokens, np.int32)
        self._recent_lens.append(len(toks))
        self._queue.append(Request(rid, toks, max_new_tokens,
                                   submitted_s=time.perf_counter()))
        self._obs.inc("requests_submitted")
        return rid

    # ------------------------------------------------------------------
    def _admit(self) -> None:
        """Move queued requests into free slots while the arena admits them
        (FILCO Fig. 5(b) fit check), then prefill the batch just admitted."""
        admitted: List[Request] = []
        while self._queue and self._free_slots:
            req = self._queue[0]
            if self._oversized(req):
                # rejected (would never fit a slot): still recorded, with
                # whatever was emitted (nothing) — requests never vanish
                req.done = True
                self._queue.pop(0)
                self._record_finished(req)
                continue
            try:
                view = self.arena.alloc(self._arena_rows(req),
                                        self._per_token_elems, ROLE_ACT)
            except AllocationError:
                break  # arena full: stay queued (admission control);
                # anything else (bad sizes, dtype bugs) propagates
            self._queue.pop(0)
            req.view = view
            req.slot = self._free_slots.pop(0)
            self._active[req.slot] = req
            admitted.append(req)
        if admitted:
            obs = self._obs
            if obs.enabled:
                now = time.perf_counter()
                for req in admitted:
                    if req.submitted_s > 0.0:
                        obs.observe("queue_wait_s", now - req.submitted_s)
            with obs.span("admit", n=len(admitted)):
                self._prefill_admitted(admitted)
        self._resume_parked()

    def _prefill_admitted(self, reqs: List[Request]) -> None:
        """Prefill the requests just admitted (hook: the enc-dec engine
        overrides this to share one batched source encode across them)."""
        for req in reqs:
            self._prefill_into_slot(req)

    def _bucketed(self, length: int) -> int:
        bucket = max(self.cfg.prefill_bucket, 8)
        return -(-length // bucket) * bucket

    def _prefill_into_slot(self, req: Request) -> None:
        """Prefill one request into its slot.

        Attention archs: pad to the bucket and pass true_len (garbage KV
        beyond true_len is masked by per-row cache pos and overwritten by
        subsequent decodes).  SSM/hybrid archs carry recurrent state that
        padding would corrupt, so they prefill at the exact prompt length
        (bounded recompiles: one per distinct length)."""
        L = len(req.tokens)
        nb = self._bucketed(L) if self.model.cfg.ssm is None else L
        toks = np.zeros((1, nb), np.int32)
        toks[0, :L] = req.tokens
        # the device_get of the first token is an existing sync point, so
        # the prefill span/histogram and TTFT cost no extra synchronization
        with self._obs.timed("prefill", "prefill_s", len=L):
            exe = self._prefill_exec(self.mesh, nb)
            first_dev, self.cache = exe(self.params, self.cache, self._single,
                                        toks, np.int32(L), np.int32(req.slot))
            first = int(jax.device_get(first_dev))
        req.out_tokens.append(first)
        req.scheduled = 1
        self._inject[req.slot] = first
        self._record_ttft(req)

    def _record_ttft(self, req: Request) -> None:
        """First token just landed on the host: record time-to-first-token
        against the request's original submit stamp."""
        if req.submitted_s > 0.0 and self._obs.enabled:
            self._obs.observe("ttft_s", time.perf_counter() - req.submitted_s)

    # ------------------------------------------------------------------
    def step(self) -> List[Tuple[int, int]]:
        """One engine iteration: admit -> dispatch decode -> harvest.
        Returns [(rid, token)] newly observed on the host — under pipelined
        decode these are the *previous* dispatch's tokens (the current one
        is still on device); totals and per-request streams are identical.
        """
        with sanitize_guard():
            self._admit()
            if not self._active:
                self._harvest()
                sanitize_check(self)
                return self._drain_emitted()
            # span + histogram around the dispatch/harvest pair: the
            # harvest's device_get of the PREVIOUS dispatch is the existing
            # sync point the host-side timing rides on — no extra syncs,
            # pipelining preserved
            with self._obs.timed("decode_step", "decode_step_s"):
                self._step_dispatch()
            out = self._drain_emitted()
        sanitize_check(self)
        obs = self._obs
        if obs.enabled:
            obs.set_gauge("slot_utilization",
                          len(self._active) / max(self.cfg.max_slots, 1))
            obs.set_gauge("arena_utilization", self.arena.utilization())
        return out

    def _step_dispatch(self) -> None:
        self._ensure_capacity()
        if not self._active:
            return
        B = self.cfg.max_slots
        pipelined = self.cfg.pipeline_decode and self.cfg.eos_id < 0
        inject_vals = np.zeros((B,), np.int32)
        inject_mask = np.zeros((B,), bool)
        live = np.zeros((B,), bool)
        for slot, req in self._active.items():
            live[slot] = True
            if not pipelined:
                inject_mask[slot] = True
                inject_vals[slot] = req.out_tokens[-1]
            elif slot in self._inject:
                inject_mask[slot] = True
                inject_vals[slot] = self._inject[slot]
        prev = (self._inflight.nxt if self._inflight is not None
                else np.zeros((B,), np.int32))
        exe = self._decode_exec(self.mesh, self._decode_bounds())
        nxt, self.cache = exe(self.params, self.cache, prev,
                              inject_vals, inject_mask, live)
        self._inject.clear()

        entries = []
        for slot in list(self._active):
            req = self._active[slot]
            req.scheduled += 1
            finishing = req.scheduled >= req.max_new_tokens
            entries.append((slot, req, finishing))
            if pipelined and finishing:
                # length-based completion is known at dispatch time: release
                # the slot now so the next admit can reuse it; the token
                # value lands at harvest
                req.done = True
                self._release_slot(slot, req)

        # harvest the PREVIOUS dispatch (its compute is done or in flight):
        # host bookkeeping below overlaps the step dispatched above.  Its
        # continuing slots are fed by the dispatch just made, so their
        # tokens must NOT be re-injected next step (they'd be stale).
        self._harvest(register_inject=False)
        self._inflight = _Inflight(nxt, entries, pipelined)
        if not pipelined or not self._active:
            # sync mode consumes immediately (eos handling needs the value);
            # a draining engine flushes so callers see complete streams as
            # soon as queue+active are empty
            self._harvest()

    def _harvest(self, register_inject: bool = True) -> None:
        """Read one in-flight dispatch's tokens back to the host.

        register_inject: when harvesting with no newer dispatch outstanding
        (snapshot/results/reshard), a continuing slot's next input token is
        no longer device-resident — record it for host injection."""
        inf = self._inflight
        if inf is None:
            return
        self._inflight = None
        nxt = np.asarray(jax.device_get(inf.nxt))
        for slot, req, finishing in inf.entries:
            tok = int(nxt[slot])
            req.out_tokens.append(tok)
            self._emit_buf.append((req.rid, tok))
            if inf.pipelined:
                if finishing:
                    self._record_finished(req)
                elif register_inject:
                    self._inject[slot] = tok
            elif tok == self.cfg.eos_id or \
                    len(req.out_tokens) >= req.max_new_tokens:
                req.done = True
                self._release_slot(slot, req)
                self._record_finished(req)

    def _drain_emitted(self) -> List[Tuple[int, int]]:
        out, self._emit_buf = self._emit_buf, []
        if out:
            self._obs.inc("tokens_emitted", len(out))
        return out

    def _record_finished(self, req: Request) -> None:
        self._finished[req.rid] = list(req.out_tokens)
        self._evict_finished()

    def run_to_completion(self, max_steps: int = 1000) -> Dict[int, List[int]]:
        """Step until idle (or ``max_steps``); returns ``snapshot()``."""
        for _ in range(max_steps):
            if not self.has_work:
                break
            self.step()
        return self.snapshot()

    def results(self) -> Dict[int, List[int]]:
        """Completed (or rejected) requests' emitted tokens."""
        self._harvest()
        return {rid: list(toks) for rid, toks in self._finished.items()}

    def snapshot(self) -> Dict[int, List[int]]:
        """Every request seen so far -> tokens emitted (in-flight, queued
        and finished)."""
        self._harvest()
        out = {req.rid: list(req.out_tokens)
               for req in list(self._active.values()) + self._queue}
        out.update({req.rid: list(req.out_tokens)
                    for req, _ in self._parked})
        out.update({rid: list(toks) for rid, toks in self._finished.items()})
        return out


def _write_slot(pool_cache: PyTree, single_cache: PyTree, slot,
                slot_axes: PyTree) -> PyTree:
    """Copy a 1-batch cache into slot `slot` of the pooled cache.

    `slot_axes` names each leaf's slot-axis position explicitly
    (Model.cache_slot_axes): scanned stacks are (layers, slots, ...), all
    other leaves are slot-leading, -1 means no slot axis.  Positional, never
    inferred from shape mismatch — a max_slots == 1 pool updates exactly
    like any other."""
    def write(ax, pool, one):
        if ax < 0:
            return pool
        start = (0,) * ax + (slot,) + (0,) * (pool.ndim - ax - 1)
        return jax.lax.dynamic_update_slice(pool, one.astype(pool.dtype),
                                            start)

    return jax.tree.map(write, slot_axes, pool_cache, single_cache)


def _migrate_slots(dst_cache: PyTree, src_cache: PyTree,
                   src_slots: List[int], slot_axes: PyTree) -> PyTree:
    """Copy ``src_slots``' rows from ``src_cache`` into slots [0, n) of
    ``dst_cache`` (pool→pool; the pools may differ in slot count but share
    every other dim).  One gather + one block write per leaf — an exact
    device-side copy, because live slot migration during an ``apply`` slot
    resize must preserve streams bit-for-bit."""
    idx = jnp.asarray(src_slots, jnp.int32)

    def cp(ax, dst, src):
        if ax < 0:
            return dst
        block = jnp.take(src, idx, axis=ax)
        start = (0,) * dst.ndim
        return jax.lax.dynamic_update_slice(dst, block.astype(dst.dtype),
                                            start)

    return jax.tree.map(cp, slot_axes, dst_cache, src_cache)
