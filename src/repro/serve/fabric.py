"""Real-time recomposition controller — the serving-side face of FILCO's
"reconfigured in real-time and flexibly composed into a unified or multiple
independent accelerators" (paper §1, §2.1).

A :class:`ComposedServer` owns the full device mesh.  Each tenant runs the
engine of its *workload class* (transformer decode / SSM recurrent decode /
encoder embedding / enc-dec encode→decode — :mod:`repro.workloads`) on a
:class:`~repro.core.composer.MeshComposer` sub-accelerator, tensor-parallel
over its sub-mesh's model axis (``serve_engine_rules``), so a tenant's
measured throughput actually tracks the CUs it holds.  A tenant's engine is
really a :class:`ReplicaGroup` — ``dp`` independent same-design engine
replicas tiling the grant (the DesignPoint ``dp`` axis), so a memory-capped
small-model tenant on a wide grant batches in parallel across tiles instead
of sharding an unchanged batch.  Between decode steps
the controller samples per-tenant load (queue depth, owed work, arena
pressure) and asks a policy — by default the analytical model driving the
DSE Stage-2 search, pricing each tenant by its class's bound resource — for
a new CU split.  When the predicted gain clears the
hysteresis threshold it *live-recomposes*: the affected tenants' params and
pooled decode caches are reshard (sharded→sharded device_put) onto their new
sub-meshes while unaffected tenants keep their exact devices (delta
recomposition).

Reconfiguration cost is attacked on both ends, mirroring the paper's
real-time story: state migration is a ~10 ms device_put, and the dominant
post-recomposition XLA recompile (0.7-2.3 s measured cold) is hoisted off
the serving path by pre-compiling the target composition's decode/prefill
executables *before* the switch commits (``warm_compile``), optionally in a
background thread (``prewarm_async``) so compilation overlaps serving.
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import itertools
import math
import time
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax

from repro.common.platform import PlatformProfile, device_profile
from repro.configs import get_config, get_reduced
from repro.configs.base import ModelConfig
from repro.core.analytical import (AccelConfig, decode_kv_read_latency,
                                   layer_latency, ssm_step_latency)
from repro.core.arena import PagedArena
from repro.core.composer import MeshComposer
from repro.core.dse import DesignPoint
from repro.distribution import partitioning as part
from repro.models import build_model
from repro.models.ssm import dims as ssm_dims
from repro.obs import MetricsRegistry, PredictionLedger, Telemetry
from repro.serve.dse import Stage1Optimizer, TenantDesignSpace, design_key
from repro.workloads import (DECODE, ENCDEC, ENCODER, SSM, DecodeEngine,
                             Engine, ExecutableCache, ServeConfig,
                             build_engine, workload_class_of)
from repro.workloads.decode import _mesh_of


def serve_engine_rules() -> part.ShardingRules:
    """serve_rules() tuned for the decode engine's composed sub-meshes.

    Two deltas vs the static-analysis serving rules: the KV cache shards
    over kv *heads* rather than split-K sequence (a dynamic-position scatter
    into a sequence-sharded cache forces SPMD to rematerialize the whole
    cache every step), and head counts that don't divide a given sub-mesh
    fall back to replication per-leaf at reshard time (fit_spec), so the
    same rules serve a 1-CU and an 8-CU composition.
    """
    rules = dict(part.serve_rules().rules)
    rules["kv_seq"] = None
    rules["kv_heads"] = "model"
    return part.ShardingRules(rules=rules)


@dataclasses.dataclass(frozen=True)
class SLOTarget:
    """Per-tenant latency targets, milliseconds (0 = that target is
    untracked).  Drives two things in :class:`ComposedServer`:

    * the SLO-aware scheduler: a tenant whose head-of-line queue wait is
      burning its p99 TTFT budget (or whose observed per-token p99 has
      breached target) gets one of its slackest live streams preempted —
      exact device-state save to host — so the freed slot/pages admit the
      waiting request *this* step;
    * :meth:`ComposedServer.slo_attainment`: the fraction of observed
      TTFTs / per-token latencies under each target, read from the same
      ``obs`` histograms the fabric already collects.

    See docs/scheduling.md for the admission/preemption policy.
    """

    ttft_p50_ms: float = 0.0
    ttft_p99_ms: float = 0.0
    per_token_p50_ms: float = 0.0
    per_token_p99_ms: float = 0.0

    def tracked(self) -> bool:
        return any(v > 0 for v in (self.ttft_p50_ms, self.ttft_p99_ms,
                                   self.per_token_p50_ms,
                                   self.per_token_p99_ms))


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant model co-resident on the fabric."""

    name: str
    arch: str                        # architecture registry id
    reduced: bool = True
    serve: ServeConfig = ServeConfig()
    seed: int = 0
    # workload class: "auto" derives from the arch (attention-free SSM ->
    # "ssm", enc-dec with cross-attention -> "encdec", else "decode");
    # "encoder" is an explicit tenant choice — any arch can serve
    # prefill-only/embedding traffic
    workload: str = "auto"
    # ceiling on the tenant's data-parallel replica count (Stage-1 dp axis);
    # 1 pins the tenant to a single engine per grant
    dp_cap: int = 64
    # latency targets for the SLO-aware scheduler; None = best-effort
    # tenant (never preempted on latency grounds, absent from attainment)
    slo: Optional[SLOTarget] = None


@dataclasses.dataclass(frozen=True)
class TenantLoad:
    """Observed load signals only (the PR-5 ``decide`` input; superseded by
    :class:`TenantObservation`, which folds in the side-channel keywords)."""

    pending_tokens: int              # decode steps of work owed
    queue_depth: int                 # requests awaiting admission
    active: int                      # live decode slots
    arena_utilization: float         # KV arena pressure, 0..1


@dataclasses.dataclass(frozen=True)
class TenantObservation:
    """Everything the policy needs to know about one tenant, in one record.

    Built by the fabric each decide tick (:meth:`ComposedServer.observe`)
    and passed as ``decide(observations={tenant: TenantObservation(...)})``.
    """

    # load signals (sampled from the tenant's engine / replica group)
    pending_tokens: int = 0          # owed work units (steps / prompt toks)
    queue_depth: int = 0             # requests awaiting admission
    active: int = 0                  # live decode slots (all replicas)
    arena_utilization: float = 0.0   # KV-arena pressure, 0..1
    # workload identity + observed traffic (Stage-1 inputs)
    wclass: Optional[str] = None     # workload class (None: derive from cfg)
    recent_lengths: Tuple[int, ...] = ()   # recently observed job lengths
    src_len: int = 0                 # enc-dec per-slot source capacity
    space: Optional[TenantDesignSpace] = None   # Stage-1 search bounds


@dataclasses.dataclass(frozen=True)
class RecompositionEvent:
    """One applied recomposition, for logs/benchmarks."""

    step: int
    sizes_before: Dict[str, int]
    sizes_after: Dict[str, int]
    moved: Tuple[str, ...]
    unchanged: Tuple[str, ...]
    parked: Tuple[str, ...]
    seconds: float                   # state migration (device_put) only
    reason: str
    # tenants whose CU set did not move but whose engine design point
    # (TP degree / slots / bucket ladder) was reconfigured live, and the
    # per-tenant knobs actually applied (DSE Stage-1 deltas)
    retuned: Tuple[str, ...] = ()
    design: Dict[str, Dict] = dataclasses.field(default_factory=dict)
    # moved tenant -> wall time of its first step on the new composition;
    # with a cold executable cache this is where the XLA recompile stall
    # lands — filled in by ComposedServer.step()
    post_step_seconds: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    # ahead-of-time compilation performed before the switch committed
    warm_compile_seconds: float = 0.0
    warm_builds: int = 0             # cold executables compiled while warming
    overlapped: bool = False         # warmed in the background thread


# ---------------------------------------------------------------------------
# policy: Stage-2-style split search on the analytical model
# ---------------------------------------------------------------------------

# tile of sequence tokens used to price encoder (full-sequence MM) work in
# its compute-bound regime; the per-token cost is normalized back out
ENC_COST_TILE = 128


def _composed_total_s(lb, cus: int) -> float:
    """Latency of an MM layer on a composed TPU sub-accelerator.

    ``layer_latency`` models the board, where every CU shares one DDR — its
    DDR/stream terms are flat in CU count.  On the TPU fabric each CU is a
    mesh column with its own HBM and VMEM, so bandwidth scales with the
    grant; all workload classes must be priced on that same assumption
    (``ssm_step_latency`` already divides by CUs) or the split search
    compares classes on inconsistent rooflines.  Compute is already divided
    by CUs inside ``layer_latency``."""
    c = max(cus, 1)
    return max(lb.compute_s, lb.ddr_s / c, lb.stream_s / c) + lb.launch_s


class AnalyticalPolicy:
    """The serving-side DSE Stage 2: chooses a *composition of design
    points* by pricing each tenant on candidate sub-accelerator grants with
    the analytical latency model (the same machinery the offline DSE
    schedules with, §3.1) and minimizing the predicted makespan of the owed
    work.

    Two-stage (default): for every candidate CU grant ``c`` the per-tenant
    Stage-1 optimizer (:class:`~repro.serve.dse.Stage1Optimizer`) first
    picks that tenant's best engine configuration — TP degree over the
    sub-mesh, slot count, bucket ladder — and ``decide`` searches splits
    over those Stage-1-optimal :class:`~repro.core.dse.DesignPoint` memos,
    returning per-tenant design points (CUs + knobs) for the fabric to
    apply live.  With ``two_stage=False`` (the split-only ablation, and the
    behavior when the fabric supplies no design spaces) the CU count is the
    whole design point — exactly the pre-DSE policy.

    Class-aware costing (the heterogeneous-workload point): each tenant is
    priced by its workload class's actual bound resource —

    * ``decode``  — bandwidth-bound batched GEMV per decode step (weights
      streamed every token);
    * ``ssm``     — state-bandwidth-bound recurrent update per step
      (``ssm_step_latency``: params + read/write of the O(1) state);
    * ``encoder`` — compute-bound full-sequence MMs per owed prompt token;
    * ``encdec``  — decode-side batched GEMVs (self-attn, cross-attn and
      MLP projections) plus the per-step cross-attention source-cache read,
      whose bytes scale with the tenant's source length (``src_len``).

    So a compute-starved encoder tenant and a bandwidth-starved decode
    tenant are priced on different rooflines, and the split search allocates
    CUs by where they actually buy throughput instead of a one-size
    decode-GEMM model.

    Hysteresis: a new split is only worth a live recomposition when the
    predicted speedup clears ``min_gain`` — resharding has a real cost
    (device_put + one warm compile per new composition).  After every
    ``decide`` the policy exposes ``runner_up``: the best candidate split it
    did NOT return (the hysteresis-rejected best, or the second-best when a
    switch was returned) — the fabric speculatively prewarms it during idle
    decide intervals.
    """

    def __init__(self, platform: Optional[PlatformProfile] = None,
                 min_gain: float = 1.25, two_stage: bool = True):
        # None: the chip this process runs on (an unknown TPU kind raises)
        self.platform = platform or device_profile()
        self.min_gain = min_gain
        self._cost_cache: Dict[Tuple, float] = {}
        self.runner_up: Optional[Dict[str, DesignPoint]] = None
        # Stage 1 shares this policy's step_cost memo as its price table
        self.stage1: Optional[Stage1Optimizer] = (
            Stage1Optimizer(self.step_cost, platform) if two_stage else None)
        # last non-idle decision's predicted makespans (telemetry /
        # benchmark): {"best_s": ..., "current_s": ...}
        self.predicted: Optional[Dict[str, float]] = None

    # -- per-tenant per-step cost on a c-CU sub-accelerator ----------------
    def step_cost(self, cfg: ModelConfig, batch: int, cus: int,
                  wclass: str = DECODE, src_len: int = 0,
                  kv_len: int = 0) -> float:
        """Predicted seconds per unit of owed work for one tenant on a
        ``cus``-CU sub-accelerator: per decode step for decode/ssm/encdec
        tenants, per owed prompt token for encoder tenants.

        src_len: enc-dec tenants' per-slot source length (frames read by
        every cross-attention step); ignored for other classes.

        kv_len: decoder-KV length each decode step streams per slot — the
        full per-slot capacity on the padded path, the expected live prefix
        under the ragged decode kernels (Stage 1 passes the estimate; 0
        keeps the term out, the pre-kernel pricing).  Attention archs only.
        """
        if cus <= 0:
            return float("inf")
        # the key carries the workload class: an SSM/encoder/encdec tenant
        # sharing a cfg.name with a transformer tenant must never read a
        # stale decode-GEMM price (and full/reduced configs share a name:
        # key on the priced dims too — d_ff and the KV dims are priced, so
        # they are in the key).  src_len prices the encdec cross-attention
        # read and kv_len the decoder-KV read, so both are in the key.
        kv = kv_len if wclass in (DECODE, ENCDEC) else 0
        key = (wclass, cfg.name, cfg.num_layers, cfg.d_model,
               cfg.d_ff, cfg.num_kv_heads, cfg.resolved_head_dim,
               max(batch, 1), cus, src_len if wclass == ENCDEC else 0, kv)
        if key not in self._cost_cache:
            accel = AccelConfig(
                name=f"tpu-sub{cus}", num_cus=cus,
                aies_per_cu=self.platform.num_compute_units,
                onchip_elems=cus * (self.platform.onchip_bytes // 4),
                num_fmus=max(cus, 1), fp=True, fmv=True, fmf=True)
            d = cfg.d_model
            if wclass == SSM and cfg.ssm is not None:
                # recurrent decode: state + parameter bandwidth per step
                d_in, dt_rank, n, w = ssm_dims(cfg)
                cost = cfg.num_layers * ssm_step_latency(
                    accel, self.platform, max(batch, 1), d, d_in, n, w,
                    dt_rank)
            elif wclass == ENCODER:
                # prefill-only: compute-bound full-sequence MMs, priced per
                # owed prompt token (demand for encoder tenants is queued
                # prompt tokens, not decode steps)
                layers = cfg.encoder_layers or cfg.num_layers
                lb_attn = layer_latency(accel, self.platform,
                                        ENC_COST_TILE, d, d)
                lb_mlp = layer_latency(accel, self.platform,
                                       ENC_COST_TILE, d, cfg.d_ff or 4 * d)
                cost = layers * (2 * _composed_total_s(lb_attn, cus)
                                 + 2 * _composed_total_s(lb_mlp, cus)) \
                    / ENC_COST_TILE
            elif wclass == ENCDEC:
                # enc-dec decode step: the decoder-side batched GEMVs — one
                # extra (d x d) projection pair vs plain decode for the
                # cross-attention block — plus the per-step cross-attention
                # source-cache read: 2·kv_heads·head_dim·src_len K/V
                # elements per layer per live slot, pure HBM bandwidth on
                # the composed sub-accelerator (each CU owns its HBM slice,
                # so the read scales down with the grant like every other
                # bandwidth term)
                b = max(batch, 1)
                lb_attn = layer_latency(accel, self.platform, b, d, d)
                lb_mlp = layer_latency(accel, self.platform,
                                       b, d, cfg.d_ff or 4 * d)
                cross_read_s = decode_kv_read_latency(
                    accel, self.platform, b, cfg.num_kv_heads,
                    cfg.resolved_head_dim, max(src_len, 1))
                kv_read_s = decode_kv_read_latency(
                    accel, self.platform, b, cfg.num_kv_heads,
                    cfg.resolved_head_dim, kv)
                cost = cfg.num_layers * (
                    3 * _composed_total_s(lb_attn, cus)
                    + 2 * _composed_total_s(lb_mlp, cus)
                    + cross_read_s + kv_read_s)
            else:
                # dominant decode GEMMs per layer: attention out/in (d x d)
                # and the MLP pair (d x d_ff), batched over live slots —
                # plus the per-step decoder-KV stream when the caller
                # prices it (kv_len > 0)
                lb_attn = layer_latency(accel, self.platform,
                                        max(batch, 1), d, d)
                lb_mlp = layer_latency(accel, self.platform,
                                       max(batch, 1), d, cfg.d_ff or 4 * d)
                kv_read_s = decode_kv_read_latency(
                    accel, self.platform, max(batch, 1), cfg.num_kv_heads,
                    cfg.resolved_head_dim, kv)
                cost = cfg.num_layers * (
                    2 * _composed_total_s(lb_attn, cus)
                    + 2 * _composed_total_s(lb_mlp, cus)
                    + kv_read_s)
            self._cost_cache[key] = cost
        return self._cost_cache[key]

    # -- the two-stage search ----------------------------------------------
    def decide(self, observations: Mapping[str, TenantObservation],
               cfgs: Mapping[str, ModelConfig],
               current: Mapping[str, object],
               num_cus: int,
               ) -> Tuple[Dict[str, DesignPoint], str]:
        """Return (per-tenant design points, reason).

        Each returned :class:`DesignPoint` carries the tenant's CU grant
        plus its Stage-1-optimal engine knobs (TP degree / replica count /
        slots / bucket ladder — ``None`` knobs mean "keep").  Tenants with
        no load are parked (cus 0); returning the ``current`` points means
        "leave the fabric alone".

        ``observations`` maps tenant -> :class:`TenantObservation`: the
        sampled load signals plus workload class (``None`` derives from the
        tenant's config; encoder tenancy can't be derived, so mixed fabrics
        set it), enc-dec source capacity (prices the per-step
        cross-attention read), recently observed job lengths and the
        tenant's Stage-1 design space — without a space a tenant is priced
        split-only (its CU count is the whole design point).  ``current``
        maps tenant -> applied CU count (int) or applied DesignPoint."""
        loads = dict(observations)
        classes = {t: o.wclass for t, o in loads.items()
                   if o.wclass is not None}
        src_lens = {t: o.src_len for t, o in loads.items() if o.src_len}
        lengths = {t: o.recent_lengths for t, o in loads.items()}
        spaces = {t: o.space for t, o in loads.items()
                  if o.space is not None}
        for t in cfgs:
            classes.setdefault(t, workload_class_of(cfgs[t]))
        # arena pressure inflates demand: a hot arena means queued work the
        # pending-token count can't see yet
        demand = {t: ld.pending_tokens * (1.0 + ld.arena_utilization)
                  for t, ld in loads.items()}
        busy = [t for t, d in demand.items() if d > 0]

        def concurrency(t: str) -> int:
            return max(loads[t].active + loads[t].queue_depth, 1)

        def split_only_cost(t: str, c: int) -> float:
            if c <= 0:
                return float("inf")
            cost = self.step_cost(cfgs[t], loads[t].active or 1, c,
                                  classes[t], src_len=src_lens.get(t, 0))
            if self.stage1 is not None and spaces:
                # a space-less tenant in a two-stage decide must price in
                # Stage 1's units (seconds per TOKEN: one batched step
                # emits `active` tokens) or the makespan would compare
                # per-step against per-token costs and systematically
                # over-grant the space-less tenant
                cost /= max(loads[t].active, 1)
            return cost

        def stage1_point(t: str, c: int) -> DesignPoint:
            """Stage 1: the tenant's best design point on a c-CU grant."""
            sp = spaces.get(t)
            if self.stage1 is not None and sp is not None:
                return self.stage1.best(cfgs[t], sp, concurrency(t), c,
                                        lengths.get(t, ()),
                                        src_lens.get(t, 0))
            return DesignPoint(cus=max(c, 0), cost=split_only_cost(t, c))

        def as_point(t: str, v) -> DesignPoint:
            """Normalize a ``current`` entry and (re-)price it under the
            current load — the hysteresis baseline."""
            if not isinstance(v, DesignPoint):
                return stage1_point(t, int(v))
            sp = spaces.get(t)
            if self.stage1 is not None and sp is not None and v.cus > 0:
                cost = self.stage1.cost_of(cfgs[t], sp, concurrency(t), v,
                                           lengths.get(t, ()),
                                           src_lens.get(t, 0))
            else:
                cost = split_only_cost(t, v.cus)
            return dataclasses.replace(v, cost=cost)

        cur_points = {t: as_point(t, v) for t, v in current.items()}
        if not busy:
            self.runner_up = None
            self.predicted = None
            return dict(cur_points), "idle"

        # Stage-1 memo: one design-point search per (busy tenant, grant)
        memo: Dict[Tuple[str, int], DesignPoint] = {}

        def point(t: str, c: int) -> DesignPoint:
            if (t, c) not in memo:
                memo[(t, c)] = stage1_point(t, c)
            return memo[(t, c)]

        def makespan(points: Mapping[str, DesignPoint]) -> float:
            worst = 0.0
            for t in busy:
                p = points.get(t)
                cost = p.cost if p is not None else float("inf")
                worst = max(worst, demand[t] * cost)
            return worst

        # Stage 2: split search over Stage-1-optimal design points
        best_pts, best_cost = None, float("inf")
        second_pts, second_cost = None, float("inf")
        for split in _candidate_splits(num_cus, busy, demand):
            pts = {t: point(t, c) for t, c in zip(busy, split)}
            cost = makespan(pts)
            if cost < best_cost:
                second_pts, second_cost = best_pts, best_cost
                best_pts, best_cost = pts, cost
            elif cost < second_cost:
                second_pts, second_cost = pts, cost
        assert best_pts is not None

        cur_cost = makespan(cur_points)
        # JSON-safe telemetry: an admit tick's current makespan is infinite
        # (a parked tenant owes work) — record None, not float('inf')
        self.predicted = {
            "best_s": best_cost,
            "current_s": cur_cost if cur_cost != float("inf") else None}
        if cur_cost == float("inf"):
            self.runner_up = second_pts
            return best_pts, "admit"            # a parked tenant got work
        if cur_cost / max(best_cost, 1e-12) >= self.min_gain:
            self.runner_up = second_pts
            if self._sizes(best_pts) == self._sizes(cur_points):
                # same split, better per-tenant configs: a pure Stage-1
                # delta (slots / TP / ladder) applied with no CU move
                return best_pts, "retune"
            if len(busy) == 1:
                return best_pts, "unify"
            return best_pts, "rebalance"
        # staying put: the best candidate is what we'd switch to next —
        # that's the design worth prewarming while the fabric idles
        self.runner_up = (best_pts
                          if self._sizes(best_pts) != self._sizes(cur_points)
                          else second_pts)
        return dict(cur_points), "hysteresis"

    @staticmethod
    def _sizes(points: Mapping[str, DesignPoint]) -> Dict[str, int]:
        return {t: p.cus for t, p in points.items() if p.cus > 0}


def _compositions(total: int, parts: int):
    """All ways to write ``total`` as ``parts`` positive integers."""
    if parts == 1:
        yield (total,)
        return
    for cuts in itertools.combinations(range(1, total), parts - 1):
        prev, out = 0, []
        for c in cuts:
            out.append(c - prev)
            prev = c
        out.append(total - prev)
        yield tuple(out)


# exhaustive Stage-2-style enumeration is C(num_cus-1, tenants-1): fine on a
# board-scale fabric, explosive on a pod.  Past this budget, fall back to a
# demand-proportional water-filling split (the argmax of the monotone
# makespan model in the common case, computed in O(cus x tenants)).
MAX_ENUMERATED_SPLITS = 20_000


def _candidate_splits(num_cus: int, busy: Sequence[str],
                      demand: Mapping[str, float]):
    if math.comb(num_cus - 1, len(busy) - 1) <= MAX_ENUMERATED_SPLITS:
        yield from _compositions(num_cus, len(busy))
        return
    total = sum(demand[t] for t in busy)
    shares = [max(1, int(num_cus * demand[t] / total)) for t in busy]
    spare = num_cus - sum(shares)
    order = sorted(range(len(busy)), key=lambda i: -demand[busy[i]])
    i = 0
    while spare != 0:                    # hand leftovers to (or claw back
        j = order[i % len(order)]        # from) the most-loaded tenants
        step = 1 if spare > 0 else (-1 if shares[j] > 1 else 0)
        shares[j] += step
        spare -= step
        i += 1
    yield tuple(shares)


# ---------------------------------------------------------------------------
# data-parallel replica groups: N independent engines inside one grant
# ---------------------------------------------------------------------------

class _Replica:
    """One engine instance inside a :class:`ReplicaGroup`, plus its rid
    translation — engine rids are per-engine and restart on adoption, so
    the group owns the stable rid a caller sees (``to_group`` maps the
    engine's rid to it)."""

    __slots__ = ("engine", "to_group", "index", "obs")

    def __init__(self, engine: Engine, index: int = 0, obs=None):
        self.engine = engine
        self.to_group: Dict[int, int] = {}
        self.index = index
        # the Telemetry handle the engine records into: one registry per
        # replica (same labels), so the group can merge histograms across
        # replicas and harvest a retiring replica's registry on a dp shrink
        self.obs = obs


class ReplicaGroup:
    """``dp`` independent same-design engines tiling one tenant's CU grant
    (the DesignPoint ``dp`` axis — Herald-style replica tiling).

    One decode step's batched GEMV cannot use more slots than fit one
    replica's KV arena, so on a wide grant a memory-capped tenant is better
    served by N narrow engines on disjoint ``replica_submesh`` tiles, each
    decoding its own batch concurrently, than by one wide engine whose
    extra CUs shard an unchanged (memory-bound) batch.  The group IS the
    tenant's engine as far as the fabric is concerned — same Engine
    protocol — and owns:

    * **routing**: ``submit`` places each request on the least-loaded
      replica (fewest owed tokens, then shallowest queue, then lowest
      index — deterministic);
    * **merged load signals**: queue depth / active / owed tokens sum
      across replicas, arena pressure averages, ``recent_lengths`` is the
      union — so the policy observes the tenant, not a replica;
    * **the dp retune** (``apply`` with a changed ``point.dp``): retiring
      replicas are drained via :meth:`~DecodeEngine.evacuate` and their
      live requests adopted by survivors through exact cache-row copies
      (never re-prefilled — a different reduction order could flip an
      argmax), queues rebalance across the new replica set, and every
      request keeps its stable group rid, so per-request streams are
      bit-identical across the retune;
    * **warm compile across tiles**: every replica slice has its own mesh
      fingerprint, so ``warm_compile`` warms each of the ``dp`` slices
      through the shared executable cache (slices of equal width still
      share programs whenever their fingerprints coincide).

    Replicas at the same TP degree run identical XLA programs — the slices
    differ only in device ids — so which replica serves a request never
    changes its tokens (pinned by tests/test_fabric.py).
    """

    def __init__(self, wclass: str, model, params, serve_cfg: ServeConfig,
                 *, sub=None, rules: Optional[part.ShardingRules] = None,
                 exec_cache: Optional[ExecutableCache] = None,
                 cu_axis: str = "model", obs: Optional[Telemetry] = None):
        self._wclass = wclass
        self.workload_class = wclass
        self._model = model
        self._serve_cfg = serve_cfg
        self._rules = rules
        self._exec = (exec_cache if exec_cache is not None
                      else ExecutableCache())
        self._cu_axis = cu_axis
        self._granted = _mesh_of(sub)    # the group's full grant (unsliced)
        self._dp = 1
        self._next_rid = 0
        # group-level telemetry: spans go to the shared tracer; each
        # replica's engine records into a *fresh* registry under the same
        # labels, merged on demand by metrics()
        self._obs = obs if obs is not None else Telemetry()
        # harvested from retired replicas so results()/telemetry survive a
        # dp shrink
        self._retired_results: Dict[int, Any] = {}
        self._retired_builds = 0
        self._retired_reshards = 0
        self._retired_preempts = 0
        self._retired_metrics = MetricsRegistry()
        rep_obs = self._obs.fresh()
        self._replicas: List[_Replica] = [_Replica(build_engine(
            wclass, model, params, serve_cfg, mesh=self._granted,
            rules=rules, exec_cache=self._exec, obs=rep_obs), obs=rep_obs)]

    # -- grant geometry -------------------------------------------------
    def _grant_width(self, granted) -> Optional[int]:
        if granted is None or self._cu_axis not in granted.axis_names:
            return None
        ax = granted.axis_names.index(self._cu_axis)
        return granted.devices.shape[ax]

    @property
    def dp(self) -> int:
        """Live replica count."""
        return self._dp

    @property
    def replicas(self) -> Tuple[Engine, ...]:
        """The member engines, replica index order (tests/telemetry)."""
        return tuple(r.engine for r in self._replicas)

    # -- work ingestion / progress --------------------------------------
    def submit(self, tokens, max_new_tokens: int = 16, **kwargs) -> int:
        """Route one request to the least-loaded replica (owed tokens,
        then queue depth, then replica index — deterministic tie-break);
        returns its stable group rid."""
        rep = min(self._replicas,
                  key=lambda r: (r.engine.pending_tokens(),
                                 r.engine.queue_depth, r.index))
        erid = rep.engine.submit(tokens, max_new_tokens, **kwargs)
        grid = self._next_rid
        self._next_rid += 1
        rep.to_group[erid] = grid
        return grid

    def step(self) -> List[Tuple[int, Any]]:
        """Step every replica; emitted (rid, unit) pairs carry group rids."""
        out: List[Tuple[int, Any]] = []
        for rep in self._replicas:
            out.extend((rep.to_group[erid], v) for erid, v in
                       rep.engine.step())
        return out

    def results(self) -> Dict[int, Any]:
        out = dict(self._retired_results)
        for rep in self._replicas:
            out.update((rep.to_group[erid], v) for erid, v in
                       rep.engine.results().items())
        return out

    def snapshot(self) -> Dict[int, Any]:
        out = dict(self._retired_results)
        for rep in self._replicas:
            out.update((rep.to_group[erid], v) for erid, v in
                       rep.engine.snapshot().items())
        return out

    def run_to_completion(self, max_steps: int = 1000) -> Dict[int, Any]:
        """Step until idle (or ``max_steps``); returns ``snapshot()``."""
        for _ in range(max_steps):
            if not self.has_work:
                break
            self.step()
        return self.snapshot()

    # -- merged load signals --------------------------------------------
    @property
    def queue_depth(self) -> int:
        return sum(r.engine.queue_depth for r in self._replicas)

    @property
    def active_count(self) -> int:
        return sum(r.engine.active_count for r in self._replicas)

    @property
    def has_work(self) -> bool:
        return any(r.engine.has_work for r in self._replicas)

    def pending_tokens(self) -> int:
        return sum(r.engine.pending_tokens() for r in self._replicas)

    def arena_utilization(self) -> float:
        return (sum(r.engine.arena_utilization() for r in self._replicas)
                / max(len(self._replicas), 1))

    def recent_lengths(self) -> Tuple[int, ...]:
        return tuple(itertools.chain.from_iterable(
            r.engine.recent_lengths() for r in self._replicas))

    # -- preemption (the SLO scheduler's lever) --------------------------
    @property
    def preempted_depth(self) -> int:
        """Requests currently parked (preempted, awaiting re-admission)."""
        return sum(r.engine.preempted_depth for r in self._replicas)

    @property
    def preempt_count(self) -> int:
        return self._retired_preempts + sum(r.engine.preempt_count
                                            for r in self._replicas)

    def queue_head_wait_s(self, now: Optional[float] = None) -> float:
        """Longest head-of-line queue wait across replicas (seconds) —
        the TTFT burn the SLO scheduler compares against targets."""
        waits = [r.engine.queue_head_wait_s(now) for r in self._replicas
                 if r.engine.queue_depth > 0]
        return max(waits) if waits else 0.0

    def preempt_one(self) -> Optional[int]:
        """Preempt one live stream — exact device-state save, re-admitted
        later bit-identically — on the replica whose head-of-line request
        has waited longest (that is where a freed slot buys TTFT;
        replica-index tie-break keeps victim choice deterministic under
        equal waits).  Returns the victim's group rid, or None when no
        replica holds a preemptible stream."""
        order = sorted(
            self._replicas,
            key=lambda r: (-(r.engine.queue_head_wait_s()
                             if r.engine.queue_depth > 0 else 0.0),
                           r.index))
        for rep in order:
            erid = rep.engine.preempt_one()
            if erid is not None:
                return rep.to_group.get(erid, erid)
        return None

    # -- pass-throughs the fabric's DSE plumbing reads ------------------
    @property
    def cfg(self) -> ServeConfig:
        return self._replicas[0].engine.cfg

    @property
    def params(self):
        """Replica 0's device-resident params (tests/telemetry: replicas
        share one design, so one replica's placement is the tenant's)."""
        return self._replicas[0].engine.params

    @property
    def arena(self):
        """Replica 0's admission arena (slots are a per-replica knob, so
        per-slot sizing reads one replica); None for arena-less classes."""
        return getattr(self._replicas[0].engine, "arena", None)

    @property
    def _max_src(self) -> int:
        return getattr(self._replicas[0].engine, "_max_src", 0)

    # -- telemetry -------------------------------------------------------
    @property
    def reshard_count(self) -> int:
        return self._retired_reshards + sum(r.engine.reshard_count
                                            for r in self._replicas)

    @property
    def compile_builds(self) -> int:
        return self._retired_builds + sum(r.engine.compile_builds
                                          for r in self._replicas)

    def metrics(self) -> MetricsRegistry:
        """Merged view of every replica's metrics registry plus the
        registries harvested from replicas retired by dp shrinks.  All
        replicas record under identical labels into a shared fixed bucket
        layout, so the merge is element-wise and order-independent —
        quantiles of the merged histograms describe the *tenant*, not one
        replica."""
        merged = MetricsRegistry()
        merged.merge(self._retired_metrics)
        for rep in self._replicas:
            if rep.obs is not None:
                merged.merge(rep.obs.registry)
        return merged

    def latency_ms(self) -> Dict[str, Dict[str, float]]:
        """Merged-histogram latency summary (milliseconds) for the group's
        key per-step distributions — the compact ``stats()`` view of the
        full ``metrics()`` registry."""
        out: Dict[str, Dict[str, float]] = {}
        reg = self.metrics()
        for name in ("decode_step_s", "ttft_s", "queue_wait_s",
                     "prefill_s", "encode_s"):
            h = reg.merged_histogram(name)
            if h.count:
                out[name[:-2]] = {
                    "p50_ms": round(h.quantile(0.5) * 1e3, 4),
                    "p99_ms": round(h.quantile(0.99) * 1e3, 4),
                    "n": h.count,
                }
        return out

    def stats(self) -> Dict[str, Any]:
        """Group-merged snapshot (sums / averages across replicas), plus
        each replica's own ``stats()`` under ``per_replica``.

        A superset of one engine's ``stats()``: engine-specific keys the
        group doesn't know about (``bucket_hits``, ``seqs_done``, ...)
        pass through merged — numerics sum, dicts of numerics sum
        key-wise — so telemetry consumers see the tenant, not a wrapper.
        """
        per = [r.engine.stats() for r in self._replicas]
        merged: Dict[str, Any] = {}
        for key in per[0]:
            vals = [s[key] for s in per if key in s]
            head = vals[0]
            if isinstance(head, bool):
                merged[key] = head
            elif isinstance(head, (int, float)):
                merged[key] = type(head)(sum(vals))
            elif isinstance(head, dict) and all(
                    isinstance(v, (int, float)) and not isinstance(v, bool)
                    for d in vals for v in d.values()):
                tot: Dict[Any, Any] = {}
                for d in vals:
                    for k, v in d.items():
                        tot[k] = tot.get(k, 0) + v
                merged[key] = tot
            else:
                merged[key] = head       # replicas share one design
        merged.update({
            "workload_class": self.workload_class,
            "dp": self._dp,
            "queue_depth": self.queue_depth,
            "active": self.active_count,
            "pending_tokens": self.pending_tokens(),
            "arena_utilization": round(self.arena_utilization(), 4),
            "reshard_count": self.reshard_count,
            "compile_builds": self.compile_builds,
            "design": self.design(),
            "latency_ms": self.latency_ms(),
            "per_replica": per,
        })
        return merged

    # -- recomposition / design-point reconfiguration -------------------
    def design(self) -> Dict[str, Any]:
        """The group's applied design point: replica 0's engine knobs
        (replicas share one design) plus the replica count."""
        d = dict(self._replicas[0].engine.design())
        d["dp"] = self._dp
        return d

    def sync(self) -> None:
        for rep in self._replicas:
            rep.engine.sync()

    def reshard_to(self, sub) -> None:
        """Move the whole group onto a new grant, each replica onto its
        tile (current dp kept)."""
        self._granted = _mesh_of(sub)
        for rep in self._replicas:
            rep.engine.reshard_to(part.replica_submesh(
                self._granted, rep.index, self._dp, self._cu_axis))

    def apply(self, sub=None,
              point: Optional[DesignPoint] = None) -> Dict[str, Any]:
        """Apply a design-point delta group-wide (``None`` fields = keep).

        ``point.dp`` is consumed here: an unchanged dp fans the
        per-replica knobs out to every member engine (each on its
        ``replica_submesh`` tile of the — possibly new — grant); a changed
        dp runs the drain-and-rebalance retune (:meth:`_retarget_dp`),
        which preserves every request's stable rid and exact token stream.
        Returns the knobs actually applied (replica 0's view, plus ``dp``
        when it changed)."""
        point = point if point is not None else DesignPoint(cus=0)
        granted = _mesh_of(sub) if sub is not None else self._granted
        dp = point.dp if point.dp is not None else self._dp
        dp = max(int(dp), 1)
        width = self._grant_width(granted)
        if width is not None:
            dp = min(dp, width)
        eng_point = dataclasses.replace(point, dp=None)
        if dp != self._dp:
            applied = self._retarget_dp(granted, dp, eng_point)
            applied["dp"] = dp
        else:
            applied = {}
            for rep in self._replicas:
                s = (part.replica_submesh(granted, rep.index, dp,
                                          self._cu_axis)
                     if sub is not None else None)
                out = rep.engine.apply(s, eng_point)
                if rep.index == 0:
                    applied = out
        self._granted = granted
        return applied

    def _retarget_dp(self, granted, dp: int,
                     eng_point: DesignPoint) -> Dict[str, Any]:
        """Change the replica count live: drain, re-tile, rebalance.

        Retiring replicas are stripped of ALL work (live slots exported as
        exact host cache blocks, queues handed back) and their finished
        records / telemetry harvested; surviving replicas give up their
        queues too, then move onto their new ``replica_submesh`` tiles with
        their slot pools pre-grown to fit planned adoptions; growth
        replicas are built fresh on theirs.  Orphaned live requests are
        then adopted least-loaded-first via exact cache-row copies (bit-
        identical streams — never re-prefilled) and queues redistribute by
        the same order, every request keeping its stable group rid."""
        keep, retire = self._replicas[:dp], self._replicas[dp:]
        span_t0, span_src = time.perf_counter(), self._dp
        live: List[Tuple[int, Any, Any]] = []
        queued: List[Tuple[int, Any]] = []
        for rep in retire:
            l_reqs, q_reqs = rep.engine.evacuate()
            live.extend((rep.to_group[r.rid], r, blk) for r, blk in l_reqs)
            queued.extend((rep.to_group[r.rid], r) for r in q_reqs)
            for erid, v in rep.engine.results().items():
                if erid in rep.to_group:
                    self._retired_results[rep.to_group[erid]] = v
            self._retired_builds += rep.engine.compile_builds
            self._retired_reshards += rep.engine.reshard_count
            self._retired_preempts += rep.engine.preempt_count
            if rep.obs is not None:
                # histograms observed by the retiring replica stay in the
                # tenant's merged view (parallel to results/builds above)
                self._retired_metrics.merge(rep.obs.registry)
        for rep in keep:
            queued.extend((rep.to_group[r.rid], r)
                          for r in rep.engine.export_queued())
        # plan live adoptions before any engine moves: least-loaded target
        # first, replica-index tie-break (deterministic)
        occupancy = {i: (keep[i].engine.active_count if i < len(keep) else 0)
                     for i in range(dp)}
        placed: Dict[int, List] = {i: [] for i in range(dp)}
        for item in live:
            i = min(range(dp),
                    key=lambda j: (occupancy[j] + len(placed[j]), j))
            placed[i].append(item)
        applied: Dict[str, Any] = {}
        reps: List[_Replica] = []
        for i in range(dp):
            tile = part.replica_submesh(granted, i, dp, self._cu_axis)
            if i < len(keep):
                rep = keep[i]
                need = rep.engine.active_count + len(placed[i])
                slots = (eng_point.slots if eng_point.slots is not None
                         else rep.engine.design()["slots"])
                out = rep.engine.apply(tile, dataclasses.replace(
                    eng_point, slots=max(slots, need, 1)))
                if i == 0:
                    applied = out
            else:
                rep_obs = self._obs.fresh()
                rep = _Replica(self._build_replica(
                    tile, eng_point, min_slots=len(placed[i]), obs=rep_obs),
                    obs=rep_obs)
            rep.index = i
            reps.append(rep)
        self._replicas, self._dp = reps, dp
        for i, items in placed.items():
            rep = reps[i]
            for grid, req, block in items:
                rep.to_group[rep.engine.adopt_request(req, block)] = grid
        for grid, req in queued:
            rep = min(reps, key=lambda r: (r.engine.pending_tokens(),
                                           r.engine.queue_depth, r.index))
            rep.to_group[rep.engine.adopt_queued(req)] = grid
        if self._obs.enabled:
            self._obs.tracer.record(
                "dp_rebalance", span_t0, time.perf_counter(),
                {"src": span_src, "dst": dp, "moved": len(live),
                 "requeued": len(queued)})
        return applied

    def _build_replica(self, mesh, eng_point: DesignPoint,
                       min_slots: int = 0, obs: Optional[Telemetry] = None
                       ) -> Engine:
        """A fresh member engine on ``mesh`` at the group's design (dp
        grow) — sized to at least ``min_slots`` so planned adoptions fit."""
        d0 = self._replicas[0].engine.design()
        slots = (eng_point.slots if eng_point.slots is not None
                 else d0["slots"])
        cfg = dataclasses.replace(self._serve_cfg,
                                  max_slots=max(slots, min_slots, 1))
        ladder = (eng_point.buckets if eng_point.buckets is not None
                  else d0["buckets"])
        if ladder:
            cfg = dataclasses.replace(cfg, len_buckets=tuple(ladder))
        # copy replica 0's live params: the group holds no tree of its own,
        # which would pin a stale full copy on its first devices
        eng0 = self._replicas[0].engine
        params = eng0._param_plan.annotate(eng0.params)
        eng = build_engine(self._wclass, self._model, params, cfg,
                           mesh=mesh, rules=self._rules,
                           exec_cache=self._exec, obs=obs)
        tp = eng_point.tp if eng_point.tp is not None else d0["tp"]
        if tp is not None:
            eng.apply(None, DesignPoint(cus=0, tp=tp))
        return eng

    def warm_compile(self, sub,
                     point: Optional[DesignPoint] = None) -> int:
        """Pre-compile a candidate design point's programs for every
        replica tile of a candidate grant (``point.dp``, defaulting to the
        live dp), through the shared executable cache — each tile has its
        own mesh fingerprint, so warming replica 0's programs alone would
        leave the sibling tiles cold.  Returns cold builds performed."""
        point = point if point is not None else DesignPoint(cus=0)
        granted = _mesh_of(sub) if sub is not None else self._granted
        dp = point.dp if point.dp is not None else self._dp
        dp = max(int(dp), 1)
        width = self._grant_width(granted)
        if width is not None:
            dp = min(dp, width)
        eng_point = dataclasses.replace(point, dp=None)
        eng0 = self._replicas[0].engine
        if granted is None:
            return eng0.warm_compile(None, eng_point)
        return sum(eng0.warm_compile(
            part.replica_submesh(granted, i, dp, self._cu_axis), eng_point)
            for i in range(dp))


# ---------------------------------------------------------------------------
# the controller
# ---------------------------------------------------------------------------

class ComposedServer:
    """Multi-tenant serving on one composable fabric with live, delta
    recomposition between decode steps.

    Tenants are a *mixed fleet*: each runs the engine of its workload class
    (transformer decode / SSM recurrent decode / encoder embedding /
    enc-dec encode→decode — see ``repro.workloads``), and the policy prices
    each class by its bound resource.  All engines share one fabric-level AOT executable cache
    keyed by (config fingerprint, mesh fingerprint, shapes), so same-config
    tenants reuse each other's warm programs instead of compiling per
    engine.

    With a two-stage :class:`AnalyticalPolicy` (the default) the fabric
    runs the paper's full DSE in the serving loop: each decide tick it
    builds per-tenant :class:`TenantObservation` records (``observe``), the
    policy returns Stage-1-optimal design points per tenant (CUs + TP
    degree + replica count + slots + bucket ladder), and ``recompose``
    applies the deltas live — CU moves via ``reshard_to``-style migration,
    knob changes via ``Engine.apply`` (retunes; a changed ``dp`` triggers
    the ReplicaGroup's drain-and-rebalance), both re-entering the shared
    AOT cache under the new fingerprints so warm-compile covers the new
    programs.

    Each tenant's engine is a :class:`ReplicaGroup`: ``dp`` independent
    same-design engines tiling the tenant's grant, with requests routed to
    the least-loaded replica and load signals merged — at ``dp=1`` (the
    default) the group is a transparent wrapper over one engine.

    tp: shard each tenant's engine (params + pooled state) over its
        sub-mesh with ``serve_engine_rules`` so granted CUs buy measured
        tokens/s; off -> replicated engines (bit-identical resharding).
    warm: pre-compile a target composition's executables before committing
        a recomposition, so the first post-move step skips the XLA stall.
    prewarm_async: compile candidate compositions in a background thread
        while the old composition keeps serving; the switch commits on a
        later autoscale tick once the executables are ready.  Idle decide
        intervals additionally prewarm the policy's runner-up split
        speculatively, so the *next* plausible recomposition is warm too.
    """

    def __init__(self, mesh, tenants: Sequence[TenantSpec], *,
                 policy: Optional[AnalyticalPolicy] = None,
                 decide_every: int = 4, cu_axis: str = "model",
                 tp: bool = True, warm: bool = True,
                 prewarm_async: bool = False, telemetry: bool = True,
                 events_cap: int = 256, slo_preempt: bool = True):
        self.composer = MeshComposer(mesh, cu_axis=cu_axis)
        self.policy = policy
        self.decide_every = decide_every
        self.rules = serve_engine_rules() if tp else None
        self.warm = warm
        self.prewarm_async = prewarm_async
        self.specs = {t.name: t for t in tenants}
        # fabric-wide telemetry (repro.obs): one tracer for every span in
        # the stack, a fabric-level registry for step/SLO histograms, and
        # the predicted-vs-measured ledger.  telemetry=False swaps in a
        # disabled handle — every record call becomes a no-op; token
        # streams are bit-identical either way (pinned by tests/test_obs).
        self.obs = Telemetry() if telemetry else Telemetry.off()
        self.ledger = PredictionLedger()
        # recomposition history: bounded (a long-running fabric must not
        # grow per event) — stats() totals below survive eviction
        self.events: "collections.deque[RecompositionEvent]" = \
            collections.deque(maxlen=max(int(events_cap), 1))
        self._recompositions = 0
        self._retunes = 0
        self._recompose_seconds_total = 0.0
        self._warm_compile_seconds_total = 0.0
        self._stall_probe: Dict[str, RecompositionEvent] = {}
        self._step_no = 0
        self._tokens_emitted: Dict[str, int] = {t.name: 0 for t in tenants}
        # SLO-aware scheduler state: preemptions issued on latency grounds,
        # plus the per-tenant observed quantiles (ms) refreshed at decide
        # cadence — the per-step path must not merge histogram registries.
        # slo_preempt=False keeps attainment *reporting* while never
        # preempting (the slot-granular benchmark baseline arm).
        self.slo_preempt = slo_preempt
        self._slo_preemptions = 0
        self._slo_obs: Dict[Tuple[str, str], float] = {}
        self._executor: Optional[concurrent.futures.ThreadPoolExecutor] = None
        self._pending_prewarm: Optional[
            Tuple[Dict[str, DesignPoint], str, list]] = None
        # speculative runner-up prewarm bookkeeping
        self.speculative_prewarms = 0
        self._spec_warmed: set = set()
        self._spec_futures: List[concurrent.futures.Future] = []

        # initial composition: equal shares, remainder to the first tenants
        n = len(tenants)
        if n > self.composer.num_cus:
            raise ValueError(
                f"{n} tenants need at least {n} CUs; the fabric has "
                f"{self.composer.num_cus} (on CPU, fake more host devices "
                f"with XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        base, extra = divmod(self.composer.num_cus, n)
        sizes = {t.name: base + (1 if i < extra else 0)
                 for i, t in enumerate(tenants)}
        self.subs, _ = self.composer.recompose({}, sizes)

        # fabric-level executable cache: shared across every tenant engine
        self.exec_cache = ExecutableCache(capacity=128)
        self.cfgs: Dict[str, ModelConfig] = {}
        self.classes: Dict[str, str] = {}
        self.src_lens: Dict[str, int] = {}
        self.engines: Dict[str, ReplicaGroup] = {}
        for spec in tenants:
            cfg = (get_reduced(spec.arch) if spec.reduced
                   else get_config(spec.arch))
            model = build_model(cfg)
            # annotated (TP plans need the logical specs), and made on the
            # tenant's own sub-mesh: no full copy ever lands on device 0
            params = model.init(jax.random.key(spec.seed),
                                mesh=self.subs[spec.name].mesh,
                                rules=self.rules)
            wclass = (workload_class_of(cfg) if spec.workload == "auto"
                      else spec.workload)
            self.cfgs[spec.name] = cfg
            self.classes[spec.name] = wclass
            if wclass == ENCDEC:
                # prices the per-step cross-attention source-cache read
                self.src_lens[spec.name] = (spec.serve.max_src_len
                                            or spec.serve.max_len)
            self.engines[spec.name] = ReplicaGroup(
                wclass, model, params, spec.serve,
                sub=self.subs[spec.name], rules=self.rules,
                exec_cache=self.exec_cache, cu_axis=cu_axis,
                obs=self.obs.scoped(tenant=spec.name, wclass=wclass))
        # design-key memo for the prediction ledger's measured side (the
        # per-step path must not rebuild design dicts per tenant per step)
        self._design_keys: Dict[str, str] = {}
        self._refresh_design_keys()

    # ------------------------------------------------------------------
    def submit(self, tenant: str, tokens, max_new_tokens: int = 16,
               **kwargs) -> int:
        """Route one request to ``tenant``'s engine; returns its rid.
        Extra keywords pass through to the engine's submit (e.g. the
        enc-dec engine's forced-decoding ``prefix=``)."""
        return self.engines[tenant].submit(tokens, max_new_tokens, **kwargs)

    def sizes(self) -> Dict[str, int]:
        """Current composition: tenant -> CUs held (0 = parked)."""
        return {t: len(self.subs[t].cu_ids) if t in self.subs else 0
                for t in self.engines}

    def loads(self) -> Dict[str, TenantLoad]:
        """Per-tenant load signals sampled from the engines (group-merged
        across replicas).  Kept for telemetry/examples; the policy's
        ``decide`` input is :meth:`observe`."""
        return {t: TenantLoad(eng.pending_tokens(), eng.queue_depth,
                              eng.active_count, eng.arena_utilization())
                for t, eng in self.engines.items()}

    def observe(self) -> Dict[str, TenantObservation]:
        """Per-tenant :class:`TenantObservation` — the one record
        ``AnalyticalPolicy.decide`` consumes: replica-merged load signals,
        workload class, observed job lengths, enc-dec source capacity and
        the tenant's Stage-1 design space."""
        spaces = self._design_spaces() or {}
        return {t: TenantObservation(
                    pending_tokens=eng.pending_tokens(),
                    queue_depth=eng.queue_depth,
                    active=eng.active_count,
                    arena_utilization=eng.arena_utilization(),
                    wclass=self.classes[t],
                    recent_lengths=eng.recent_lengths(),
                    src_len=self.src_lens.get(t, 0),
                    space=spaces.get(t))
                for t, eng in self.engines.items()}

    # ------------------------------------------------------------------
    def step(self) -> Dict[str, List[Tuple[int, int]]]:
        """One fabric iteration: SLO admission check, then step every
        composed (non-parked) tenant, then maybe recompose.  Returns
        per-tenant emitted (rid, token)."""
        if (self.decide_every > 0
                and self._step_no % self.decide_every == 0):
            self._refresh_slo_observed()
        self._slo_schedule()
        emitted = {}
        for t, eng in self.engines.items():
            if t not in self.subs:
                continue                      # parked: no CUs this interval
            probe = self._stall_probe.pop(t, None)
            busy = eng.has_work
            q0 = eng.queue_depth
            t0 = time.monotonic()
            out = eng.step()
            if probe is not None:
                # pipelined dispatch returns before the step executes; the
                # probed post-move step must cover the whole step (compile
                # when cold + execution), not just the async dispatch
                eng.sync()
            dt = time.monotonic() - t0
            if probe is not None:
                probe.post_step_seconds[t] = dt
            elif busy and eng.queue_depth == q0 and self.obs.enabled:
                # decode percentiles only: idle no-op steps would deflate
                # them; admission steps (blocking prefill) and probed
                # full-sync steps would inflate them.  The timing rides the
                # engines' existing pipelined-dispatch sync point — the
                # registry/ledger writes below are host-side only.
                reg = self.obs.registry
                reg.histogram("decode_step_s", tenant=t).observe(dt)
                if out:
                    unit = dt / len(out)
                    reg.histogram("per_token_s", tenant=t).observe(unit)
                    self.ledger.observe(t, self._design_keys[t], unit,
                                        wclass=self.classes[t])
            if self.obs.enabled:
                self.obs.registry.gauge("queue_depth", tenant=t).value = \
                    eng.queue_depth
            self._tokens_emitted[t] += len(out)
            if out:
                emitted[t] = out
        self._step_no += 1
        if (self.policy is not None and self.decide_every > 0
                and self._step_no % self.decide_every == 0):
            self.autoscale()
        return emitted

    # ------------------------------------------------------------------
    # serving-side DSE plumbing (Stage-1 inputs, applied design points)
    # ------------------------------------------------------------------
    def _design_spaces(self) -> Optional[Dict[str, TenantDesignSpace]]:
        """Per-tenant Stage-1 search bounds, snapshotted from the engines
        each decide tick (None when the policy is split-only)."""
        if self.policy is None or self.policy.stage1 is None:
            return None
        out = {}
        for t, eng in self.engines.items():
            d = eng.design()
            arena = getattr(eng, "arena", None)
            per_slot = (arena.capacity // max(d["slots"], 1)
                        if arena is not None else 0)
            paged = isinstance(arena, PagedArena)
            out[t] = TenantDesignSpace(
                wclass=self.classes[t],
                max_len=eng.cfg.max_len,
                max_src=getattr(eng, "_max_src", 0),
                base_slots=d["slots"],
                base_buckets=tuple(d["buckets"] or ()),
                base_tp=d["tp"],
                base_dp=d.get("dp", 1),
                per_slot_elems=per_slot,
                tp_allowed=self.rules is not None,
                slot_cap=max(eng.cfg.slot_cap, 1),
                dp_cap=max(self.specs[t].dp_cap, 1),
                # SSM/hybrid archs prefill at exact lengths — no padding
                # for Stage 1 to price on their admission path
                prefill_bucket=(eng.cfg.prefill_bucket
                                if getattr(self.cfgs[t], "ssm", None) is None
                                else 0),
                use_kernels=getattr(eng.cfg, "use_kernels", True),
                # paged KV arenas admit by expected page footprint, not the
                # worst-case slot reservation — Stage 1 prices accordingly
                paged=paged,
                page_rows=arena.page_rows if paged else 0,
                page_elems=arena.page_elems if paged else 0)
        return out

    def _applied_points(self) -> Dict[str, DesignPoint]:
        """The live composition as applied design points (the policy's
        hysteresis baseline; parked tenants carry cus 0)."""
        out = {}
        for t, eng in self.engines.items():
            c = len(self.subs[t].cu_ids) if t in self.subs else 0
            d = eng.design()
            out[t] = DesignPoint(
                cus=c, tp=d["tp"], slots=d["slots"],
                buckets=tuple(d["buckets"]) if d["buckets"] else None,
                dp=d.get("dp", 1))
        return out

    def _refresh_design_keys(self) -> None:
        """Re-memoize each tenant's compact design key (``serve.dse
        .design_key``) for the prediction ledger's per-step measured side.
        Called at construction and after every recomposition — the hot
        step path must not rebuild design dicts per tenant per step."""
        for t, eng in self.engines.items():
            cus = len(self.subs[t].cu_ids) if t in self.subs else 0
            self._design_keys[t] = design_key(cus, eng.design())

    def _knob_delta(self, t: str, p: DesignPoint) -> Dict[str, object]:
        """Engine-knob overrides that actually change tenant ``t``'s
        configuration when design point ``p`` commits (None knobs keep; a
        slot shrink clamps at the per-replica live occupancy — streams are
        migrated, never evicted).  TP degree and slots compare at the
        point's replica-tile width: a group at dp computes on
        ``cus // dp``-wide tiles, not the whole grant."""
        eng = self.engines[t]
        d = eng.design()
        out: Dict[str, object] = {}
        dp_now = d.get("dp", 1) or 1
        dp_want = dp_now
        if p.dp is not None:
            dp_want = max(1, min(p.dp, max(p.cus, 1)))
            if dp_want != dp_now:
                out["dp"] = dp_want
        width = max(p.cus // max(dp_want, 1), 1)
        if p.tp is not None:
            want = min(p.tp, width)
            would = min(d["tp"], width) if d["tp"] else width
            if want != would:
                out["tp"] = p.tp
        if p.slots is not None:
            want_s = max(p.slots, -(-eng.active_count // max(dp_want, 1)))
            if want_s != d["slots"]:
                out["slots"] = want_s
        if p.buckets is not None and d["buckets"] is not None \
                and tuple(p.buckets) != tuple(d["buckets"]):
            out["buckets"] = tuple(p.buckets)
        return out

    @staticmethod
    def _delta_point(p: DesignPoint,
                     knobs: Optional[Dict[str, object]]) -> DesignPoint:
        """A knob delta as the DesignPoint handed to ``Engine.apply`` /
        ``warm_compile`` (absent knobs become None = keep)."""
        kn = knobs or {}
        return DesignPoint(cus=p.cus, tp=kn.get("tp"),
                           slots=kn.get("slots"),
                           buckets=kn.get("buckets"), dp=kn.get("dp"))

    def _no_change(self, points: Mapping[str, DesignPoint]) -> bool:
        """True when applying ``points`` would change nothing: same CU
        split AND no engine-knob delta on any composed tenant."""
        sizes = {t: p.cus for t, p in points.items() if p.cus > 0}
        if sizes != self._normalized(self.sizes()):
            return False
        return all(not self._knob_delta(t, p) for t, p in points.items()
                   if p.cus > 0)

    def autoscale(self) -> Optional[RecompositionEvent]:
        """Consult the policy; apply the recomposition it asks for.

        With ``prewarm_async`` the switch is two-phase: kick background
        compiles for the chosen composition (at its target design points),
        keep serving on the current one, and commit on a later tick once
        every executable is warm."""
        if self._pending_prewarm is not None:
            target, reason, futures = self._pending_prewarm
            if not all(f.done() for f in futures):
                return None               # still compiling in the background
            self._pending_prewarm = None
            for f in futures:
                f.result()                # surface background build errors
            if self._no_change(target):
                return None
            return self.recompose(target, reason=reason, overlapped=True)

        with self.obs.span("decide", step=self._step_no):
            target, reason = self.policy.decide(
                self.observe(), self.cfgs, self._applied_points(),
                self.composer.num_cus)
        target = {t: p for t, p in target.items() if p.cus > 0}
        if self._no_change(target):
            # idle decide interval: nothing committed — speculatively warm
            # the policy's runner-up design so the *next* plausible switch
            # is already compiled when its gain clears hysteresis
            self._speculative_prewarm()
            return None
        if self.warm and self.prewarm_async:
            futures = self._warm_design(target)
            self._pending_prewarm = (target, reason, futures)
            return None
        return self.recompose(target, reason=reason)

    def _warm_design(self, points: Mapping[str, DesignPoint]) -> list:
        """Submit background warm compiles for a candidate design — every
        tenant a CU move or a knob delta would touch, each warmed at its
        target design point's overrides.  Returns the futures."""
        new_subs, delta = self.composer.recompose(
            self.subs, {t: p.cus for t, p in points.items()})
        touched = set(delta.moved + delta.admitted)
        touched |= {t for t, p in points.items() if self._knob_delta(t, p)}
        return [self._pool().submit(
            lambda t=t, pt=self._delta_point(
                points[t], self._knob_delta(t, points[t])):
            self.engines[t].warm_compile(new_subs[t], pt))
            for t in sorted(touched)]

    def _speculative_prewarm(self) -> None:
        """Warm the runner-up candidate design in the background.

        Reuses the ``prewarm_async`` machinery (same single-worker pool, so
        speculative compiles never contend with a committed prewarm) and is
        gated on it: synchronous fabrics shouldn't burn serving time on
        compositions that may never commit.  Each distinct runner-up —
        keyed on the FULL design point (composition + per-tenant config) —
        is warmed once; ``warm_compile`` itself is idempotent on the shared
        executable cache."""
        # surface errors from (and drop) finished speculative compiles
        pending = []
        for f in self._spec_futures:
            if f.done():
                f.result()
            else:
                pending.append(f)
        self._spec_futures = pending
        ru = self.policy.runner_up if self.policy is not None else None
        if not (self.warm and self.prewarm_async and ru):
            return
        ru = {t: p for t, p in ru.items() if p.cus > 0}
        if not ru or self._no_change(ru):
            return
        key = tuple(sorted((t, p.cus, p.tp, p.slots, p.dp,
                            tuple(p.buckets or ())) for t, p in ru.items()))
        if key in self._spec_warmed:
            return
        if len(self._spec_warmed) > 64:      # long-lived fabric: re-warm ok
            self._spec_warmed.clear()
        futures = self._warm_design(ru)
        if not futures:
            return
        self._spec_warmed.add(key)
        self.speculative_prewarms += 1
        self._spec_futures.extend(futures)

    @staticmethod
    def _normalized(sizes: Mapping[str, int]) -> Dict[str, int]:
        return {t: s for t, s in sizes.items() if s > 0}

    def _pool(self) -> concurrent.futures.ThreadPoolExecutor:
        if self._executor is None:
            self._executor = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="prewarm")
        return self._executor

    def recompose(self, target_sizes: Mapping[str, object], *,
                  reason: str = "manual",
                  overlapped: bool = False) -> RecompositionEvent:
        """Live recomposition: grow/shrink/admit/park tenants AND apply
        per-tenant design-point deltas (DSE Stage-1 knobs).

        ``target_sizes`` maps tenant -> CU count (int, the pre-DSE contract)
        or DesignPoint (CUs + TP degree + replica count + slots + bucket
        ladder).  Only moved tenants pay a state migration; unchanged ones
        keep their devices — but a tenant whose knobs changed with its CU
        set intact is *retuned* in place (``Engine.apply``, draining
        nothing: live slots migrate inside the resize, and a dp retune
        rebalances them across the new replica set).  With warming on, the
        target composition's executables are compiled at the target design
        points before any state moves, so the post-move step is
        stall-free."""
        rc_t0 = time.perf_counter()
        before = self.sizes()
        points = {t: (v if isinstance(v, DesignPoint)
                      else DesignPoint(cus=int(v)))
                  for t, v in target_sizes.items()}
        sizes = {t: p.cus for t, p in points.items()}
        new_subs, delta = self.composer.recompose(self.subs, sizes)
        knobs = {t: self._knob_delta(t, p) for t, p in points.items()
                 if p.cus > 0}
        moved = delta.moved + delta.admitted
        retuned = tuple(t for t in knobs
                        if knobs[t] and t not in moved)
        touched = moved + retuned
        warm_s, warm_builds = 0.0, 0
        if self.warm:
            w0 = time.monotonic()
            for t in touched:
                warm_builds += self.engines[t].warm_compile(
                    new_subs[t],
                    self._delta_point(points[t], knobs.get(t)))
            warm_s = time.monotonic() - w0
        t0 = time.monotonic()
        applied: Dict[str, Dict] = {}
        for t in touched:
            eng = self.engines[t]
            with self.obs.span("migrate", tenant=t,
                               kind="move" if t in moved else "retune"):
                out = eng.apply(new_subs[t] if t in moved else None,
                                self._delta_point(points[t], knobs.get(t)))
                if out:
                    applied[t] = out
                eng.sync()
        self.subs = new_subs
        # the committed move changes device assignments, so a previously
        # prewarmed runner-up design now maps to different sub-meshes
        # (different mesh fingerprints): let it be warmed again
        self._spec_warmed.clear()
        seconds = time.monotonic() - t0
        event = RecompositionEvent(
            step=self._step_no, sizes_before=before, sizes_after=self.sizes(),
            moved=moved, unchanged=delta.unchanged,
            parked=delta.evicted, seconds=seconds, reason=reason,
            retuned=retuned, design=applied,
            warm_compile_seconds=warm_s, warm_builds=warm_builds,
            overlapped=overlapped)
        for t in touched:
            self._stall_probe[t] = event
        self.events.append(event)
        # fold-before-evict totals: the deque above is bounded, so stats()
        # aggregates accumulate here instead of re-scanning the history
        self._recompositions += 1
        self._retunes += len(retuned)
        self._recompose_seconds_total += seconds
        self._warm_compile_seconds_total += warm_s
        # predicted-vs-measured accounting: refresh the per-tenant design
        # keys for the committed composition, then record each touched
        # tenant's Stage-1 predicted per-unit cost next to the measured
        # per-step histogram that accumulates under the same key
        self._refresh_design_keys()
        for t in touched:
            p = points.get(t)
            if p is not None:
                self.ledger.commit(t, self.classes[t],
                                   self._design_keys[t], p.cost)
        if self.obs.enabled:
            self.obs.tracer.record(
                "recompose", rc_t0, time.perf_counter(),
                {"reason": reason, "moved": list(moved),
                 "retuned": list(retuned), "parked": list(delta.evicted),
                 "warm_builds": warm_builds},
                cat="recompose")
            self.obs.inc("recompositions")
        return event

    def unify(self, tenant: str, *, reason: str = "unify"
              ) -> RecompositionEvent:
        """The monolithic composition: the whole fabric for one tenant."""
        return self.recompose({tenant: self.composer.num_cus}, reason=reason)

    # ------------------------------------------------------------------
    def pending(self) -> int:
        """Total owed work units across tenants (decode steps / prompt
        tokens by class)."""
        return sum(ld.pending_tokens for ld in self.loads().values())

    def drain(self, max_steps: int = 10_000) -> Dict[str, Dict[int, List[int]]]:
        """Step until every tenant's queue, slots and in-flight dispatches
        are empty; returns per-tenant {rid: tokens} for all requests seen."""
        for _ in range(max_steps):
            busy = [t for t, eng in self.engines.items() if eng.has_work]
            if not busy:
                break
            if any(t not in self.subs for t in busy) and self.policy is None:
                # no policy to re-admit a parked tenant: give it CUs back
                self.recompose({t: 0 for t in self.engines} |
                               {t: self.composer.num_cus // max(len(busy), 1)
                                for t in busy}, reason="drain")
            self.step()
        return self.results()

    def results(self) -> Dict[str, Dict[int, List[int]]]:
        """Per-tenant ``snapshot()``: every request seen -> emitted units
        (tokens, or embedding components for encoder tenants)."""
        return {t: eng.snapshot() for t, eng in self.engines.items()}

    def decode_step_ms(self) -> Dict[str, Dict[str, float]]:
        """Per-tenant decode step latency percentiles (milliseconds), read
        from the fabric registry's ``decode_step_s{tenant}`` histograms
        (empty with telemetry off — latency accounting is the registry's)."""
        out = {}
        for t in self.engines:
            h = self.obs.registry.merged_histogram("decode_step_s", tenant=t)
            if h.count == 0:
                continue
            out[t] = {"p50": round(h.quantile(0.5) * 1e3, 3),
                      "p95": round(h.quantile(0.95) * 1e3, 3),
                      "n": h.count}
        return out

    # ------------------------------------------------------------------
    # telemetry export surface (repro.obs)
    # ------------------------------------------------------------------
    def metrics(self) -> MetricsRegistry:
        """One merged registry across the whole stack: the fabric's own
        step/SLO histograms plus every tenant engine's per-replica
        registries (retired dp replicas included), with the shared
        executable cache folded in as gauges."""
        merged = MetricsRegistry()
        merged.merge(self.obs.registry)
        for eng in self.engines.values():
            merged.merge(eng.metrics())
        snap = self.exec_cache.snapshot()
        for k, v in snap.items():
            merged.gauge(f"exec_cache_{k}").set(float(v))
        merged.counter("recompositions_total").inc(self._recompositions)
        merged.counter("retunes_total").inc(self._retunes)
        return merged

    def metrics_snapshot(self) -> Dict[str, object]:
        """JSON-friendly dump of :meth:`metrics` (the ``--metrics-json``
        payload)."""
        return self.metrics().snapshot()

    def dump_trace(self, path: str) -> str:
        """Write the span ring buffer as Chrome/Perfetto trace-event JSON
        (load in ``chrome://tracing`` or https://ui.perfetto.dev); returns
        the path written."""
        return self.obs.tracer.dump(path)

    # ------------------------------------------------------------------
    # SLO-aware scheduling (docs/scheduling.md)
    # ------------------------------------------------------------------
    def _refresh_slo_observed(self) -> None:
        """Re-sample each SLO-tracked tenant's observed p99s (ms) from the
        obs histograms.  Decide-cadence only: merging replica registries
        per step would tax the hot path, and the observed quantiles move
        slowly anyway."""
        for t, eng in self.engines.items():
            slo = self.specs[t].slo
            if slo is None or not slo.tracked():
                continue
            if slo.ttft_p99_ms > 0:
                h = eng.metrics().merged_histogram("ttft_s")
                if h.count:
                    self._slo_obs[(t, "ttft_p99_ms")] = \
                        h.quantile(0.99) * 1e3
            if slo.per_token_p99_ms > 0:
                h = self.obs.registry.merged_histogram("per_token_s",
                                                       tenant=t)
                if h.count:
                    self._slo_obs[(t, "per_token_p99_ms")] = \
                        h.quantile(0.99) * 1e3

    def _slo_preempt(self, t: str, why: str) -> bool:
        rid = self.engines[t].preempt_one()
        if rid is None:
            return False
        self._slo_preemptions += 1
        if self.obs.enabled:
            self.obs.inc("slo_preemptions")
            self.obs.inc(f"slo_preemptions_{why}")
        return True

    def _slo_schedule(self) -> None:
        """The SLO-aware admission/preemption pass, run before each fabric
        step.

        TTFT protection: a tenant whose head-of-line queue wait has burned
        half its p99 TTFT budget (a quarter once its *observed* TTFT p99
        is already over target) gets its slackest live stream preempted,
        so the freed slot/pages admit the waiting request in this very
        step's ``_admit``.  Per-token protection: a tenant whose observed
        per-token p99 breached target sheds one stream (smaller batch =>
        faster steps), at most one parked at a time so shedding never
        cascades.  Preemption saves exact device state; the victim
        re-admits later and continues bit-identically (greedy decode rows
        are batch-independent, pinned by tests/test_preempt_chaos.py)."""
        if not self.slo_preempt:
            return
        for t, eng in self.engines.items():
            if t not in self.subs:
                continue                     # parked tenant: no CUs at all
            slo = self.specs[t].slo
            if slo is None or not slo.tracked():
                continue
            if slo.ttft_p99_ms > 0 and eng.queue_depth > 0:
                breached = (self._slo_obs.get((t, "ttft_p99_ms"), 0.0)
                            > slo.ttft_p99_ms)
                frac = 0.25 if breached else 0.5
                if (eng.queue_head_wait_s() * 1e3
                        >= frac * slo.ttft_p99_ms):
                    if self._slo_preempt(t, "ttft"):
                        continue
            if (slo.per_token_p99_ms > 0 and eng.active_count > 1
                    and eng.preempted_depth == 0
                    and self._slo_obs.get((t, "per_token_p99_ms"), 0.0)
                    > slo.per_token_p99_ms):
                self._slo_preempt(t, "per_token")

    def slo_attainment(self) -> Dict[str, object]:
        """Per-tenant SLO attainment: for every declared target, the
        fraction of observed TTFTs / per-token latencies at or under it
        (``Histogram.fraction_below``) and whether that fraction meets the
        target's own percentile, plus the preemption counters the
        scheduler spent getting there.  TTFT histograms come from the
        engines' merged registries; per-token from the fabric's filtered
        steady-state histograms (same sources as :meth:`slo_summary`)."""
        merged = self.metrics()
        tenants: Dict[str, Dict[str, object]] = {}
        for t, eng in self.engines.items():
            slo = self.specs[t].slo
            if slo is None or not slo.tracked():
                continue
            row: Dict[str, object] = {
                "class": self.classes[t],
                "preemptions": int(getattr(eng, "preempt_count", 0)),
                "parked": int(getattr(eng, "preempted_depth", 0)),
            }
            for metric, name, src, targets in (
                    ("ttft", "ttft_s", merged,
                     ((0.50, slo.ttft_p50_ms), (0.99, slo.ttft_p99_ms))),
                    ("per_token", "per_token_s", self.obs.registry,
                     ((0.50, slo.per_token_p50_ms),
                      (0.99, slo.per_token_p99_ms)))):
                if not any(tgt > 0 for _, tgt in targets):
                    continue
                h = src.merged_histogram(name, tenant=t)
                ent: Dict[str, object] = {"n": h.count}
                for q, tgt in targets:
                    if tgt <= 0:
                        continue
                    att = (h.fraction_below(tgt * 1e-3)
                           if h.count else 0.0)
                    ent[f"p{int(q * 100)}"] = {
                        "target_ms": tgt,
                        "observed_ms": (round(h.quantile(q) * 1e3, 3)
                                        if h.count else None),
                        "attainment": round(att, 4),
                        "met": bool(h.count) and att + 1e-12 >= q,
                    }
                row[metric] = ent
            tenants[t] = row
        return {"tenants": tenants,
                "slo_preemptions": self._slo_preemptions}

    def slo_summary(self) -> Dict[str, object]:
        """Per-tenant serving SLO percentiles (milliseconds): TTFT,
        per-token latency, decode-step latency and queue wait, plus the
        predicted-vs-measured aggregate.  TTFT/queue-wait come from the
        engines' merged registries; per-token and step latency from the
        fabric-level filtered histograms."""
        merged = self.metrics()
        per_tenant: Dict[str, Dict[str, object]] = {}
        for t in self.engines:
            row: Dict[str, object] = {"class": self.classes[t]}
            for name, label in (("ttft_s", "ttft_ms"),
                                ("queue_wait_s", "queue_wait_ms"),
                                ("per_token_s", "per_token_ms"),
                                ("decode_step_s", "decode_step_ms")):
                # step latency comes from the fabric-level filtered
                # histogram (steady-state decode only); the merged view
                # would fold in the engines' unfiltered step timer, which
                # includes cold-compile and admission-adjacent steps
                src = (self.obs.registry if name in
                       ("decode_step_s", "per_token_s") else merged)
                h = src.merged_histogram(name, tenant=t)
                if h.count == 0:
                    continue
                row[label] = {"p50": round(h.quantile(0.5) * 1e3, 4),
                              "p99": round(h.quantile(0.99) * 1e3, 4),
                              "n": h.count}
            per_tenant[t] = row
        return {"tenants": per_tenant,
                "predicted_vs_measured":
                    self.ledger.summary()["aggregate"]}

    def stats(self) -> Dict[str, object]:
        """Fabric-wide telemetry: per-tenant emitted units and classes,
        recomposition timings (seconds), per-tenant migrations and cold
        builds, shared-cache hit counts, speculative prewarms, decode step
        latency percentiles (ms), predicted-vs-measured accounting and the
        current device composition.  Counts and totals come from fold
        counters, not the bounded ``events`` deque — they stay correct
        after old events are evicted."""
        return {
            "steps": self._step_no,
            "workload_classes": dict(self.classes),
            # per-tenant emitted units: tokens for decode/ssm tenants,
            # completed sequences (embeddings) for encoder tenants
            "tokens_emitted": dict(self._tokens_emitted),
            # applied design points (the serving DSE's Stage-1 knobs)
            "design_points": {
                t: {"cus": len(self.subs[t].cu_ids) if t in self.subs else 0,
                    "tp": d["tp"], "slots": d["slots"],
                    "buckets": list(d["buckets"]) if d["buckets"] else None,
                    "dp": d.get("dp", 1)}
                for t, d in ((t, eng.design())
                             for t, eng in self.engines.items())},
            "retunes": self._retunes,
            "recompositions": self._recompositions,
            "recompose_seconds": round(self._recompose_seconds_total, 4),
            "warm_compile_seconds": round(self._warm_compile_seconds_total,
                                          4),
            "recompose_seconds_recent": [round(e.seconds, 4)
                                         for e in self.events],
            "preemptions": {t: int(getattr(eng, "preempt_count", 0))
                            for t, eng in self.engines.items()},
            "slo_preemptions": self._slo_preemptions,
            "reshards_per_tenant": {t: eng.reshard_count
                                    for t, eng in self.engines.items()},
            "compile_builds": {t: eng.compile_builds
                               for t, eng in self.engines.items()},
            "shared_exec_cache": {"builds": self.exec_cache.builds,
                                  "hits": self.exec_cache.hits},
            "speculative_prewarms": self.speculative_prewarms,
            "decode_step_ms": self.decode_step_ms(),
            "predicted_vs_measured": self.ledger.summary(),
            "composition": {t: list(self.subs[t].cu_ids)
                            for t in self.subs},
        }
