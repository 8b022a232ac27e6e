"""Encoder engine: prefill-only / embedding workloads on the composed fabric.

The third workload class (FILCO's diverse-workload story): encoder jobs are
**compute-bound full-sequence matmuls** — no decode loop, no growing cache,
no per-token host round-trips.  A tenant serving embedding traffic therefore
wants CUs for raw FLOP/s, while a decode tenant wants them for weight/KV
bandwidth and an SSM tenant for state bandwidth; the class-aware policy
prices each accordingly, and the split search allocates the fabric by each
class's actual bound resource.

Design (throughput-oriented):

* jobs queue on the host; each ``step()`` runs ONE batched encoder forward
  over up to ``max_slots`` jobs and completes them — there is no in-flight
  device state between steps, so ``reshard_to`` only moves params;
* the batch compiles at ``(max_slots, bucket)`` for each sequence-length
  bucket of ``ServeConfig.len_buckets`` (always including ``max_len``); a
  step groups its jobs by each job's OWN smallest fitting bucket and runs
  one batched forward per group, cutting the padded FLOPs of short
  embedding jobs.  The bucket ladder is static, so ``warm_compile`` still
  fully covers a candidate composition — and the ladder is a *runtime
  design knob*: ``apply(point.buckets)`` swaps it live (the serving-side
  DSE Stage 1 picks it from observed job lengths).  ``stats()`` reports
  per-bucket hit counts (jobs served per bucket);
* each job's output is the masked mean over its valid positions of
  :meth:`Model.encode` hidden states, in fp32 — a (d_model,) embedding.
  Causal stacks are padding-proof by construction; bidirectional encoder
  stacks mask each row's own key padding (``Model.encode(lens=...)``), so a
  job's embedding is bit-identical across bucket ladders — which is what
  makes the live ladder swap numerics-safe.

Jobs longer than ``max_len`` are rejected-but-recorded (empty embedding),
mirroring the decode engine's contract that requests never vanish.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.composer import mesh_fingerprint
from repro.core.dse import DesignPoint
from repro.distribution import partitioning as part
from repro.models.model import Model
from repro.obs import Telemetry
from repro.workloads.base import (DecayedLengthEstimator, EngineTelemetry,
                                  length_buckets, pick_bucket,
                                  sanitize_check, sanitize_guard)
from repro.workloads.compile_cache import ExecutableCache
from repro.workloads.decode import ServeConfig, _mesh_of, _rules_fp


@dataclasses.dataclass
class EncodeJob:
    """One embedding job's host-side record (``embedding`` is the fp32
    mean-pooled (d_model,) vector once done; ``[]`` marks a reject)."""

    rid: int
    tokens: np.ndarray
    embedding: Optional[List[float]] = None
    done: bool = False
    # perf_counter() at submit — SLO telemetry (queue wait / time-to-result);
    # survives adoption by a sibling replica.  0.0 = unknown.
    submitted_s: float = 0.0


class EncoderEngine(EngineTelemetry):
    """Prefill-only embedding serving (the ``encoder`` workload class):
    each step batches queued jobs through one bucketed compiled
    ``Model.encode`` forward and completes them — no decode loop, no
    in-flight device state (see the module docstring; the Engine-protocol
    contract is docs/workloads.md)."""

    workload_class = "encoder"

    def __init__(self, model: Model, params, cfg: ServeConfig,
                 mesh=None, rules: Optional[part.ShardingRules] = None,
                 exec_cache: Optional[ExecutableCache] = None,
                 obs: Optional[Telemetry] = None):
        self.model = model
        self.cfg = cfg
        self.rules = rules
        self._obs = obs if obs is not None else Telemetry()
        self._rules_eff = rules or part.ShardingRules(rules={})
        self.reshard_count = 0
        params = model.heads_major(params)    # as DecodeEngine holds them
        self._param_plan = part.ShardingPlan.of(params)
        self.params = part.strip(params)
        if rules is not None and not self._param_plan.annotated:
            raise ValueError(
                "tensor-parallel serving needs annotated params: pass "
                "model.init(...) without strip() when rules are given")
        self._exec = exec_cache if exec_cache is not None else ExecutableCache()
        self._own_builds = 0
        self._tp: Optional[int] = None
        self._granted = None
        self._recent_lens = DecayedLengthEstimator()
        self._buckets = length_buckets(cfg.len_buckets, cfg.max_len)
        self._bucket_hits: Dict[int, int] = {b: 0 for b in self._buckets}
        self._cfg_key = self._config_key(cfg.max_slots)
        self._queue: List[EncodeJob] = []
        self._finished: Dict[int, List[float]] = {}
        self.finished_cap = 10_000
        self._next_rid = 0
        self._seqs_done = 0
        self.mesh: Optional[Mesh] = None
        self.reshard_to(mesh)
        self.reshard_count = 0         # construction placement isn't a move

    def _config_key(self, slots: int, buckets=None) -> Tuple:
        """Shared-executable-cache config fingerprint at a (possibly
        prospective) design point — batch size and bucket ladder shape the
        compiled programs, so both are in the key."""
        ladder = (length_buckets(buckets, self.cfg.max_len)
                  if buckets is not None else self._buckets)
        return (self.workload_class, self.model.cfg, slots,
                self.cfg.max_len, ladder, _rules_fp(self.rules))

    # ------------------------------------------------------------------
    def reshard_to(self, sub) -> None:
        """Move the engine onto a new composed sub-accelerator.  Encoder
        jobs complete within the step that runs them, so the only device
        state is the params pytree — one sharded→sharded device_put (onto
        the grant restricted to the engine's TP degree)."""
        with self._obs.span("reshard"):
            self._granted = _mesh_of(sub)
            mesh = part.tp_submesh(self._granted, self._tp)
            self.mesh = mesh
            self._mesh_fp = mesh_fingerprint(mesh)
            if mesh is not None:
                self.params = jax.device_put(
                    self.params,
                    self._param_plan.shardings(mesh, self._rules_eff))
        self.reshard_count += 1
        self._obs.inc("reshards")

    def sync(self) -> None:
        """No in-flight device state: step() already syncs on device_get."""
        jax.block_until_ready(self.params)

    # ------------------------------------------------------------------
    # live design-point reconfiguration (serving DSE Stage 1's knobs)
    # ------------------------------------------------------------------
    def design(self) -> Dict[str, Any]:
        """Currently applied design point: TP degree (None = whole grant),
        batch slots per step, and the sequence-length bucket ladder."""
        return {"tp": self._tp, "slots": self.cfg.max_slots,
                "buckets": self._buckets}

    def apply(self, sub=None,
              point: Optional[DesignPoint] = None) -> Dict[str, Any]:
        """Apply a design-point delta live (``point`` fields of ``None`` =
        keep).  Encoder jobs hold no cross-step device state, so every knob
        is a host-side swap (plus a params reshard for ``sub``/``tp``):
        ``slots`` resizes the batched program's job count per step,
        ``buckets`` swaps the padded-length program ladder (numerics-safe —
        encodes mask their key padding, so embeddings are bucket-invariant);
        ``dp`` is a group knob, consumed by the ReplicaGroup.  Returns the
        applied knobs."""
        point = point if point is not None else DesignPoint(cus=0)
        applied: Dict[str, Any] = {}
        if point.tp is not None and point.tp != (self._tp or 0):
            self._tp = max(int(point.tp), 1)
            applied["tp"] = self._tp
        if sub is not None or "tp" in applied:
            self.reshard_to(sub if sub is not None else self._granted)
        if point.slots is not None and int(point.slots) != self.cfg.max_slots:
            self.cfg = dataclasses.replace(self.cfg,
                                           max_slots=max(int(point.slots), 1))
            applied["slots"] = self.cfg.max_slots
        if point.buckets is not None:
            ladder = length_buckets(point.buckets, self.cfg.max_len)
            if ladder != self._buckets:
                self._buckets = ladder
                self._bucket_hits = {b: self._bucket_hits.get(b, 0)
                                     for b in ladder}
                applied["buckets"] = ladder
        if applied:
            self._cfg_key = self._config_key(self.cfg.max_slots)
        return applied

    # ------------------------------------------------------------------
    # cross-replica migration (ReplicaGroup dp retune): encoder jobs hold
    # no cross-step device state, so only the host queue moves
    # ------------------------------------------------------------------
    def evacuate(self) -> Tuple[List, List[EncodeJob]]:
        """Strip this engine of its queued jobs for adoption by sibling
        replicas; the live list is always empty (jobs complete within the
        step that runs them).  Finished records stay readable."""
        queued, self._queue = self._queue, []
        return [], queued

    def adopt_queued(self, job: EncodeJob) -> int:
        """Adopt a queued job from a sibling replica under a fresh engine
        rid (the ReplicaGroup owns the stable group-level rid)."""
        rid = self._next_rid
        self._next_rid += 1
        job.rid = rid
        self._queue.append(job)
        return rid

    def export_queued(self) -> List[EncodeJob]:
        """Hand back the queued jobs (ReplicaGroup queue rebalance on a dp
        grow)."""
        queued, self._queue = self._queue, []
        return queued

    def recent_lengths(self) -> Tuple[int, ...]:
        """Recently submitted job lengths, exponentially decayed toward the
        newest traffic — what the serving DSE's Stage-1 bucket-ladder search
        optimizes against."""
        return self._recent_lens.lengths()

    # ------------------------------------------------------------------
    # compiled executable: one fixed-shape batched encode per mesh
    # (build counting: EngineTelemetry)
    # ------------------------------------------------------------------
    def _encode_fn(self, params, tokens, lens):
        """(B, S) padded tokens + (B,) valid lengths -> (B, d) fp32 masked
        mean-pooled embeddings.  ``lens`` both masks the mean-pool AND (on
        bidirectional stacks) the attention's key padding, so a job's
        embedding is independent of the bucket it ran in."""
        x = self.model.encode(params, {"tokens": tokens}, lens=lens)
        S = x.shape[1]
        mask = (jnp.arange(S)[None, :] < lens[:, None]).astype(jnp.float32)
        pooled = jnp.einsum("bsd,bs->bd", x.astype(jnp.float32), mask)
        return pooled / jnp.maximum(lens, 1).astype(jnp.float32)[:, None]

    def _build_encode(self, mesh, sb: int, slots: Optional[int] = None):
        B, S = slots or self.cfg.max_slots, sb
        kwargs = {}
        if mesh is not None:
            kwargs["out_shardings"] = NamedSharding(mesh, P())
        fn = jax.jit(self._encode_fn, **kwargs)

        def aval(dtype, shape):
            if mesh is None:
                return jax.ShapeDtypeStruct(shape, dtype)
            return jax.ShapeDtypeStruct(shape, dtype,
                                        sharding=NamedSharding(mesh, P()))

        return fn.lower(
            self._param_plan.avals(mesh, self._rules_eff),
            aval(jnp.int32, (B, S)),
            aval(jnp.int32, (B,)),
        ).compile()

    def _encode_exec(self, mesh, sb: int):
        key = ("encode", self._cfg_key, self._mesh_fp, sb)
        return self._exec.get_or_build(
            key, self._counted(lambda: self._build_encode(mesh, sb)))

    def warm_compile(self, sub,
                     point: Optional[DesignPoint] = None) -> int:
        """Pre-compile the batched encode program of every sequence-length
        bucket for a candidate sub-accelerator — at a candidate design
        point when one is given.  The ladder is finite, so this fully
        covers the composition.  Returns cold builds performed."""
        point = point if point is not None else DesignPoint(cus=0)
        with self._obs.timed("warm_compile", "warm_compile_s") as sp:
            mesh = part.tp_submesh(
                _mesh_of(sub), point.tp if point.tp is not None else self._tp)
            B = point.slots or self.cfg.max_slots
            key = self._config_key(B, point.buckets)
            ladder = (length_buckets(point.buckets, self.cfg.max_len)
                      if point.buckets is not None else self._buckets)
            fp = mesh_fingerprint(mesh)
            built = sum(self._exec.ensure(
                ("encode", key, fp, sb),
                self._counted(lambda sb=sb: self._build_encode(mesh, sb, B)))
                for sb in ladder)
            if sp is not None:
                sp["builds"] = built
        return built

    # ------------------------------------------------------------------
    # load signals
    # ------------------------------------------------------------------
    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def active_count(self) -> int:
        return 0                       # jobs complete within their step

    @property
    def has_work(self) -> bool:
        return bool(self._queue)

    def pending_tokens(self) -> int:
        """Prefill tokens of work owed: encoder demand is full-sequence
        compute, so the signal is prompt tokens, not decode steps."""
        return sum(len(j.tokens) for j in self._queue)

    def arena_utilization(self) -> float:
        """Batch-fill pressure: how far the queue over-subscribes one step's
        batch (the encoder has no growing per-request device state)."""
        return min(1.0, len(self._queue) / max(self.cfg.max_slots, 1))

    def stats(self) -> Dict[str, Any]:
        """Load/telemetry snapshot: queue depth (jobs), owed prompt tokens,
        batch-fill pressure (0..1), migrations, cold builds, completed
        sequences, and jobs served per sequence-length bucket."""
        return {
            "workload_class": self.workload_class,
            "queue_depth": self.queue_depth,
            "active": self.active_count,
            "pending_tokens": self.pending_tokens(),
            "arena_utilization": round(self.arena_utilization(), 4),
            "reshard_count": self.reshard_count,
            "compile_builds": self.compile_builds,
            "seqs_done": self._seqs_done,
            "bucket_hits": {str(b): n for b, n in self._bucket_hits.items()},
            "design": self.design(),
        }

    # ------------------------------------------------------------------
    def submit(self, tokens, max_new_tokens: int = 0) -> int:
        """Queue one embedding job.  ``max_new_tokens`` is accepted for
        Engine-protocol compatibility and ignored (nothing is generated)."""
        del max_new_tokens
        rid = self._next_rid
        self._next_rid += 1
        toks = np.asarray(tokens, np.int32)
        self._recent_lens.append(len(toks))
        self._queue.append(EncodeJob(rid, toks,
                                     submitted_s=time.perf_counter()))
        self._obs.inc("requests_submitted")
        return rid

    def step(self) -> List[Tuple[int, List[float]]]:
        """One engine iteration: batch up to max_slots queued jobs through
        one compiled encode and complete them.  Returns [(rid, embedding)]."""
        emitted: List[Tuple[int, List[float]]] = []
        batch: List[EncodeJob] = []
        while self._queue and len(batch) < self.cfg.max_slots:
            job = self._queue.pop(0)
            if len(job.tokens) > self.cfg.max_len:
                # rejected-but-recorded (empty embedding), like the decode
                # engine's oversized requests — and like them NOT emitted:
                # emitted entries are completed sequences and feed the
                # fabric's per-class throughput accounting
                job.done = True
                job.embedding = []
                self._record_finished(job)
                continue
            batch.append(job)
        if not batch:
            return emitted
        obs = self._obs
        if obs.enabled:
            now = time.perf_counter()
            for job in batch:
                if job.submitted_s > 0.0:
                    obs.observe("queue_wait_s", now - job.submitted_s)
        # group by each job's OWN smallest fitting bucket (NOT the batch
        # max) so a short job never pays a co-batched long job's padded
        # FLOPs; numerically the bucket doesn't matter — encode masks each
        # row's key padding, so embeddings are bucket-invariant
        groups: Dict[int, List[EncodeJob]] = {}
        for job in batch:
            groups.setdefault(pick_bucket(self._buckets, len(job.tokens)),
                              []).append(job)
        B = self.cfg.max_slots
        # the encoder's "decode step" is its batched encode iteration — the
        # uniform decode_step_s metric keeps per-class step latency
        # comparable across the fleet; each group's device_get is an
        # existing sync point, so the timings add no synchronization
        with obs.timed("encode_step", "decode_step_s", jobs=len(batch)), \
                sanitize_guard():
            for sb in sorted(groups):
                jobs = groups[sb]
                self._bucket_hits[sb] += len(jobs)
                toks = np.zeros((B, sb), np.int32)
                lens = np.zeros((B,), np.int32)
                for i, job in enumerate(jobs):
                    toks[i, :len(job.tokens)] = job.tokens
                    lens[i] = len(job.tokens)
                with obs.timed("encode", "encode_s", bucket=sb, n=len(jobs)):
                    exe = self._encode_exec(self.mesh, sb)
                    emb = np.asarray(
                        jax.device_get(exe(self.params, toks, lens)))
                for i, job in enumerate(jobs):
                    job.embedding = [float(v) for v in emb[i]]
                    job.done = True
                    self._record_finished(job)
                    emitted.append((job.rid, job.embedding))
        sanitize_check(self)
        if obs.enabled:
            done = time.perf_counter()
            for job in batch:
                if job.submitted_s > 0.0:
                    obs.observe("ttft_s", done - job.submitted_s)
            obs.set_gauge("slot_utilization", len(batch) / max(B, 1))
            obs.inc("tokens_emitted", len(batch))
        self._seqs_done += len(batch)
        return emitted

    def _record_finished(self, job: EncodeJob) -> None:
        # copy: the job's list is handed to callers via step()'s emitted
        # pairs — a caller mutating it must not corrupt the engine's record
        self._finished[job.rid] = list(job.embedding)
        self._evict_finished()

    def run_to_completion(self, max_steps: int = 1000) -> Dict[int, List[float]]:
        """Step until idle (or ``max_steps``); returns ``snapshot()``."""
        for _ in range(max_steps):
            if not self.has_work:
                break
            self.step()
        return self.snapshot()

    def results(self) -> Dict[int, List[float]]:
        """Completed (or rejected) jobs' embeddings (copies, like the
        decode engine's token streams)."""
        return {rid: list(e) for rid, e in self._finished.items()}

    def snapshot(self) -> Dict[int, List[float]]:
        out: Dict[int, List[float]] = {j.rid: [] for j in self._queue}
        out.update(self.results())
        return out
