"""Serving launcher.

Single-tenant continuous batching:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-32b --reduced \
      --requests 8

Multi-tenant fabric with real-time recomposition (traffic-driven: bursty
tenants steal CUs from idle ones; a lone busy tenant unifies the fabric).
Tenant engines run tensor-parallel on their sub-meshes and recompositions
pre-compile the target composition (--no-tp / --no-warm to disable).  Needs
one CU (model-axis column) per tenant — on a CPU host fake enough devices
first:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --fabric \
      --arch minitron-4b --arch qwen2.5-32b --reduced --requests 12

Heterogeneous fleet (one tenant per workload class — transformer decode +
mamba SSM + encoder embedding + seamless enc-dec — with class-aware CU
costing):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --fabric --scenario mixed \
      --reduced --requests 6

Tokens/s-vs-CU-count scaling curve (the measured counterpart of the
policy's analytical speedup — run under fake devices as above):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --scaling-curve

TP-decode smoke (2-way TP streams must equal replicated 1-way; CI guard):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --tp-smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import sys
import time

import jax
import numpy as np

from repro.common.jax_cache import setup_compile_cache
from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.configs.base import ModelConfig
from repro.core.composer import MeshComposer
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import build_model
from repro.serve import (AnalyticalPolicy, ComposedServer, ReplicaGroup,
                         SLOTarget, ServeConfig, ServeEngine,
                         TenantDesignSpace, TenantSpec, arrival_schedule,
                         serve_engine_rules)
from repro.workloads import DECODE

# --scenario profiles served by the open-loop traffic generator
# (repro.serve.traffic) on the mixed four-class fleet, with SLO targets
# attached so the fabric's SLO-aware scheduler is live
TRAFFIC_SCENARIOS = ("diurnal", "flash-crowd", "heavy-tail")


# the heterogeneous fleet --scenario mixed serves: one tenant per workload
# class, so the class-aware policy splits the fabric across all four bound
# resources (decode bandwidth / SSM state bandwidth / encoder compute /
# enc-dec decode + cross-attention source reads)
MIXED_FLEET = (("decode", "minitron-4b"),
               ("ssm", "falcon-mamba-7b"),
               ("encoder", "qwen2.5-32b"),
               ("encdec", "seamless-m4t-medium"))


def _telemetry_line(server, steps: int, toks: int, dt: float) -> str:
    """The per-interval serving summary (one line, stderr): throughput,
    decode-step percentiles, fleet queue depth, last recompose reason."""
    h = server.obs.registry.merged_histogram("decode_step_s")
    p50 = h.quantile(0.5) * 1e3 if h.count else 0.0
    p99 = h.quantile(0.99) * 1e3 if h.count else 0.0
    qd = sum(eng.queue_depth for eng in server.engines.values())
    reason = server.events[-1].reason if server.events else "-"
    return (f"[serve {dt:7.1f}s step {steps:5d}] "
            f"tok/s={toks / max(dt, 1e-9):7.1f} "
            f"step_ms p50={p50:.2f} p99={p99:.2f} "
            f"queue={qd} last_recompose={reason}")


def _streams_digest(results) -> str:
    """Order-independent sha256 over every tenant's (rid -> token stream)
    map.  Equal digests mean bit-identical serving output — the acceptance
    check that paging / preemption / SLO scheduling never change a single
    emitted token (greedy decode rows are batch-independent).  Float
    outputs (encoder embeddings) are excluded: their bits legitimately
    track the applied TP degree — reduction order — and are pinned
    close-not-equal across degrees in tests/test_workloads.py, so two runs
    whose policies diverge may differ there without any scheduling bug."""
    h = hashlib.sha256()
    for t in sorted(results):
        for rid in sorted(results[t]):
            arr = np.asarray(results[t][rid])
            if not np.issubdtype(arr.dtype, np.integer):
                continue
            h.update(f"{t}/{rid}:".encode())
            h.update(arr.tobytes())
            h.update(b";")
    return h.hexdigest()


def run_fabric(args) -> int:
    """Traffic-driven multi-tenant serving on one recomposable fabric."""
    mesh = (make_production_mesh(multi_pod=args.multi_pod)
            if args.production_mesh else
            make_host_mesh((1, jax.device_count())))
    serve = ServeConfig(max_slots=args.max_slots, max_len=args.max_len,
                        eos_id=-1, kv_arena_frac=args.kv_frac,
                        kv_page_rows=args.kv_page_rows)
    use_traffic = args.scenario in TRAFFIC_SCENARIOS
    if args.scenario == "mixed" or use_traffic:
        # traffic scenarios carry SLO targets so the SLO-aware scheduler
        # (and the attainment report) are live; plain "mixed" stays
        # best-effort — its benchmark baselines predate SLO scheduling
        slo = (SLOTarget(ttft_p50_ms=args.slo_ttft_p50_ms,
                         ttft_p99_ms=args.slo_ttft_p99_ms,
                         per_token_p99_ms=args.slo_per_token_p99_ms)
               if use_traffic else None)
        # --slo-tenant scopes the targets (and therefore the scheduler's
        # preemption lever and the attainment report) to one tenant; the
        # rest of the fleet serves best-effort
        tenants = [TenantSpec(f"{w}-{arch}", arch, reduced=args.reduced,
                              serve=serve, seed=i, workload=w,
                              slo=(slo if args.slo_tenant in f"{w}-{arch}"
                                   else None))
                   for i, (w, arch) in enumerate(MIXED_FLEET)]
    else:
        tenants = [TenantSpec(f"tenant{i}-{arch}", arch, reduced=args.reduced,
                              serve=serve, seed=i)
                   for i, arch in enumerate(args.arch)]
    policy = AnalyticalPolicy(two_stage=not args.split_only)
    server = ComposedServer(mesh, tenants, policy=policy,
                            decide_every=args.decide_every,
                            tp=not args.no_tp, warm=not args.no_warm,
                            prewarm_async=args.prewarm_async,
                            telemetry=not args.no_telemetry,
                            slo_preempt=not args.no_preempt)
    rng = np.random.default_rng(args.seed)
    t0 = time.monotonic()
    if use_traffic:
        # seeded open-loop arrival process (repro.serve.traffic): the same
        # seed replays the identical schedule, so paired benchmark arms
        # (paged vs slot-granular) see the same offered load
        queue = [(a.step, a.tenant, a.prompt_len, a.max_new)
                 for a in arrival_schedule(
                     args.scenario, [t.name for t in tenants],
                     args.requests, args.seed,
                     max_new=args.max_new_tokens)]
    else:
        # bursty open-loop traffic: each tenant gets its requests in one
        # burst at a random step, so load keeps shifting under the
        # policy's feet (prompt lengths draw at submit time — the rng
        # stream here is unchanged from the pre-traffic-module launcher)
        queue = [(s, n, None, args.max_new_tokens)
                 for s, n in sorted((int(rng.integers(0, 4 * args.requests)),
                                     t.name)
                                    for t in tenants
                                    for _ in range(args.requests))]
    steps = 0
    predicted = None
    toks = 0
    # harness-level step timing: host perf_counter around server.step(),
    # measured identically with telemetry on or off — the benchmark's
    # overhead comparison reads this, not the registry's own histograms
    harness_step_ms = []
    while queue or server.pending():
        while queue and queue[0][0] <= steps:
            _, name, plen, mnew = queue.pop(0)
            vocab = server.cfgs[name].vocab_size
            if plen is None:
                plen = int(rng.integers(4, 24))
            server.submit(name, rng.integers(1, vocab, size=plen),
                          max_new_tokens=mnew)
        s0 = time.perf_counter()
        out = server.step()
        harness_step_ms.append((time.perf_counter() - s0) * 1e3)
        toks += sum(len(v) for v in out.values())
        if policy.predicted is not None:
            predicted = dict(policy.predicted)   # last busy decide's view
        steps += 1
        if args.log_every and steps % args.log_every == 0:
            # stderr: stdout carries exactly one JSON document (the
            # benchmark harness parses it from the first brace)
            print(_telemetry_line(server, steps, toks,
                                  time.monotonic() - t0), file=sys.stderr)
        if steps > 10_000:
            break
    if use_traffic:
        # the open-loop while above exits when no tokens are *owed*; drain
        # the in-flight pipelined dispatches too so completion checks and
        # the streams digest see every request's full output
        server.drain(max_steps=2000)
    dt = time.monotonic() - t0
    stats = server.stats()
    arr = np.asarray(harness_step_ms if harness_step_ms else [0.0])
    # per-class throughput: decode/ssm/encdec tenants emit tokens, encoder
    # tenants emit completed sequences (embeddings)
    throughput = {
        t: {"class": server.classes[t],
            "unit": ("seqs_per_s" if server.classes[t] == "encoder"
                     else "tokens_per_s"),
            "value": round(stats["tokens_emitted"][t] / dt, 2)}
        for t in server.engines}
    print(json.dumps({
        "tenants": [t.name for t in tenants], "scenario": args.scenario,
        "two_stage": not args.split_only,
        "decode_steps": steps,
        "wall_s": round(dt, 2), **stats,
        "telemetry": not args.no_telemetry,
        "harness_step_ms": {
            "p50": round(float(np.percentile(arr, 50)), 3),
            "p99": round(float(np.percentile(arr, 99)), 3),
            "n": len(harness_step_ms)},
        "slo": server.slo_summary(),
        "slo_attainment": server.slo_attainment(),
        "streams_digest": _streams_digest(server.results()),
        "per_class_throughput": throughput,
        # the last busy decide's predicted makespans (analytical, seconds):
        # what Stage 2 thought the best and the applied design cost
        "predicted_makespan_s": predicted,
        "events": [{"step": e.step, "reason": e.reason,
                    "sizes": e.sizes_after,
                    "retuned": list(e.retuned),
                    "design": e.design,
                    "seconds": round(e.seconds, 4),
                    "warm_compile_seconds": round(e.warm_compile_seconds, 4),
                    "warm_builds": e.warm_builds,
                    "overlapped": e.overlapped,
                    "post_step_seconds": {
                        t: round(s, 4)
                        for t, s in e.post_step_seconds.items()}}
                   for e in server.events],
    }, indent=1))
    if args.trace_out:
        server.dump_trace(args.trace_out)
        print(f"trace written: {args.trace_out}", file=sys.stderr)
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(server.metrics_snapshot(), f, indent=1)
        print(f"metrics written: {args.metrics_json}", file=sys.stderr)
    return 0


# ---------------------------------------------------------------------------
# tokens/s vs CU count: the measured scaling curve
# ---------------------------------------------------------------------------

def bench_config(d_model: int, layers: int, d_ff: int) -> ModelConfig:
    """A dense decode-bench model heavy enough that per-CU work dominates
    dispatch overhead on a CPU host (the reduced smoke configs are dominated
    by fixed per-step cost, which no amount of TP can shrink)."""
    heads = max(d_model // 128, 1)
    return ModelConfig(
        name=f"serve-bench-d{d_model}-L{layers}", family="dense",
        num_layers=layers, d_model=d_model, num_heads=heads,
        num_kv_heads=max(heads // 2, 1), d_ff=d_ff, vocab_size=2048,
        head_dim=128, attn_type="full", dtype="float32", remat=False)


def run_scaling(args) -> int:
    """Measure steady-state decode tokens/s at each sub-mesh size: the
    direct validation that CUs granted by the policy buy throughput.

    CUs buy *capacity*: the tenant's pooled KV cache shards over its
    sub-mesh, so a composition of k CUs holds k times the decode slots at
    the same per-device memory (``--scale-slots-per-cu``).  Decode at small
    batch is weights-bound, so the extra slots ride the same weight streams
    and per-step latency stays ~flat while tokens/s scales with the grant —
    the measured counterpart of the policy's analytical speedup.  The
    flatness of ``step_ms_by_cus`` is itself part of the evidence."""
    cfg = bench_config(args.scale_dmodel, args.scale_layers, args.scale_dff)
    model = build_model(cfg)
    params = model.init(jax.random.key(args.seed))
    mesh = make_host_mesh((1, jax.device_count()))
    comp = MeshComposer(mesh)
    rules = None if args.no_tp else serve_engine_rules()
    sizes = [s for s in args.scale_sizes if s <= comp.num_cus]
    M = args.scale_steps
    curve, lat, slots = {}, {}, {}
    for size in sizes:
        B = args.scale_slots_per_cu * size
        eng = ServeEngine(model, params,
                          ServeConfig(max_slots=B, max_len=args.max_len,
                                      eos_id=-1),
                          mesh=comp.submesh(range(size), f"cus{size}"),
                          rules=rules)
        rng = np.random.default_rng(args.seed)
        for _ in range(B):
            eng.submit(rng.integers(1, cfg.vocab_size, size=16),
                       max_new_tokens=3 * M + 8)
        for _ in range(3):                    # prefill + warm the executable
            eng.step()
        jax.block_until_ready(eng.cache)
        best, steps_ms = 0.0, []
        for _ in range(2):                    # best-of-2 absorbs host jitter
            t0 = time.perf_counter()
            for _ in range(M):
                s0 = time.perf_counter()
                eng.step()
                steps_ms.append((time.perf_counter() - s0) * 1e3)
            jax.block_until_ready(eng.cache)
            best = max(best, B * M / (time.perf_counter() - t0))
        curve[size], slots[size] = round(best, 2), B
        arr = np.asarray(steps_ms)
        lat[size] = {"p50": round(float(np.percentile(arr, 50)), 2),
                     "p95": round(float(np.percentile(arr, 95)), 2)}
    monotone = all(curve[a] < curve[b]
                   for a, b in zip(sizes, sizes[1:]))
    print(json.dumps({
        "bench_model": cfg.name, "measured_steps": M,
        "tp": not args.no_tp,
        "slots_by_cus": {str(s): slots[s] for s in sizes},
        "tokens_per_s_by_cus": {str(s): curve[s] for s in sizes},
        "step_ms_by_cus": {str(s): lat[s] for s in sizes},
        "monotone": monotone,
    }, indent=1))
    return 0


# ---------------------------------------------------------------------------
# DSE smoke: Stage 1 must pick a non-default design point, applied live
# ---------------------------------------------------------------------------

def run_dse_smoke(args) -> int:
    """Two-tenant fleet under the two-stage policy: the serving DSE's
    Stage 1 must pick at least one non-default design point (slot count
    above the provisioned default, or a TP degree below the grant) and the
    fabric must apply it live (a recomposition event carrying design
    deltas) while every stream completes.  Tenant "a" is a small model
    whose engine batch is structurally capped (``slot_cap``), so on a
    multi-CU grant Stage 1 must also pick ``dp > 1`` — data-parallel
    replica tiling, applied live through the ReplicaGroup's
    drain-and-rebalance.  Fast CI guard that the two-stage path actually
    optimizes rather than echoing the engine defaults."""
    if jax.device_count() < 4:
        print("dse-smoke needs >= 4 devices "
              "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        return 2
    mesh = make_host_mesh((1, jax.device_count()))
    sc = ServeConfig(max_slots=2, max_len=48, eos_id=-1)
    # a: small model, batch capped at 4 slots/engine -> a deep queue on a
    # wide grant is only servable by replica tiling (the dp axis)
    sc_a = dataclasses.replace(sc, slot_cap=4)
    tenants = [TenantSpec("a", "minitron-4b", serve=sc_a),
               TenantSpec("b", "qwen2.5-32b", seed=1, serve=sc)]
    server = ComposedServer(mesh, tenants, policy=AnalyticalPolicy(),
                            decide_every=3)
    rng = np.random.default_rng(args.seed)
    for t, n in (("a", 16), ("b", 6)):     # queue depth >> default slots
        vocab = server.cfgs[t].vocab_size
        for _ in range(n):
            server.submit(t, rng.integers(1, vocab, size=8),
                          max_new_tokens=10)
    out = server.drain(max_steps=500)
    stats = server.stats()
    applied = {t: d for e in server.events for t, d in e.design.items()}
    nondefault = {
        t: d for t, d in stats["design_points"].items()
        if d["slots"] != sc.max_slots
        or (d["tp"] is not None and 0 < d["tp"] < d["cus"])}
    # dp > 1 is a steady-load design: once the fleet drains, the policy
    # folds "a" back to one engine — so assert over the event history
    dp_picked = any(e.design.get("a", {}).get("dp", 1) > 1
                    and e.sizes_after.get("a", 0) >= 4
                    for e in server.events)
    complete = all(len(toks) == 10
                   for streams in out.values() for toks in streams.values())
    ok = bool(nondefault) and bool(applied) and dp_picked and complete
    print(json.dumps({"design_points": stats["design_points"],
                      "applied_deltas": applied,
                      "nondefault": sorted(nondefault),
                      "dp_picked": dp_picked,
                      "complete": complete, "ok": ok}))
    if not ok:
        print("DSE smoke FAILED: Stage 1 never picked (or the fabric never "
              "applied) a non-default design point with dp > 1")
        return 1
    print("DSE smoke OK: non-default design point (dp > 1) chosen and "
          "applied live")
    return 0


# ---------------------------------------------------------------------------
# obs smoke: the telemetry pipeline must observe a real mixed-fleet run
# ---------------------------------------------------------------------------

def run_obs_smoke(args) -> int:
    """Telemetry smoke on the heterogeneous fleet: serve a short
    ``--scenario mixed`` run with tracing on, export the Perfetto trace,
    and assert that

    * the trace-event JSON is valid and carries at least one ``recompose``
      span plus decode-step and warm-compile spans, and
    * every tenant class accumulated a non-empty decode-step histogram
      (the encoder class records its batched encode iteration under the
      same ``decode_step_s`` name — one CI predicate covers all four).

    Fast CI guard that instrumentation stays wired through the whole
    stack: engines, replica groups, the fabric and the exporters."""
    if jax.device_count() < 4:
        print("obs-smoke needs >= 4 devices "
              "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        return 2
    mesh = make_host_mesh((1, jax.device_count()))
    serve = ServeConfig(max_slots=2, max_len=64, eos_id=-1)
    tenants = [TenantSpec(f"{w}-{arch}", arch, reduced=True, serve=serve,
                          seed=i, workload=w)
               for i, (w, arch) in enumerate(MIXED_FLEET)]
    server = ComposedServer(mesh, tenants, policy=AnalyticalPolicy(),
                            decide_every=3)
    rng = np.random.default_rng(args.seed)
    for t in server.engines:
        vocab = server.cfgs[t].vocab_size
        for _ in range(3):
            server.submit(t, rng.integers(1, vocab, size=8),
                          max_new_tokens=6)
    server.drain(max_steps=600)
    if server.stats()["recompositions"] == 0:
        # quiet run: force one live recomposition so the trace predicate
        # exercises the recompose span path deterministically
        sizes = server.sizes()
        lo = min(sizes, key=sizes.get)
        hi = max(sizes, key=sizes.get)
        sizes[lo], sizes[hi] = sizes[lo] + 1, sizes[hi] - 1
        server.recompose(sizes, reason="obs-smoke")
        server.drain(max_steps=200)
    trace_path = args.trace_out or "/tmp/obs_smoke_trace.json"
    server.dump_trace(trace_path)
    with open(trace_path) as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    names = [e.get("name") for e in events]
    schema_ok = all(
        isinstance(e.get("ts"), (int, float))
        and isinstance(e.get("dur"), (int, float))
        and e.get("ph") == "X" and e.get("name")
        for e in events)
    merged = server.metrics()
    hist_by_class = {
        server.classes[t]:
            merged.merged_histogram("decode_step_s", tenant=t).count
        for t in server.engines}
    checks = {
        "trace_events": len(events),
        "trace_schema_ok": bool(events) and schema_ok,
        "recompose_spans": names.count("recompose"),
        "decode_step_spans": sum(n in ("decode_step", "encode_step")
                                 for n in names),
        "warm_compile_spans": names.count("warm_compile"),
        "decode_step_hist_by_class": hist_by_class,
    }
    ok = (checks["trace_schema_ok"]
          and checks["recompose_spans"] >= 1
          and checks["decode_step_spans"] >= 1
          and checks["warm_compile_spans"] >= 1
          and all(n > 0 for n in hist_by_class.values()))
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(server.metrics_snapshot(), f, indent=1)
    print(json.dumps({**checks, "trace_path": trace_path, "ok": ok}))
    if not ok:
        print("obs smoke FAILED: telemetry pipeline lost spans or "
              "histograms (see checks above)")
        return 1
    print("obs smoke OK: recompose/decode-step/warm-compile spans traced "
          "and every tenant class has decode-step latency histograms")
    return 0


# ---------------------------------------------------------------------------
# SLO smoke: flash-crowd must preempt, preempted streams must stay bit-exact
# ---------------------------------------------------------------------------

def run_slo_smoke(args) -> int:
    """Paged-KV + SLO-preemption smoke on the mixed fleet.

    A flash-crowd schedule lands on an *oversubscribed* paged arena
    (``kv_arena_frac`` well under 1), so page exhaustion during decode
    growth — plus the SLO scheduler's TTFT protection — must preempt at
    least one live stream.  The same schedule then replays on slot-granular
    (non-paged, non-preempting) engines, and every emitted unit must match
    bit-for-bit: preemption saves exact device state and greedy decode rows
    are batch-independent, so scheduling may never change output.  Asserts

    * at least one preemption fired on the paged run,
    * every request (preempted ones included) completed its full budget,
    * paged and slot-granular runs produce identical stream digests, and
    * the SLO attainment block is non-empty.
    """
    if jax.device_count() < 4:
        print("slo-smoke needs >= 4 devices "
              "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        return 2
    mesh = make_host_mesh((1, jax.device_count()))
    requests, mnew = max(args.requests, 6), 24

    def build(paged: bool) -> ComposedServer:
        serve = ServeConfig(max_slots=3, max_len=64, eos_id=-1,
                            paged_kv=paged, kv_page_rows=8,
                            kv_arena_frac=0.4 if paged else 1.0)
        slo = (SLOTarget(ttft_p50_ms=100.0, ttft_p99_ms=400.0)
               if paged else None)
        tenants = [TenantSpec(f"{w}-{arch}", arch, reduced=True, serve=serve,
                              seed=i, workload=w, slo=slo)
                   for i, (w, arch) in enumerate(MIXED_FLEET)]
        # no policy: the smoke pins scheduling behavior, not the DSE
        return ComposedServer(mesh, tenants, policy=None,
                              slo_preempt=paged)

    sched = arrival_schedule(
        "flash-crowd", [f"{w}-{arch}" for w, arch in MIXED_FLEET],
        requests, args.seed, max_new=mnew)

    def run(server: ComposedServer):
        rng = np.random.default_rng(args.seed)
        queue = [(a.step, a.tenant, a.prompt_len, a.max_new) for a in sched]
        steps = 0
        while queue or server.pending():
            while queue and queue[0][0] <= steps:
                _, name, plen, mn = queue.pop(0)
                vocab = server.cfgs[name].vocab_size
                server.submit(name, rng.integers(1, vocab, size=plen),
                              max_new_tokens=mn)
            server.step()
            steps += 1
            if steps > 4000:
                break
        server.drain(max_steps=1000)
        return server.results()

    paged_srv = build(True)
    res_paged = run(paged_srv)
    base_srv = build(False)
    res_base = run(base_srv)
    stats = paged_srv.stats()
    preemptions = sum(stats["preemptions"].values())
    att = paged_srv.slo_attainment()
    complete = all(
        len(units) == mnew
        for t, streams in res_paged.items()
        if paged_srv.classes[t] != "encoder"
        for units in streams.values())
    digest_paged = _streams_digest(res_paged)
    digest_base = _streams_digest(res_base)
    checks = {
        "preemptions": preemptions,
        "slo_preemptions": stats["slo_preemptions"],
        "complete": complete,
        "digest_match": digest_paged == digest_base,
        "attainment_tenants": sorted(att["tenants"]),
        "streams_digest": digest_paged,
    }
    ok = (preemptions >= 1 and complete and checks["digest_match"]
          and bool(att["tenants"]))
    print(json.dumps({**checks, "ok": ok}))
    if not ok:
        print("SLO smoke FAILED: flash-crowd did not preempt, or a "
              "preempted stream diverged / never completed (see checks)")
        return 1
    print("SLO smoke OK: flash-crowd preempted live streams and every "
          "request completed bit-identically to the unpreempted run")
    return 0


# ---------------------------------------------------------------------------
# dp bench: Stage-1-chosen replica tiling vs the same grant forced to dp=1
# ---------------------------------------------------------------------------

def run_dp_bench(args) -> int:
    """Steady-state decode tokens/s on one fixed grant, Stage-1-chosen
    design (which must pick ``dp > 1``) vs the same search with the tenant
    pinned to a single engine (``dp_cap=1``).

    The engine's step program is batch-capped (``slot_cap``), so the
    single-engine arm can shard its (small, weights-bound) batch over the
    whole grant but never widen it — while the replica-tiled arm decodes
    ``dp`` independent capped batches concurrently.  The measured gap is
    the serving counterpart of the paper's reconfigurable-tiling win.

    Both arms are built up front and their timed loops interleave
    (A,B,A,B,...) with best-of per arm, so slow drift in host load hits
    both the same way instead of whichever arm happens to run last."""
    if jax.device_count() < 4:
        print("dp-bench needs >= 4 devices "
              "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        return 2
    # deep-narrow at a long context: per-sublayer compute is tiny next to
    # the 2(p-1) collective phases a tp=4 step pays, while the long padded
    # KV read keeps tp=4 the best *single-engine* design — exactly the
    # regime where the grant only buys throughput as replicas.  Fixed
    # max_len (not --max-len): the regime is the benchmark.
    cfg = bench_config(512, 6, 4096)
    model = build_model(cfg)
    params = model.init(jax.random.key(args.seed))
    mesh = make_host_mesh((1, jax.device_count()))
    comp = MeshComposer(mesh)
    grant, queue, M, reps = 4, 16, args.scale_steps, 3
    sc = ServeConfig(max_slots=4, max_len=4096, eos_id=-1, slot_cap=4)
    pol = AnalyticalPolicy()

    def arm(dp_cap):
        space = TenantDesignSpace(wclass=DECODE, max_len=sc.max_len,
                                  base_slots=sc.max_slots,
                                  slot_cap=sc.slot_cap, dp_cap=dp_cap)
        best = pol.stage1.best(cfg, space, queue, grant)
        grp = ReplicaGroup(DECODE, model, params, sc,
                           sub=comp.submesh(range(grant), f"dpb{dp_cap}"),
                           rules=serve_engine_rules())
        grp.apply(None, best)
        rng = np.random.default_rng(args.seed)
        for _ in range(queue):
            grp.submit(rng.integers(1, cfg.vocab_size, size=16),
                       max_new_tokens=reps * M + 8)
        for _ in range(3):                  # prefill + warm the executables
            grp.step()
        grp.sync()
        return best, grp

    chosen, grp_dp = arm(dp_cap=64)
    forced, grp_one = arm(dp_cap=1)
    toks_dp = toks_one = 0.0
    for _ in range(reps):
        for grp, which in ((grp_dp, "dp"), (grp_one, "one")):
            n, t0 = 0, time.perf_counter()
            for _ in range(M):
                n += len(grp.step())
            grp.sync()
            tput = round(n / (time.perf_counter() - t0), 2)
            if which == "dp":
                toks_dp = max(toks_dp, tput)
            else:
                toks_one = max(toks_one, tput)
    ok = (chosen.dp or 1) > 1 and (forced.dp or 1) == 1 \
        and toks_dp > toks_one
    print(json.dumps({
        "bench_model": cfg.name, "grant_cus": grant, "queue": queue,
        "measured_steps": M, "timed_reps": reps, "slot_cap": sc.slot_cap,
        "chosen": {"dp": chosen.dp, "tp": chosen.tp, "slots": chosen.slots},
        "forced": {"dp": forced.dp, "tp": forced.tp, "slots": forced.slots},
        "tokens_per_s_dp": toks_dp, "tokens_per_s_dp1": toks_one,
        "speedup": round(toks_dp / max(toks_one, 1e-9), 3), "ok": ok,
    }))
    if not ok:
        print("dp bench FAILED: Stage 1 did not pick dp > 1, or replica "
              "tiling did not beat the single-engine arm")
        return 1
    print("dp bench OK: Stage-1-chosen replica tiling beats dp=1")
    return 0


# ---------------------------------------------------------------------------
# TP smoke: sharded decode must emit the replicated stream
# ---------------------------------------------------------------------------

def run_tp_smoke(args) -> int:
    """2-way TP vs replicated 1-way: same prompts, identical token streams,
    including across a mid-stream reshard that changes the TP degree.  Fast
    CI guard against sharded decode silently regressing to replication or
    diverging from it."""
    if jax.device_count() < 2:
        print("tp-smoke needs >= 2 devices "
              "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        return 2
    cfg = dataclasses.replace(get_reduced("minitron-4b"), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    mesh = make_host_mesh((1, jax.device_count()))
    comp = MeshComposer(mesh)
    sc = ServeConfig(max_slots=2, max_len=64, eos_id=-1)
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(rng.integers(4, 12)))
               for _ in range(3)]

    def run(tp, rules, reshard_at=None):
        eng = ServeEngine(model, params, sc,
                          mesh=comp.submesh(range(tp), f"tp{tp}"),
                          rules=rules)
        for p in prompts:
            eng.submit(p, max_new_tokens=8)
        step = 0
        while eng.has_work:
            if reshard_at and step in reshard_at:
                eng.reshard_to(comp.submesh(range(reshard_at[step]), "re"))
            eng.step()
            step += 1
            assert step < 200
        return eng.results()

    ref = run(1, None)                                 # replicated baseline
    tp2 = run(2, serve_engine_rules())
    dyn = run(2, serve_engine_rules(), reshard_at={4: 1, 8: 2})
    ok = ref == tp2 == dyn
    print(json.dumps({"match_tp2": tp2 == ref, "match_dyn": dyn == ref,
                      "requests": len(ref), "ok": ok}))
    if not ok:
        print("TP smoke FAILED: sharded decode diverged from replicated")
        return 1
    print("TP smoke OK: 2-way TP and mid-stream reshard match replicated")
    return 0


def main(argv=None) -> int:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, action="append",
                    help="repeat for multiple tenants with --fabric")
    ap.add_argument("--fabric", action="store_true",
                    help="multi-tenant ComposedServer with live recomposition")
    ap.add_argument("--scenario",
                    choices=["bursty", "mixed", "diurnal", "flash-crowd",
                             "heavy-tail"],
                    default="bursty",
                    help="fabric traffic: 'bursty' serves the --arch tenants; "
                         "'mixed' serves one tenant per workload class "
                         "(transformer decode + mamba SSM + encoder + "
                         "seamless enc-dec); 'diurnal'/'flash-crowd'/"
                         "'heavy-tail' serve the mixed fleet under the "
                         "seeded open-loop generator (repro.serve.traffic) "
                         "with SLO targets attached")
    ap.add_argument("--decide-every", type=int, default=4)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-tp", action="store_true",
                    help="replicated engines (no tensor parallelism)")
    ap.add_argument("--no-warm", action="store_true",
                    help="skip pre-compiling recomposition targets")
    ap.add_argument("--prewarm-async", action="store_true",
                    help="compile recomposition targets in a background "
                         "thread while serving continues")
    ap.add_argument("--scaling-curve", action="store_true",
                    help="measure decode tokens/s at each --scale-sizes "
                         "sub-mesh size")
    ap.add_argument("--scale-sizes", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument("--scale-steps", type=int, default=10)
    ap.add_argument("--scale-slots-per-cu", type=int, default=4,
                    help="decode slots per granted CU (capacity scales "
                         "with the composition)")
    ap.add_argument("--scale-dmodel", type=int, default=2048)
    ap.add_argument("--scale-layers", type=int, default=4)
    ap.add_argument("--scale-dff", type=int, default=8192)
    ap.add_argument("--tp-smoke", action="store_true",
                    help="assert 2-way TP decode matches replicated decode")
    ap.add_argument("--split-only", action="store_true",
                    help="disable the serving DSE's Stage 1: the policy "
                         "searches raw CU splits (the pre-two-stage "
                         "behavior; the two_stage_dse benchmark ablation)")
    ap.add_argument("--dse-smoke", action="store_true",
                    help="assert the two-stage policy picks and applies a "
                         "non-default per-tenant design point (dp > 1 for "
                         "the batch-capped small-model tenant)")
    ap.add_argument("--dp-bench", action="store_true",
                    help="measure Stage-1-chosen replica tiling (dp > 1) vs "
                         "the same grant forced to one engine (dp_cap=1)")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="disable the fabric's metrics registry and span "
                         "tracer (token streams are identical either way)")
    ap.add_argument("--metrics-json", metavar="PATH",
                    help="write the merged metrics-registry snapshot as "
                         "JSON after the run")
    ap.add_argument("--trace-out", metavar="PATH",
                    help="write the span ring buffer as Chrome/Perfetto "
                         "trace-event JSON after the run")
    ap.add_argument("--log-every", type=int, default=200, metavar="N",
                    help="print a one-line telemetry summary to stderr "
                         "every N fabric steps (0 disables)")
    ap.add_argument("--obs-smoke", action="store_true",
                    help="assert the telemetry pipeline traces a mixed-"
                         "fleet run end to end (spans + per-class "
                         "decode-step histograms)")
    ap.add_argument("--kv-frac", type=float, default=1.0,
                    help="paged-KV arena size as a fraction of the worst-"
                         "case slot reservation (< 1 oversubscribes: page "
                         "exhaustion during growth triggers preemption)")
    ap.add_argument("--kv-page-rows", type=int, default=16,
                    help="token rows per KV page (ServeConfig.kv_page_rows)")
    ap.add_argument("--no-preempt", action="store_true",
                    help="disable the fabric's SLO-preemption lever while "
                         "keeping attainment reporting (the slot-granular "
                         "benchmark baseline arm)")
    ap.add_argument("--slo-ttft-p50-ms", type=float, default=150.0,
                    help="TTFT p50 target for traffic-scenario tenants")
    ap.add_argument("--slo-ttft-p99-ms", type=float, default=400.0,
                    help="TTFT p99 target for traffic-scenario tenants")
    ap.add_argument("--slo-per-token-p99-ms", type=float, default=0.0,
                    help="per-token p99 target for traffic-scenario "
                         "tenants (0 = untracked)")
    ap.add_argument("--slo-tenant", default="", metavar="SUBSTR",
                    help="apply SLO targets only to tenants whose name "
                         "contains SUBSTR (empty = every tenant); scopes "
                         "both the scheduler and the attainment report to "
                         "the tenant under test")
    ap.add_argument("--slo-smoke", action="store_true",
                    help="assert a flash-crowd on an oversubscribed paged "
                         "arena preempts at least one stream and every "
                         "request completes bit-identically to the "
                         "slot-granular run")
    args = ap.parse_args(argv)

    if args.tp_smoke:
        return run_tp_smoke(args)
    if args.obs_smoke:
        return run_obs_smoke(args)
    if args.slo_smoke:
        return run_slo_smoke(args)
    if args.dse_smoke:
        return run_dse_smoke(args)
    if args.dp_bench:
        return run_dp_bench(args)
    if args.scaling_curve:
        return run_scaling(args)
    if args.scenario == "mixed" or args.scenario in TRAFFIC_SCENARIOS:
        if not args.fabric:
            ap.error(f"--scenario {args.scenario} requires --fabric")
        if args.arch:
            ap.error(f"--scenario {args.scenario} picks its own per-class "
                     "fleet; drop --arch")
        return run_fabric(args)
    if not args.arch:
        ap.error("--arch is required (except with "
                 "--tp-smoke/--scaling-curve/--fabric --scenario mixed)")
    if args.fabric:
        return run_fabric(args)
    if len(args.arch) != 1:
        ap.error("multiple --arch requires --fabric")
    args.arch = args.arch[0]

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(args.seed))
    mesh = rules = None
    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        rules = None if args.no_tp else serve_engine_rules()

    engine = ServeEngine(model, params,
                         ServeConfig(max_slots=args.max_slots,
                                     max_len=args.max_len, eos_id=-1),
                         mesh=mesh, rules=rules)
    rng = np.random.default_rng(args.seed)
    t0 = time.monotonic()
    rids = []
    for _ in range(args.requests):
        plen = int(rng.integers(4, 24))
        prompt = rng.integers(1, cfg.vocab_size, size=plen)
        rids.append(engine.submit(prompt, max_new_tokens=args.max_new_tokens))
    steps = 0
    emitted = 0
    step_ms = []
    while engine.has_work:
        s0 = time.perf_counter()
        emitted += len(engine.step())
        step_ms.append((time.perf_counter() - s0) * 1e3)
        steps += 1
        if steps > 10_000:
            break
    dt = time.monotonic() - t0
    arr = np.asarray(step_ms)
    print(json.dumps({
        "requests": args.requests, "decode_steps": steps,
        "tokens_emitted": emitted, "wall_s": round(dt, 2),
        "tokens_per_s": round(emitted / dt, 1),
        "step_ms": {"p50": round(float(np.percentile(arr, 50)), 2),
                    "p95": round(float(np.percentile(arr, 95)), 2)},
        "arena_utilization": engine.arena.utilization(),
    }, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
