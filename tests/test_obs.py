"""Fabric telemetry (repro.obs): histogram determinism and merge algebra,
span tracing + Perfetto export, predicted-vs-measured accounting, and the
acceptance invariant — token streams bit-identical with telemetry on or
off across a live recomposition (device scenario in an 8-host-device
subprocess; device count is fixed at first jax init)."""
import importlib.util
import json
import math
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.obs import (Histogram, MetricsRegistry, PredictionLedger,
                       SpanTracer, Telemetry, bucket_bounds, metric_key)
from repro.obs.metrics import HIST_NBUCKETS

# ---------------------------------------------------------------------------
# histograms: exact stats, bucket resolution, deterministic quantiles, merge
# ---------------------------------------------------------------------------


def test_histogram_exact_stats():
    h = Histogram()
    vals = [0.004, 0.001, 0.0017, 0.25, 0.001]
    for v in vals:
        h.observe(v)
    assert h.count == len(vals)
    assert h.sum == pytest.approx(sum(vals))
    assert h.min == min(vals) and h.max == max(vals)
    assert h.mean == pytest.approx(sum(vals) / len(vals))


def test_histogram_bucket_resolution_separates_benchmark_gate():
    """~9% relative bucket width must separate the ragged-kernels p50 gap
    (1.71 ms vs 1.98 ms in BENCH_serve_fabric) — the quantiles the SLO
    block reports have to resolve the differences the benchmarks gate on."""
    a, b = Histogram(), Histogram()
    for _ in range(32):
        a.observe(1.71e-3)
        b.observe(1.98e-3)
    assert a.quantile(0.5) < b.quantile(0.5)


def test_histogram_quantiles_deterministic_and_clamped():
    h1, h2 = Histogram(), Histogram()
    vals = [1e-4 * (i % 37 + 1) for i in range(500)]
    for v in vals:
        h1.observe(v)
    for v in reversed(vals):                  # insertion order must not matter
        h2.observe(v)
    for q in (0.0, 0.01, 0.5, 0.95, 0.99, 1.0):
        assert h1.quantile(q) == h2.quantile(q)
        assert h1.min <= h1.quantile(q) <= h1.max
    assert h1.quantile(1.0) == h1.max
    # clamping: a single value's every quantile IS that value
    single = Histogram()
    single.observe(0.0042)
    assert single.quantile(0.5) == 0.0042 == single.quantile(0.99)


def test_histogram_merge_equals_single_stream():
    a, b, ref = Histogram(), Histogram(), Histogram()
    for i in range(200):
        v = 1e-5 * (i + 1)
        (a if i % 2 else b).observe(v)
        ref.observe(v)
    a.merge(b)
    assert a.count == ref.count and a.sum == pytest.approx(ref.sum)
    assert a.min == ref.min and a.max == ref.max
    assert list(a.counts) == list(ref.counts)
    for q in (0.1, 0.5, 0.9, 0.99):
        assert a.quantile(q) == ref.quantile(q)


def test_histogram_out_of_range_values_clamp_to_edge_buckets():
    h = Histogram()
    h.observe(1e-12)                          # below base -> bucket 0
    h.observe(1e12)                           # beyond top -> last bucket
    assert h.count == 2
    assert h.counts[0] == 1 and h.counts[HIST_NBUCKETS - 1] == 1
    lo, hi = bucket_bounds(0)
    assert lo < hi


# ---------------------------------------------------------------------------
# registry: label keys, merge semantics, filtered merges, snapshot
# ---------------------------------------------------------------------------


def test_metric_key_renders_sorted_labelsets():
    """Label sorting happens once at handle creation (the registry's
    ``_labelset``), so kwargs order never forks a metric's identity."""
    r = MetricsRegistry()
    assert (r.counter("x", b="2", a="1")
            is r.counter("x", a="1", b="2"))
    r.counter("x", b="2", a="1").inc()
    assert r.snapshot()["counters"] == {"x{a=1,b=2}": 1}
    assert metric_key("x", ()) == "x"


def test_registry_merge_semantics():
    """Counters sum, gauges keep the max (the hottest replica), histograms
    bucket-add — the ReplicaGroup merge contract."""
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    r1.counter("toks", tenant="a").inc(3)
    r2.counter("toks", tenant="a").inc(5)
    r1.gauge("util", tenant="a").set(0.25)
    r2.gauge("util", tenant="a").set(0.75)
    r1.histogram("lat", tenant="a").observe(0.001)
    r2.histogram("lat", tenant="a").observe(0.004)
    merged = MetricsRegistry.merged([r1, r2])
    assert merged.counter("toks", tenant="a").value == 8
    assert merged.gauge("util", tenant="a").value == 0.75
    assert merged.histogram("lat", tenant="a").count == 2
    # merging must not mutate the sources
    assert r1.counter("toks", tenant="a").value == 3


def test_merged_histogram_filters_by_label_subset():
    r = MetricsRegistry()
    r.histogram("lat", tenant="a", wclass="decode").observe(0.001)
    r.histogram("lat", tenant="a", wclass="decode").observe(0.002)
    r.histogram("lat", tenant="b", wclass="ssm").observe(0.009)
    assert r.merged_histogram("lat", tenant="a").count == 2
    assert r.merged_histogram("lat").count == 3
    assert r.merged_histogram("lat", tenant="c").count == 0


def test_registry_snapshot_shape():
    r = MetricsRegistry()
    r.counter("n", t="x").inc()
    r.histogram("lat").observe(0.5)
    snap = r.snapshot()
    assert snap["counters"] == {"n{t=x}": 1}
    assert snap["histograms"]["lat"]["count"] == 1
    json.dumps(snap)                          # JSON-serializable end to end


# ---------------------------------------------------------------------------
# span tracer: nesting, ring eviction, Perfetto export schema
# ---------------------------------------------------------------------------


def test_span_nesting_and_args():
    tr = SpanTracer()
    with tr.span("outer", kind="parent"):
        with tr.span("inner") as payload:
            payload["extra"] = 7
    ev = tr.events()
    by_name = {e["name"]: e for e in ev}
    assert set(by_name) == {"outer", "inner"}
    outer, inner = by_name["outer"], by_name["inner"]
    # the child nests inside the parent on the timeline
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert outer["args"] == {"kind": "parent"}
    assert inner["args"] == {"extra": 7}


def test_span_ring_eviction():
    tr = SpanTracer(capacity=4)
    for i in range(7):
        tr.record(f"s{i}", 0.0, 0.001)
    assert len(tr) == 4
    assert {e["name"] for e in tr.events()} == {"s3", "s4", "s5", "s6"}


def _load_export_trace():
    path = (pathlib.Path(__file__).resolve().parent.parent
            / "tools" / "export_trace.py")
    spec = importlib.util.spec_from_file_location("export_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_export_schema_roundtrip(tmp_path):
    """dump() output must survive a JSON round trip AND satisfy the
    trace-event schema tools/export_trace.py validates (the CI gate)."""
    tr = SpanTracer()
    with tr.span("recompose", reason="test"):
        with tr.span("migrate", tenant="a"):
            pass
    path = tmp_path / "trace.json"
    tr.dump(str(path))
    trace = json.loads(path.read_text())
    assert trace["displayTimeUnit"] == "ms"
    mod = _load_export_trace()
    assert mod.validate(trace) == []
    summary = mod.summarize(trace["traceEvents"])
    assert summary["recompose"]["count"] == 1
    assert mod.main([str(path), "--require-span", "recompose"]) == 0
    assert mod.main([str(path), "--require-span", "decode_step"]) == 1


# ---------------------------------------------------------------------------
# telemetry handle: no-op discipline when disabled, scoping
# ---------------------------------------------------------------------------


def test_disabled_telemetry_records_nothing():
    obs = Telemetry.off()
    obs.observe("lat", 0.5)
    obs.inc("n")
    obs.set_gauge("g", 1.0)
    with obs.span("s") as payload:
        assert payload is None                # callers guard before writing
    with obs.timed("t", "lat2") as payload:
        assert payload is None
    snap = obs.registry.snapshot()
    assert snap == {"counters": {}, "gauges": {}, "histograms": {}}
    assert len(obs.tracer) == 0


def test_scoped_shares_registry_fresh_does_not():
    root = Telemetry()
    scoped = root.scoped(tenant="a")
    scoped.observe("lat", 0.1)
    assert root.registry.histogram("lat", tenant="a").count == 1
    fresh = scoped.fresh()
    fresh.observe("lat", 0.2)                 # lands in the replica registry
    assert root.registry.histogram("lat", tenant="a").count == 1
    assert fresh.registry.histogram("lat", tenant="a").count == 1
    assert fresh.tracer is root.tracer        # spans still share one ring


def test_timed_records_span_and_histogram():
    obs = Telemetry().scoped(tenant="a")
    with obs.timed("work", "work_s", size=3) as payload:
        payload["done"] = True
    assert obs.registry.histogram("work_s", tenant="a").count == 1
    (ev,) = obs.tracer.events()
    assert ev["name"] == "work" and ev["args"] == {"size": 3, "done": True}


# ---------------------------------------------------------------------------
# spans on the profiler's clock: each span is also a TraceAnnotation
# ---------------------------------------------------------------------------


def _profiled_spans(body, tmp_path):
    """Run ``body`` under a CPU ``jax.profiler`` trace and read back the
    host events named ``serve.*`` / ``fabric.*`` as (start, end, name)."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    prof = ProfileData.from_file(str(path))
    return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for plane in prof.planes for line in plane.lines
                  for e in line.events
                  if e.name.startswith(("serve.", "fabric.")))


def test_spans_are_profiler_annotations_nested_as_taken(tmp_path):
    obs = Telemetry().scoped(tenant="a")

    def body():
        with obs.timed("decode_step", "decode_step_s"):
            with obs.span("dispatch", n=2):
                pass
            with obs.timed("harvest"):
                pass
        with obs.tracer.span("move", cat="fabric"):
            pass

    ev = _profiled_spans(body, tmp_path)
    # the annotation is named by category and span alone: args stay in
    # the ring buffer
    assert [n for _, _, n in ev] == ["serve.decode_step", "serve.dispatch",
                                     "serve.harvest", "fabric.move"]
    (s0, s1, _), dispatch, harvest, (m0, _, _) = ev
    for a, b, _ in (dispatch, harvest):
        assert s0 <= a <= b <= s1
    assert dispatch[1] <= harvest[0] and s1 <= m0
    assert [(e["name"], e.get("args")) for e in obs.tracer.events()] == [
        ("decode_step", None), ("dispatch", {"n": 2}), ("harvest", None),
        ("move", None)]


def test_disabled_telemetry_writes_no_profiler_annotation(tmp_path):
    obs = Telemetry.off()

    def body():
        with obs.timed("decode_step", "decode_step_s"):
            with obs.span("harvest"):
                pass

    assert _profiled_spans(body, tmp_path) == []


# ---------------------------------------------------------------------------
# predicted-vs-measured ledger
# ---------------------------------------------------------------------------


def test_ledger_ratio_and_aggregate():
    led = PredictionLedger()
    led.commit("a", "decode", "c4-tp2-dp1-s4", predicted_unit_s=0.002)
    for _ in range(5):
        led.observe("a", "c4-tp2-dp1-s4", 0.001, wclass="decode")
    s = led.summary()
    entry = s["entries"]["a|c4-tp2-dp1-s4"]
    assert entry["ratio"] == pytest.approx(2.0)      # over-prediction
    assert entry["measured_n"] == 5 and entry["commits"] == 1
    agg = s["aggregate"]
    assert agg["entries_with_both"] == 1
    assert agg["mean_abs_log2_error"] == pytest.approx(1.0)


def test_ledger_rejects_non_positive_predictions():
    led = PredictionLedger()
    led.commit("a", "decode", "k", predicted_unit_s=0.0)
    led.commit("a", "decode", "k", predicted_unit_s=float("inf"))
    led.observe("a", "k", 0.001, wclass="decode")
    entry = led.summary()["entries"]["a|k"]
    assert entry["predicted_unit_s"] is None and entry["ratio"] is None
    assert led.summary()["aggregate"]["entries_with_both"] == 0


# ---------------------------------------------------------------------------
# fabric integration: bounded events with fold totals (single CPU device)
# ---------------------------------------------------------------------------


def test_bounded_events_totals_survive_eviction():
    """The events deque evicts, the stats() totals don't (the ISSUE-8
    bugfix: a long-running fabric must not grow per recomposition, and
    `recompositions`/`retunes`/`recompose_seconds` must stay correct)."""
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.serve import ComposedServer, ServeConfig, TenantSpec

    mesh = make_host_mesh((1, jax.device_count()), ("data", "model"))
    srv = ComposedServer(
        mesh, [TenantSpec("a", "minitron-4b", reduced=True,
                          serve=ServeConfig(max_slots=2, max_len=32,
                                            eos_id=-1))],
        policy=None, events_cap=2)
    for i in range(5):
        srv.recompose({"a": srv.composer.num_cus}, reason=f"r{i}")
    assert len(srv.events) == 2               # deque evicted the first three
    assert [e.reason for e in srv.events] == ["r3", "r4"]
    st = srv.stats()
    assert st["recompositions"] == 5
    assert st["recompose_seconds"] >= 0
    assert len(st["recompose_seconds_recent"]) == 2


# ---------------------------------------------------------------------------
# device scenario: streams bit-identical with telemetry on/off across a
# live recomposition (8 fake host devices, subprocess)
# ---------------------------------------------------------------------------

_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import json
import jax
import numpy as np
from repro.launch.mesh import make_host_mesh
"""


def _run(body: str, timeout=900):
    out = subprocess.run([sys.executable, "-c",
                          _PRELUDE + textwrap.dedent(body)],
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_streams_bit_identical_with_telemetry_on_off():
    """Acceptance invariant: instrumentation must observe, never steer.
    The same traffic through the same recompose schedule emits identical
    token streams with the registry/tracer live and with telemetry=False —
    and the on-arm actually recorded (non-empty step histograms, spans),
    while the off-arm recorded nothing."""
    res = _run("""
    from repro.serve.fabric import ComposedServer, TenantSpec
    from repro.serve import ServeConfig

    sc = ServeConfig(max_slots=2, max_len=64, eos_id=-1)

    def run(telemetry):
        mesh = make_host_mesh((1, 8), ("data", "model"))
        srv = ComposedServer(mesh, [
            TenantSpec("a", "minitron-4b", serve=sc),
            TenantSpec("b", "falcon-mamba-7b", seed=1, serve=sc,
                       workload="ssm"),
        ], policy=None, telemetry=telemetry)
        rng = np.random.default_rng(0)
        for t in ("a", "b"):
            vocab = srv.cfgs[t].vocab_size
            for _ in range(3):
                srv.submit(t, rng.integers(1, vocab, size=8),
                           max_new_tokens=8)
        for _ in range(6):
            srv.step()
        srv.recompose({"a": 6, "b": 2}, reason="mid-stream")
        srv.drain(max_steps=300)
        streams = {t: {str(r): toks for r, toks in out.items()}
                   for t, out in srv.results().items()}
        return streams, srv

    on_streams, on_srv = run(True)
    off_streams, off_srv = run(False)
    on_snap = on_srv.metrics_snapshot()
    off_snap = off_srv.metrics_snapshot()
    on_hist = {k: h for k, h in on_snap["histograms"].items()
               if k.startswith("decode_step_s") and h["count"] > 0}
    print(json.dumps({
        "match": on_streams == off_streams,
        "n_requests": sum(len(s) for s in on_streams.values()),
        "on_decode_step_hists": sorted(on_hist),
        "on_spans": len(on_srv.obs.tracer),
        # the off arm records nothing: no histograms, no spans (the
        # exec-cache gauges and recompose fold counters survive — they
        # are the fabric's own bookkeeping, not registry recordings)
        "off_hists": sorted(off_snap["histograms"]),
        "off_registry_empty": off_srv.obs.registry.snapshot() ==
            {"counters": {}, "gauges": {}, "histograms": {}},
        "off_spans": len(off_srv.obs.tracer),
        "on_pvm_entries": len(on_srv.stats()
                              ["predicted_vs_measured"]["entries"]),
    }))
    """)
    assert res["match"], "telemetry changed the token streams"
    assert res["n_requests"] == 6
    assert res["on_decode_step_hists"], "on-arm recorded no step histograms"
    assert res["on_spans"] > 0
    assert res["off_hists"] == [] and res["off_spans"] == 0
    assert res["off_registry_empty"]
    assert res["on_pvm_entries"] > 0
