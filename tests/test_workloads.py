"""Heterogeneous workload subsystem: SSM serving numerics (chunked-scan
prefill == step-by-step decode state; streams invariant across TP degree and
live recomposition), encoder embedding invariance, enc-dec decode through
the fabric (cross-attention source-cache correctness vs a monolithic Model
forward; streams invariant across live recomposition), class-aware policy
costing, and the mixed-fleet end-to-end acceptance (one fabric, four
workload classes, outputs bit-identical across a live move between classes).

Device-touching scenarios run in an 8-host-device subprocess (device count
is fixed at first jax init), mirroring tests/test_fabric.py."""
import dataclasses
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.models import build_model, ssm as S
from repro.distribution import strip
from repro.serve.fabric import AnalyticalPolicy, TenantObservation
from repro.workloads import (DECODE, ENCDEC, ENCODER, SSM, DecodeEngine,
                             EncDecEngine, EncoderEngine, Engine,
                             ExecutableCache, SSMEngine, ServeConfig,
                             length_buckets, pick_bucket, workload_class_of)


def _fm_cfg():
    return dataclasses.replace(get_reduced("falcon-mamba-7b"),
                               dtype="float32")


def _s2t_cfg():
    return dataclasses.replace(get_reduced("seamless-m4t-medium"),
                               dtype="float32")


@pytest.fixture(scope="module")
def mamba():
    cfg = _fm_cfg()
    model = build_model(cfg)
    params = strip(model.init(jax.random.key(0)))
    return cfg, model, params


# ---------------------------------------------------------------------------
# SSM numerics: the chunked-scan prefill must land the exact state the
# step-by-step recurrence would (admission via mamba_prefill is only sound
# if subsequent mamba_step decodes continue from an equivalent state)
# ---------------------------------------------------------------------------

def test_mamba_prefill_state_matches_stepwise():
    cfg = _fm_cfg()
    block = S.mamba_init(jax.random.key(0), cfg)
    block = strip(block)
    B, Sq = 2, 11                      # odd length: exercises scan padding
    x = np.asarray(jax.random.normal(jax.random.key(1),
                                     (B, Sq, cfg.d_model)), np.float32)
    cache0 = strip(S.mamba_cache_init(cfg, B, np.float32))

    out_p, cache_p = S.mamba_prefill(block, cfg, x, cache0, chunk=4)

    cache_s = cache0
    outs = []
    for t in range(Sq):
        y, cache_s = S.mamba_step(block, cfg, x[:, t:t + 1], cache_s)
        outs.append(y)
    out_s = np.concatenate([np.asarray(o) for o in outs], axis=1)

    np.testing.assert_allclose(np.asarray(cache_p["h"]),
                               np.asarray(cache_s["h"]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cache_p["conv"]),
                               np.asarray(cache_s["conv"]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_p), out_s,
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# constant-size state pool: admission is slot-bound, never length-bound
# ---------------------------------------------------------------------------

def test_ssm_engine_admits_beyond_max_len(mamba):
    """An SSM request whose prompt + budget exceeds max_len still serves:
    the recurrent state is O(1) per slot.  The same request on a transformer
    DecodeEngine is rejected (KV would overflow the slot)."""
    cfg, model, params = mamba
    sc = ServeConfig(max_slots=2, max_len=16, eos_id=-1)
    prompt = np.arange(1, 40) % cfg.vocab_size       # 39 tokens >> max_len

    eng = SSMEngine(model, params, sc)
    rid = eng.submit(prompt, max_new_tokens=5)
    out = eng.run_to_completion(100)
    assert len(out[rid]) == 5

    dec = DecodeEngine(model, params, sc)
    rid2 = dec.submit(prompt, max_new_tokens=5)
    out2 = dec.run_to_completion(100)
    assert out2[rid2] == []            # rejected-but-recorded


def test_ssm_engine_arena_is_slot_bound(mamba):
    """Arena capacity reflects slots x constant state, independent of
    max_len; a full slot pool backpressures, a free one admits."""
    cfg, model, params = mamba
    a = SSMEngine(model, params, ServeConfig(max_slots=2, max_len=16,
                                             eos_id=-1))
    b = SSMEngine(model, params, ServeConfig(max_slots=2, max_len=4096,
                                             eos_id=-1))
    assert a.arena.capacity == b.arena.capacity
    assert a.arena.capacity == 2 * S.state_elems(cfg) * cfg.num_layers


def test_ssm_engine_rejects_kv_archs(mamba):
    cfg, model, params = mamba
    qcfg = get_reduced("qwen2.5-32b")
    qmodel = build_model(qcfg)
    qparams = strip(qmodel.init(jax.random.key(0)))
    with pytest.raises(ValueError):
        SSMEngine(qmodel, qparams, ServeConfig())


def test_workload_class_derivation():
    assert workload_class_of(_fm_cfg()) == SSM
    assert workload_class_of(get_reduced("qwen2.5-32b")) == DECODE
    assert workload_class_of(get_reduced("hymba-1.5b")) == DECODE  # hybrid: KV
    assert workload_class_of(_s2t_cfg()) == ENCDEC  # enc-dec: full jobs


def test_length_bucket_ladder():
    assert length_buckets((), 128) == (128,)
    assert length_buckets((512, 128, 999), 512) == (128, 512)
    ladder = length_buckets((8, 16), 32)
    assert ladder == (8, 16, 32)
    assert pick_bucket(ladder, 5) == 8
    assert pick_bucket(ladder, 8) == 8
    assert pick_bucket(ladder, 9) == 16
    assert pick_bucket(ladder, 30) == 32


def test_engines_satisfy_protocol(mamba):
    cfg, model, params = mamba
    eng = SSMEngine(model, params, ServeConfig(max_slots=1, eos_id=-1))
    enc = EncoderEngine(model, params, ServeConfig(max_slots=1, max_len=16))
    assert isinstance(eng, Engine) and isinstance(enc, Engine)


# ---------------------------------------------------------------------------
# enc-dec decode through the fabric: cross-attention source cache
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def seamless():
    cfg = _s2t_cfg()
    model = build_model(cfg)
    params = strip(model.init(jax.random.key(0)))
    return cfg, model, params


def test_encdec_engine_satisfies_protocol(seamless):
    cfg, model, params = seamless
    eng = EncDecEngine(model, params,
                       ServeConfig(max_slots=1, max_len=16, eos_id=-1,
                                   max_src_len=8))
    assert isinstance(eng, Engine)
    assert eng.workload_class == ENCDEC


def test_encdec_rejects_decoder_only_archs():
    qcfg = get_reduced("qwen2.5-32b")
    qmodel = build_model(qcfg)
    qparams = strip(qmodel.init(jax.random.key(0)))
    with pytest.raises(ValueError):
        EncDecEngine(qmodel, qparams, ServeConfig())


def test_encdec_stream_matches_monolithic_forward(seamless):
    """Cross-attention cache correctness: the engine's pooled-slot decode —
    bucketed batched encode (key padding masked per row), per-slot cross
    K/V write, masked per-row src_len — must emit the exact token stream of
    a monolithic Model prefill + decode_step loop over the EXACT-LENGTH
    inputs: the padding mask makes bucketed encodes bit-identical to
    unpadded ones, so the reference needs no bucket knowledge at all."""
    cfg, model, params = seamless
    sc = ServeConfig(max_slots=1, max_len=16, eos_id=-1, max_src_len=12,
                     len_buckets=(8,))
    eng = EncDecEngine(model, params, sc)
    rng = np.random.default_rng(0)
    srcs = [rng.integers(1, cfg.vocab_size, size=L) for L in (5, 7, 11)]
    rids = [eng.submit(s, max_new_tokens=6) for s in srcs]
    out = eng.run_to_completion(200)
    # two sources share the 8-bucket, the 11-frame one runs at capacity
    assert eng.stats()["bucket_hits"] == {"8": 2, "12": 1}

    for s, rid in zip(srcs, rids):
        enc = model.encode(params, {"tokens": jnp.asarray(s[None])})
        cache = strip(model.init_cache(1, sc.max_len, src_len=len(s)))
        logits, cache = model.prefill(
            params, {"tokens": jnp.full((1, 1), sc.bos_id, jnp.int32)},
            cache, enc_out=enc, src_len=len(s))
        stream = [int(jnp.argmax(logits[0]))]
        for _ in range(5):
            logits, cache = model.decode_step(
                params, cache, jnp.asarray([[stream[-1]]], jnp.int32))
            stream.append(int(jnp.argmax(logits[0])))
        assert out[rid] == stream, \
            f"engine decode diverged from monolithic forward for rid {rid}"


def test_encdec_admission_backpressure_on_source_cache(seamless):
    """Admission is arena-bound across BOTH caches: when live source caches
    + decode budgets exhaust the arena, later jobs stay queued (never lost)
    and admit as slots free.  The arena is shrunk to one job's footprint so
    the source-cache rows are what blocks the second admission."""
    from repro.core.arena import FlexArena
    cfg, model, params = seamless
    sc = ServeConfig(max_slots=2, max_len=16, eos_id=-1, max_src_len=8)
    eng = EncDecEngine(model, params, sc)
    src, new = 8, 7
    rows = src + 1 + new                       # source + BOS + budget
    eng.arena = FlexArena(rows * eng._per_token_elems)
    rng = np.random.default_rng(0)
    r1 = eng.submit(rng.integers(1, cfg.vocab_size, size=src),
                    max_new_tokens=new)
    r2 = eng.submit(rng.integers(1, cfg.vocab_size, size=src),
                    max_new_tokens=new)
    eng.step()
    assert eng.active_count == 1 and eng.queue_depth == 1, \
        "second job should backpressure on the exhausted arena"
    out = eng.run_to_completion(200)
    assert len(out[r1]) == new and len(out[r2]) == new

    # oversized sources are rejected-but-recorded, like every other class
    r3 = eng.submit(rng.integers(1, cfg.vocab_size, size=9),  # > max_src_len
                    max_new_tokens=2)
    out = eng.run_to_completion(50)
    assert out[r3] == []


def test_encoder_embeddings_bucket_invariant(seamless):
    """ROADMAP-flagged bugfix: the bidirectional encoder masks each row's
    own bucket padding, so the same job's embedding does not depend on the
    bucket ladder (before the fix, the padded program shape leaked into the
    numerics).  Masked padding contributes exact zeros, but XLA may tile
    the reductions of each program shape differently, so the embeddings
    agree to float32 rounding (observed ~4e-7), pinned at the TP-degree
    test's tolerance."""
    cfg, model, params = seamless
    job = np.arange(1, 6) % cfg.vocab_size

    def run(buckets):
        eng = EncoderEngine(model, params,
                            ServeConfig(max_slots=2, max_len=32,
                                        len_buckets=buckets))
        rid = eng.submit(job)
        eng.run_to_completion(10)
        return eng.results()[rid]

    a, b, full = run((8,)), run((16,)), run(())
    for got in (a, b):
        np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                                   rtol=1e-5, atol=1e-6)


def test_encdec_forced_decode_matches_monolithic(seamless):
    """Forced decoding: a target prefix threads through submit and the
    fused slot-prefill program — the stream must equal a monolithic Model
    prefill over [bos]+prefix (exact lengths) + greedy decode_step loop."""
    cfg, model, params = seamless
    sc = ServeConfig(max_slots=2, max_len=24, eos_id=-1, max_src_len=12,
                     len_buckets=(8,))
    eng = EncDecEngine(model, params, sc)
    rng = np.random.default_rng(0)
    src = rng.integers(1, cfg.vocab_size, size=7)
    prefix = rng.integers(1, cfg.vocab_size, size=4)
    rid = eng.submit(src, max_new_tokens=6, prefix=prefix)
    plain = eng.submit(src, max_new_tokens=6)        # BOS-only co-resident
    out = eng.run_to_completion(200)

    dec = np.concatenate([[sc.bos_id], prefix]).astype(np.int32)
    enc = model.encode(params, {"tokens": jnp.asarray(src[None])})
    cache = strip(model.init_cache(1, sc.max_len, src_len=len(src)))
    logits, cache = model.prefill(params, {"tokens": jnp.asarray(dec[None])},
                                  cache, enc_out=enc, src_len=len(src))
    stream = [int(jnp.argmax(logits[0]))]
    for _ in range(5):
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([[stream[-1]]], jnp.int32))
        stream.append(int(jnp.argmax(logits[0])))
    assert out[rid] == stream, "forced decode diverged from monolithic"
    assert out[plain] != out[rid], \
        "prefix had no effect on the decoder stream"
    # arena accounting covers the prefix rows: src + (1+prefix) + budget
    from repro.workloads.decode import Request
    req = Request(0, src, 6, prefix=np.asarray(prefix, np.int32))
    assert eng._slot_rows(req) == len(src) + 1 + len(prefix) + 6
    # a prefix that overflows the decoder slot is a hard reject
    assert eng._oversized(Request(1, src, sc.max_len,
                                  prefix=np.asarray(prefix, np.int32)))


def test_encdec_accepts_precomputed_frames(seamless):
    """A real frontend's precomputed (S, d_model) frame embeddings enter
    submit directly — no token re-embedding — and (the STUB embedding
    being jnp.take on the embed table) produce the token path's exact
    stream; the embedded rows pay the same arena rows as token sources."""
    cfg, model, params = seamless
    # slot-granular arena: the reservation is the exact worst case below
    # (a paged table would cover live rows only, growing with decode)
    sc = ServeConfig(max_slots=2, max_len=24, eos_id=-1, max_src_len=12,
                     len_buckets=(8,), paged_kv=False)
    eng = EncDecEngine(model, params, sc)
    rng = np.random.default_rng(0)
    src = rng.integers(1, cfg.vocab_size, size=7)
    frames = np.asarray(params["embed"])[src]         # the STUB's embedding
    r_tok = eng.submit(src, max_new_tokens=6)
    r_frm = eng.submit(frames, max_new_tokens=6)
    # both jobs admitted: the frame job's arena view covers its frame rows
    eng.step()
    assert eng.active_count == 2
    views = {req.rid: req.view for req in eng._active.values()}
    assert views[r_frm].rows == views[r_tok].rows == 7 + 1 + 6
    # under paging both source kinds still pay identical (live) rows
    engp = EncDecEngine(model, params,
                        dataclasses.replace(sc, paged_kv=True))
    rp_tok = engp.submit(src, max_new_tokens=6)
    rp_frm = engp.submit(frames, max_new_tokens=6)
    engp.step()
    vp = {req.rid: req.view for req in engp._active.values()}
    assert vp[rp_frm].rows == vp[rp_tok].rows
    out = eng.run_to_completion(200)
    assert out[r_frm] == out[r_tok], \
        "precomputed frames diverged from the token-embedding path"
    # oversized frame sources reject-but-record like token sources
    r_big = eng.submit(np.zeros((13, cfg.d_model), np.float32),
                       max_new_tokens=2)
    out = eng.run_to_completion(50)
    assert out[r_big] == []


# ---------------------------------------------------------------------------
# shared executable cache: same-config engines reuse programs
# ---------------------------------------------------------------------------

def test_same_config_engines_share_executables(mamba):
    cfg, model, params = mamba
    shared = ExecutableCache(capacity=32)
    sc = ServeConfig(max_slots=2, max_len=32, eos_id=-1)
    a = SSMEngine(model, params, sc, exec_cache=shared)
    b = SSMEngine(model, params, sc, exec_cache=shared)
    prompt = np.arange(1, 9)
    a.submit(prompt, max_new_tokens=3)
    a.run_to_completion(50)
    assert a.compile_builds > 0
    b.submit(prompt, max_new_tokens=3)
    b.run_to_completion(50)
    assert b.compile_builds == 0, \
        "same-config tenant should hit the shared fabric cache"
    # different serve dims -> different program: no false sharing
    c = SSMEngine(model, params, ServeConfig(max_slots=3, max_len=32,
                                             eos_id=-1), exec_cache=shared)
    c.submit(prompt, max_new_tokens=3)
    c.run_to_completion(50)
    assert c.compile_builds > 0
    # different sharding rules -> different program: a replicated and a TP
    # engine of the same config must never share a compiled executable
    from repro.serve import serve_engine_rules
    ann = model.init(jax.random.key(0))     # annotated params (rules need them)
    d = SSMEngine(model, ann, sc, rules=serve_engine_rules(),
                  exec_cache=shared)
    d.submit(prompt, max_new_tokens=3)
    d.run_to_completion(50)
    assert d.compile_builds > 0


def test_encoder_bucketed_programs_match_full_capacity(mamba):
    """Bucketed sequence-length encode: every job runs in its OWN smallest
    fitting program (recorded in stats) and — causal stacks being
    padding-proof — emits exactly the embeddings of the full-capacity
    program."""
    cfg, model, params = mamba
    jobs = [np.arange(1, 1 + L) % cfg.vocab_size for L in (4, 6, 20, 3)]

    def run(buckets):
        eng = EncoderEngine(model, params,
                            ServeConfig(max_slots=2, max_len=32,
                                        len_buckets=buckets))
        for j in jobs:
            eng.submit(j)
        while eng.has_work:
            eng.step()
        return eng

    full = run(())
    bucketed = run((8, 16))
    assert full.stats()["bucket_hits"] == {"32": 4}
    # step 1 batches lens (4, 6) -> both 8-bucket; step 2 batches (20, 3)
    # -> split per job into the capacity program and the 8-bucket one
    assert bucketed.stats()["bucket_hits"] == {"8": 3, "16": 0, "32": 1}
    assert bucketed.results() == full.results(), \
        "bucketed encode changed a causal stack's embeddings"


def test_encoder_bucket_is_per_job_not_per_batch(seamless):
    """A job's bucket — hence the row padding a BIDIRECTIONAL stack sees —
    must be a function of the job alone: co-batching a short job with a
    long one must not change its embedding (arrival timing would otherwise
    alter results)."""
    cfg, model, params = seamless
    sc = ServeConfig(max_slots=2, max_len=32, len_buckets=(8,))
    short = np.arange(1, 5) % cfg.vocab_size
    long = np.arange(1, 21) % cfg.vocab_size

    alone = EncoderEngine(model, params, sc)
    r_alone = alone.submit(short)
    alone.run_to_completion(10)

    both = EncoderEngine(model, params, sc)
    r_both = both.submit(short)
    both.submit(long)                       # co-batched in the same step
    both.run_to_completion(10)

    assert both.results()[r_both] == alone.results()[r_alone], \
        "co-batching changed a bidirectional job's embedding"


def test_encoder_rejections_not_counted_as_throughput(mamba):
    """Oversized embedding jobs are rejected-but-recorded, and — like the
    decode engine's rejects — never emitted: emitted entries feed the
    fabric's per-class throughput accounting."""
    cfg, model, params = mamba
    enc = EncoderEngine(model, params, ServeConfig(max_slots=2, max_len=8))
    ok = enc.submit(np.arange(1, 6))
    bad = enc.submit(np.arange(1, 30))          # 29 tokens > max_len
    emitted = []
    while enc.has_work:
        emitted.extend(enc.step())
    assert [r for r, _ in emitted] == [ok]
    assert enc.results()[bad] == []             # recorded, empty
    assert len(enc.results()[ok]) == cfg.d_model
    assert enc.stats()["seqs_done"] == 1


# ---------------------------------------------------------------------------
# class-aware policy costing
# ---------------------------------------------------------------------------

def test_step_cost_cache_key_includes_workload_class():
    """Satellite regression: an SSM/encoder/encdec tenant sharing a cfg.name
    with a transformer tenant must not read a stale decode-GEMM price."""
    pol = AnalyticalPolicy()
    cfg = _fm_cfg()
    dec = pol.step_cost(cfg, 2, 4)                   # caches under DECODE
    ssm = pol.step_cost(cfg, 2, 4, SSM)
    enc = pol.step_cost(cfg, 2, 4, ENCODER)
    ed = pol.step_cost(cfg, 2, 4, ENCDEC, src_len=64)
    assert len({dec, ssm, enc, ed}) == 4
    # and the decode price is unchanged by the later class-keyed entries
    assert pol.step_cost(cfg, 2, 4) == dec


def test_step_cost_scales_down_with_cus_per_class():
    pol = AnalyticalPolicy()
    cfg = _fm_cfg()
    qcfg = get_reduced("qwen2.5-32b")
    scfg = _s2t_cfg()
    for c, wc in ((cfg, SSM), (qcfg, ENCODER), (qcfg, DECODE),
                  (scfg, ENCDEC)):
        assert pol.step_cost(c, 2, 4, wc) < pol.step_cost(c, 2, 1, wc)


def test_step_cost_encdec_prices_cross_attention_by_src_len():
    """The encdec step price (seconds per decode step) must grow with the
    source length — each step reads the whole per-slot cross-attention
    source cache — and the price must be keyed by src_len so two enc-dec
    tenants with different source capacities never share a stale entry."""
    pol = AnalyticalPolicy()
    cfg = _s2t_cfg()
    short = pol.step_cost(cfg, 2, 2, ENCDEC, src_len=64)
    long = pol.step_cost(cfg, 2, 2, ENCDEC, src_len=64 * 1024)
    assert long > short
    # cached entries survive interleaved queries at the other src_len
    assert pol.step_cost(cfg, 2, 2, ENCDEC, src_len=64) == short
    # an encdec step also prices the extra cross-projection GEMVs: it must
    # cost at least a plain decode step of the same dims
    assert pol.step_cost(cfg, 2, 2, ENCDEC, src_len=64) > \
        pol.step_cost(cfg, 2, 2, DECODE)


def _load(pending, active=1, util=0.0, wclass=None):
    return TenantObservation(pending_tokens=pending, queue_depth=0,
                             active=active, arena_utilization=util,
                             wclass=wclass)


def _cus(points):
    return {t: p.cus for t, p in points.items() if p.cus > 0}


def test_mixed_fleet_split_shifts_toward_owed_class():
    """The split search allocates CUs toward the class with owed work,
    under each class's own cost model."""
    cfgs = {"dec": get_reduced("minitron-4b"), "ssm": _fm_cfg(),
            "enc": get_reduced("qwen2.5-32b")}
    classes = {"dec": DECODE, "ssm": SSM, "enc": ENCODER}
    pol = AnalyticalPolicy()
    # the encoder tenant owes a large prefill backlog; others trickle
    points, reason = pol.decide(
        {t: _load(p, wclass=classes[t])
         for t, p in (("dec", 5), ("ssm", 5), ("enc", 5000))},
        cfgs, {"dec": 3, "ssm": 3, "enc": 2}, 8)
    sizes = _cus(points)
    assert reason in ("rebalance", "admit")
    assert sizes["enc"] > 2, f"expected encoder to gain CUs, got {sizes}"
    assert sizes["enc"] > sizes["dec"] and sizes["enc"] > sizes["ssm"]
    # now the SSM tenant owes the work
    points2, reason2 = pol.decide(
        {t: _load(p, wclass=classes[t])
         for t, p in (("dec", 5), ("ssm", 5000), ("enc", 5))},
        cfgs, {"dec": 3, "ssm": 3, "enc": 2}, 8)
    sizes2 = _cus(points2)
    assert sizes2["ssm"] >= sizes2["dec"] and sizes2["ssm"] >= sizes2["enc"]
    assert sizes2["ssm"] > 3 or reason2 == "hysteresis"


def test_policy_exposes_runner_up():
    cfgs = {"a": get_reduced("minitron-4b"), "b": get_reduced("minitron-4b")}
    pol = AnalyticalPolicy()
    points, reason = pol.decide({"a": _load(50), "b": _load(50)},
                                cfgs, {"a": 4, "b": 4}, 8)
    assert reason == "hysteresis"
    # staying put: the runner-up is the best alternative design, the one
    # the fabric speculatively prewarms during idle decide intervals
    assert pol.runner_up is not None
    assert sum(_cus(pol.runner_up).values()) == 8
    pol.decide({"a": _load(0), "b": _load(0)}, cfgs, {"a": 4, "b": 4}, 8)
    assert pol.runner_up is None       # idle fabric: nothing worth warming


# ---------------------------------------------------------------------------
# device scenarios (8 fake host devices, subprocess)
# ---------------------------------------------------------------------------

_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import dataclasses
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


def test_ssm_tp_and_recomposition_stream_invariance():
    """SSM serving mirrors the transformer pins: token streams across 1-way
    (replicated) and 2-way TP sub-meshes are identical, including across a
    mid-stream recomposition that changes the TP degree."""
    res = _run("""
    from repro.configs import get_reduced
    from repro.core.composer import MeshComposer
    from repro.models import build_model
    from repro.serve import serve_engine_rules
    from repro.workloads import SSMEngine, ServeConfig

    mesh = make_host_mesh((1, 8), ("data", "model"))
    comp = MeshComposer(mesh)
    cfg = dataclasses.replace(get_reduced("falcon-mamba-7b"),
                              dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    sc = ServeConfig(max_slots=2, max_len=64, eos_id=-1)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=L)
               for L in (5, 9, 7)]              # few distinct exact lengths

    def run(tp, rules, script=None):
        eng = SSMEngine(model, params, sc,
                        mesh=comp.submesh(range(tp), f"tp{tp}"),
                        rules=rules)
        for p in prompts:
            eng.submit(p, max_new_tokens=10)
        step = 0
        while eng.has_work:
            if script and step in script:
                eng.reshard_to(comp.submesh(range(script[step]), "re"))
            eng.step()
            step += 1
            assert step < 200
        return {str(r): t for r, t in eng.results().items()}

    rules = serve_engine_rules()
    ref = run(1, None)                          # replicated baseline
    tp2 = run(2, rules)
    dyn = run(2, rules, {3: 1, 7: 4, 11: 2})    # shrink -> grow -> back
    print(json.dumps({"n": len(ref), "tp2": tp2 == ref, "dyn": dyn == ref}))
    """)
    assert res["n"] == 3
    assert res["tp2"], "TP SSM decode diverged from replicated"
    assert res["dyn"], "mid-stream recomposition altered the SSM stream"


def test_encoder_embeddings_invariant_across_moves():
    """Embedding outputs are bit-identical when the engine migrates between
    sub-accelerators (replicated), and equal across 1-way vs 2-way TP."""
    res = _run("""
    from repro.configs import get_reduced
    from repro.core.composer import MeshComposer
    from repro.models import build_model
    from repro.serve import serve_engine_rules
    from repro.workloads import EncoderEngine, ServeConfig

    mesh = make_host_mesh((1, 8), ("data", "model"))
    comp = MeshComposer(mesh)
    cfg = dataclasses.replace(get_reduced("qwen2.5-32b"), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    sc = ServeConfig(max_slots=2, max_len=32)
    rng = np.random.default_rng(0)
    jobs = [rng.integers(1, cfg.vocab_size, size=int(rng.integers(4, 20)))
            for _ in range(5)]

    def run(ids, rules, move=None):
        eng = EncoderEngine(model, params, sc,
                            mesh=comp.submesh(ids, "enc"), rules=rules)
        out = {}
        for i, j in enumerate(jobs):
            eng.submit(j)
            if move is not None and i == 2:
                eng.reshard_to(comp.submesh(move, "moved"))
            eng.step()
        return eng.results()

    ref = run(range(2), None)
    moved = run(range(2), None, move=[4, 5])     # same size, other devices
    tp2 = run(range(2), serve_engine_rules())
    exact = all(ref[r] == moved[r] for r in ref)
    close = all(np.allclose(ref[r], tp2[r], rtol=1e-5, atol=1e-6)
                for r in ref)
    print(json.dumps({"n": len(ref), "exact_across_move": exact,
                      "tp_close": close}))
    """)
    assert res["n"] == 5
    assert res["exact_across_move"], \
        "moving the encoder between same-size compositions changed outputs"
    assert res["tp_close"], "TP encoder diverged from replicated"


def test_encdec_streams_invariant_across_recomposition():
    """Acceptance pin: enc-dec decode streams are bit-identical across a
    mid-stream live recomposition (1->2 CU grow, then back) vs a never-moved
    reference run, and 2-way TP (with and without mid-stream degree changes)
    emits the replicated streams."""
    res = _run("""
    from repro.configs import get_reduced
    from repro.core.composer import MeshComposer
    from repro.models import build_model
    from repro.serve import serve_engine_rules
    from repro.workloads import EncDecEngine, ServeConfig

    mesh = make_host_mesh((1, 8), ("data", "model"))
    comp = MeshComposer(mesh)
    cfg = dataclasses.replace(get_reduced("seamless-m4t-medium"),
                              dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    sc = ServeConfig(max_slots=2, max_len=24, eos_id=-1, max_src_len=16,
                     len_buckets=(8,))
    rng = np.random.default_rng(0)
    srcs = [rng.integers(1, cfg.vocab_size, size=L) for L in (5, 9, 7, 13)]

    def run(tp, rules, script=None):
        eng = EncDecEngine(model, params, sc,
                           mesh=comp.submesh(range(tp), f"tp{tp}"),
                           rules=rules)
        for s in srcs:
            eng.submit(s, max_new_tokens=8)
        step = 0
        while eng.has_work:
            if script and step in script:
                eng.reshard_to(comp.submesh(range(script[step]), "re"))
            eng.step()
            step += 1
            assert step < 200
        return {str(r): t for r, t in eng.results().items()}

    rules = serve_engine_rules()
    ref = run(1, None)                          # never-moved baseline
    moved = run(1, None, {3: 2, 7: 1})          # the 1->2 CU move (and back)
    tp2 = run(2, rules)
    dyn = run(2, rules, {3: 1, 7: 4})
    print(json.dumps({"n": len(ref),
                      "lens_ok": all(len(t) == 8 for t in ref.values()),
                      "moved": moved == ref, "tp2": tp2 == ref,
                      "dyn": dyn == ref}))
    """)
    assert res["n"] == 4 and res["lens_ok"]
    assert res["moved"], "1->2 CU live recomposition altered enc-dec streams"
    assert res["tp2"], "TP enc-dec decode diverged from replicated"
    assert res["dyn"], "mid-stream TP degree change altered enc-dec streams"


def test_live_reconfigure_stream_invariance():
    """Serving-DSE acceptance pin: a mid-stream ``Engine.apply`` — a
    slot-count change AND a TP-degree change on a FIXED CU grant — leaves
    pinned decode streams bit-identical vs a never-retuned run, for
    both the transformer decode and the SSM engine (live slots are
    migrated into the resized pool; the TP move is a sharded device_put)."""
    res = _run("""
    from repro.configs import get_reduced
    from repro.core.composer import MeshComposer
    from repro.core.dse import DesignPoint
    from repro.models import build_model
    from repro.serve import serve_engine_rules
    from repro.workloads import DecodeEngine, SSMEngine, ServeConfig

    mesh = make_host_mesh((1, 8), ("data", "model"))
    comp = MeshComposer(mesh)
    rules = serve_engine_rules()
    out = {}
    for arch, cls in (("minitron-4b", DecodeEngine),
                      ("falcon-mamba-7b", SSMEngine)):
        cfg = dataclasses.replace(get_reduced(arch), dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        sc = ServeConfig(max_slots=2, max_len=48, eos_id=-1)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab_size, size=L)
                   for L in (5, 9, 7)]
        grant = comp.submesh(range(4), "fixed")      # the grant never moves

        def run(script=None):
            eng = cls(model, params, sc, mesh=grant, rules=rules)
            for p in prompts:
                eng.submit(p, max_new_tokens=10)
            step = 0
            while eng.has_work:
                if script and step in script:
                    eng.apply(None, DesignPoint(cus=0, **script[step]))
                eng.step()
                step += 1
                assert step < 300
            return eng, {str(r): t for r, t in eng.results().items()}

        _, ref = run()
        eng, dyn = run({2: {"slots": 4}, 5: {"tp": 2},
                        8: {"slots": 2, "tp": 4}})
        out[arch] = {"match": dyn == ref,
                     "design": {k: (list(v) if isinstance(v, tuple) else v)
                                for k, v in eng.design().items()}}
    print(json.dumps(out))
    """)
    for arch, r in res.items():
        assert r["match"], \
            f"mid-stream reconfigure altered {arch} decode streams"
        assert r["design"]["tp"] == 4 and r["design"]["slots"] >= 2


def test_mixed_fleet_end_to_end_with_live_class_moves():
    """Acceptance: a mixed fleet (transformer decode + mamba + encoder +
    seamless enc-dec) runs end-to-end through ComposedServer with >=1 live
    recomposition between classes, and SSM token streams / encoder
    embeddings / enc-dec decode streams are bit-identical to a
    never-recomposed run of the same fleet."""
    res = _run("""
    from repro.serve.fabric import (AnalyticalPolicy, ComposedServer,
                                    TenantSpec)
    from repro.workloads import ServeConfig

    mesh = make_host_mesh((1, 8), ("data", "model"))
    sc = ServeConfig(max_slots=2, max_len=48, eos_id=-1)
    s2t_sc = ServeConfig(max_slots=2, max_len=16, eos_id=-1, max_src_len=16,
                         len_buckets=(8,))
    tenants = [
        TenantSpec("llm", "minitron-4b", serve=sc),
        TenantSpec("mamba", "falcon-mamba-7b", seed=1, serve=sc),
        TenantSpec("embed", "qwen2.5-32b", seed=2, serve=sc,
                   workload="encoder"),
        TenantSpec("s2t", "seamless-m4t-medium", seed=3, serve=s2t_sc),
    ]

    def run(policy):
        srv = ComposedServer(mesh, tenants, policy=policy, decide_every=3,
                             tp=False)       # replicated: bit-exact moves
        rng = np.random.default_rng(0)
        def traffic(name, n, new):
            vocab = srv.cfgs[name].vocab_size
            for _ in range(n):
                srv.submit(name, rng.integers(1, vocab, size=8),
                           max_new_tokens=new)
        traffic("llm", 2, 8)
        traffic("embed", 3, 0)
        traffic("s2t", 2, 8)
        for _ in range(8):
            srv.step()
        traffic("mamba", 3, 10)              # burst: forces a class move
        out = srv.drain(max_steps=300)
        return srv, out

    srv, out = run(AnalyticalPolicy())
    ref_srv, ref = run(None)                  # static composition baseline
    moved_classes = {srv.classes[t] for e in srv.events for t in e.moved}
    print(json.dumps({
        "recomps": len(srv.events),
        "classes": srv.classes,
        "moved_classes": sorted(moved_classes),
        "ssm_match": out["mamba"] == ref["mamba"],
        "enc_match": out["embed"] == ref["embed"],
        "encdec_match": out["s2t"] == ref["s2t"],
        "llm_match": out["llm"] == ref["llm"],
        "done": {t: len(d) for t, d in out.items()},
    }))
    """)
    assert res["recomps"] >= 1, "expected a live recomposition"
    assert len(res["moved_classes"]) >= 2, \
        f"expected moves across classes, got {res['moved_classes']}"
    assert res["classes"]["s2t"] == "encdec"   # derived from the arch
    assert res["ssm_match"], "SSM streams changed across the live move"
    assert res["enc_match"], "encoder embeddings changed across the live move"
    assert res["encdec_match"], \
        "enc-dec decode streams changed across the live move"
    assert res["llm_match"]
    assert res["done"] == {"llm": 2, "mamba": 3, "embed": 3, "s2t": 2}


def test_speculative_runner_up_prewarm():
    """Idle decide intervals warm the policy's runner-up split in the
    background: the fabric records speculative prewarms and the runner-up
    composition's executables are already cached when it later commits."""
    res = _run("""
    import time
    from repro.serve.fabric import (AnalyticalPolicy, ComposedServer,
                                    TenantSpec)
    from repro.workloads import ServeConfig

    mesh = make_host_mesh((1, 8), ("data", "model"))
    sc = ServeConfig(max_slots=2, max_len=32, eos_id=-1)
    # min_gain pinned sky-high: every decide is a hysteresis tick, so the
    # test exercises exactly the idle-interval speculative path (at the
    # default gain the two-stage policy would commit a rebalance first)
    srv = ComposedServer(mesh, [
        TenantSpec("a", "minitron-4b", serve=sc),
        TenantSpec("b", "minitron-4b", seed=1, serve=sc),
    ], policy=AnalyticalPolicy(min_gain=100.0), decide_every=2,
       prewarm_async=True)
    rng = np.random.default_rng(0)
    vocab = srv.cfgs["a"].vocab_size
    # balanced load: the policy stays put (hysteresis) but exposes a
    # runner-up design, which the idle ticks compile in the background
    for t in ("a", "b"):
        srv.submit(t, rng.integers(1, vocab, size=8), max_new_tokens=20)
    steps = 0
    while srv.speculative_prewarms == 0 and steps < 100:
        srv.step()
        steps += 1
    for f in srv._spec_futures:
        f.result()                     # block: surface background errors
    print(json.dumps({"speculative": srv.speculative_prewarms,
                      "events": len(srv.events)}))
    """)
    assert res["speculative"] >= 1, \
        "balanced fleet never speculatively prewarmed its runner-up split"
