"""Ragged decode kernels on the serving hot path: bit-equality of the
kernel-backed decode programs vs the padded XLA path, the fused single-step
mamba scan, the decayed length estimator behind ``recent_lengths()``, and
the kernel-aware analytical step-cost terms.

The load-bearing invariant: ``ServeConfig.use_kernels`` must be a pure
performance knob — every engine's token stream is bit-identical with it on
or off, including across mid-stream recompositions (pinned here and in the
subprocess scenario at the bottom).
"""
import subprocess
import sys
import textwrap

import json
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_reduced
from repro.kernels.mamba_scan import (mamba_step_fused, mamba_step_kernel,
                                      mamba_step_ref)
from repro.kernels.ragged_decode import (ragged_decode_attention,
                                         ragged_decode_attention_ref,
                                         ragged_decode_kernel)
from repro.models import ssm as S
from repro.models.layers import decode_attention
from repro.serve.dse import Stage1Optimizer, TenantDesignSpace
from repro.serve.fabric import AnalyticalPolicy
from repro.workloads.base import DECODE, ENCODER, DecayedLengthEstimator

RNG = np.random.default_rng(11)


def _qkv(B, T, Hq, Hkv, D, dtype=jnp.float32):
    q = jnp.asarray(RNG.normal(size=(B, 1, Hq, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, T, Hkv, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, T, Hkv, D)), dtype)
    return q, k, v


# ---------------------------------------------------------------------------
# ragged decode attention: ref == padded decode_attention, kernel == ref
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,logit_cap,is_global", [
    (0, 0.0, None), (16, 0.0, None), (8, 30.0, None),
    (8, 0.0, True), (0, 50.0, None),
])
def test_ragged_ref_is_bitexact_vs_padded_path(window, logit_cap, is_global):
    """The oracle IS the padded path op-for-op: exact equality, not close."""
    B, T, Hq, Hkv, D = 5, 64, 8, 2, 16
    q, k, v = _qkv(B, T, Hq, Hkv, D)
    lens = jnp.asarray([1, 17, 64, 5, 33], jnp.int32)
    ref = ragged_decode_attention_ref(q, k, v, lens, window=window,
                                      logit_cap=logit_cap,
                                      is_global=is_global)
    padded = decode_attention(q, k, v, lens, window=window,
                              logit_cap=logit_cap, is_global=is_global)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(padded))


def test_sliced_cache_is_bitexact():
    """Foundation of the KV-bound fast path: attention over k[:, :Tc] for
    any Tc >= max(lengths) equals the full-T computation exactly."""
    B, T, Hq, Hkv, D = 4, 96, 4, 4, 8
    q, k, v = _qkv(B, T, Hq, Hkv, D)
    lens = jnp.asarray([3, 30, 11, 25], jnp.int32)
    full = ragged_decode_attention_ref(q, k, v, lens)
    for tc in (32, 64, 96):
        cut = ragged_decode_attention_ref(q, k[:, :tc], v[:, :tc], lens)
        np.testing.assert_array_equal(np.asarray(cut), np.asarray(full))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000),
       hkv=st.sampled_from([1, 2, 4]),
       groups=st.sampled_from([1, 2, 4]),
       window=st.sampled_from([0, 8]),
       logit_cap=st.sampled_from([0.0, 30.0]))
def test_ragged_kernel_matches_ref(seed, hkv, groups, window, logit_cap):
    B, T, D = 4, 64, 16
    q, k, v = _qkv(B, T, hkv * groups, hkv, D)
    lens = jnp.asarray(np.random.default_rng(seed).integers(1, T + 1, size=B),
                       jnp.int32)
    out = ragged_decode_attention(q, k, v, lens, window=window,
                                  logit_cap=logit_cap, impl="interpret",
                                  bk=32)
    ref = ragged_decode_attention_ref(q, k, v, lens, window=window,
                                      logit_cap=logit_cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ragged_kernel_empty_slots_are_exact_zero():
    B, T, Hq, Hkv, D = 6, 64, 4, 2, 16
    q, k, v = _qkv(B, T, Hq, Hkv, D)
    lens = jnp.asarray([9, 64, 1, 200, 3, 17], jnp.int32)  # 200: dead junk
    live = jnp.asarray([1, 1, 0, 0, 1, 0], bool)
    for impl in ("ref", "interpret"):
        out = np.asarray(ragged_decode_attention(
            q, k, v, lens, live=live, impl=impl, bk=32))
        assert np.abs(out[[2, 3, 5]]).max() == 0.0
        ref = np.asarray(ragged_decode_attention_ref(q, k, v, lens))
        np.testing.assert_allclose(out[[0, 1, 4]], ref[[0, 1, 4]],
                                   rtol=2e-5, atol=2e-5)


def test_ragged_kernel_block_multiple_boundaries():
    """Lengths straddling kv-block boundaries (the DMA-skip index map)."""
    B, T, Hq, Hkv, D = 4, 128, 2, 2, 8
    q, k, v = _qkv(B, T, Hq, Hkv, D)
    lens = jnp.asarray([32, 33, 127, 128], jnp.int32)
    out = ragged_decode_kernel(q[:, 0], k, v, lens,
                               jnp.ones((B,), jnp.int32),
                               jnp.zeros((1,), jnp.int32),
                               bk=32, interpret=True)[:, None]
    ref = ragged_decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# fused single-step mamba scan
# ---------------------------------------------------------------------------

def _mamba_setup(B=3):
    cfg = get_reduced("falcon-mamba-7b")
    p = {k: getattr(v, "value", v)
         for k, v in S.mamba_init(jax.random.PRNGKey(3), cfg).items()}
    d_in, _, n, w = S.dims(cfg)
    x1 = jnp.asarray(RNG.normal(size=(B, 1, cfg.d_model)), jnp.float32)
    cache = {"conv": jnp.asarray(RNG.normal(size=(B, w - 1, d_in)),
                                 jnp.float32),
             "h": jnp.asarray(RNG.normal(size=(B, d_in, n)), jnp.float32)}
    return cfg, p, x1, cache


def test_mamba_step_ref_is_bitexact_vs_inline_chain():
    cfg, p, x1, cache = _mamba_setup()
    out_i, new_i = S.mamba_step(p, cfg, x1, dict(cache))
    out_r, conv_r, h_r = mamba_step_ref(
        x1, cache["conv"], cache["h"], p["in_proj"], p["conv_w"],
        p["conv_b"], p["x_proj"], p["dt_proj"], p["dt_bias"], p["A_log"],
        p["D"], p["out_proj"])
    np.testing.assert_array_equal(np.asarray(out_r), np.asarray(out_i))
    np.testing.assert_array_equal(np.asarray(conv_r),
                                  np.asarray(new_i["conv"]))
    np.testing.assert_array_equal(np.asarray(h_r), np.asarray(new_i["h"]))


def test_mamba_step_kernel_matches_ref():
    cfg, p, x1, cache = _mamba_setup()
    args = (x1, cache["conv"], cache["h"], p["in_proj"], p["conv_w"],
            p["conv_b"], p["x_proj"], p["dt_proj"], p["dt_bias"], p["A_log"],
            p["D"], p["out_proj"])
    out_r, conv_r, h_r = mamba_step_ref(*args)
    out_k, conv_k, h_k = mamba_step_fused(*args, impl="interpret")
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(conv_k), np.asarray(conv_r),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                               rtol=3e-5, atol=3e-5)


def test_mamba_step_dead_rows_freeze_state():
    """Dead slots: zero output, conv/h untouched (kernel and ref agree)."""
    cfg, p, x1, cache = _mamba_setup()
    live = jnp.asarray([1, 0, 1], bool)
    args = (x1, cache["conv"], cache["h"], p["in_proj"], p["conv_w"],
            p["conv_b"], p["x_proj"], p["dt_proj"], p["dt_bias"], p["A_log"],
            p["D"], p["out_proj"])
    for impl in ("ref", "interpret"):
        out, conv, h = mamba_step_fused(*args, live=live, impl=impl)
        assert np.abs(np.asarray(out)[1]).max() == 0.0
        np.testing.assert_array_equal(np.asarray(conv)[1],
                                      np.asarray(cache["conv"])[1])
        np.testing.assert_array_equal(np.asarray(h)[1],
                                      np.asarray(cache["h"])[1])


# ---------------------------------------------------------------------------
# KV-bound dispatch: growth past the warm set never compiles on the
# serving path — it falls back to the smallest warm covering bound
# ---------------------------------------------------------------------------

def test_decode_exec_falls_back_to_warm_covering_bound():
    import dataclasses
    from repro.models import build_model
    from repro.distribution import strip
    from repro.workloads import DecodeEngine, ServeConfig
    cfg = dataclasses.replace(get_reduced("minitron-4b"), dtype="float32")
    model = build_model(cfg)
    params = strip(model.init(jax.random.key(0)))
    eng = DecodeEngine(model, params,
                       ServeConfig(max_slots=2, max_len=128, eos_id=-1))
    assert eng._covering_bounds((32,)) == [(64,), (96,), (128,)]
    assert eng._next_bounds() == (64,)       # idle engine: current is (32,)

    full = eng._decode_exec(eng.mesh, (128,))
    builds = eng.compile_builds
    # (96,) was never built: the dispatch must reuse the warm full-bound
    # program, not compile inline
    assert eng._decode_exec(eng.mesh, (96,)) is full
    assert eng.compile_builds == builds


# ---------------------------------------------------------------------------
# decayed length estimator -> Stage-1 bucket choice tracks shifted traffic
# ---------------------------------------------------------------------------

def test_decayed_estimator_tracks_shift_within_bounded_observations():
    est = DecayedLengthEstimator()
    for _ in range(200):
        est.observe(12)
    assert 11.0 <= est.mean() <= 13.0
    # traffic shifts: within ~80 observations (far under the old flat-256
    # window, which would still be majority-stale) the estimate must be
    # dominated by the new regime
    for _ in range(80):
        est.observe(100)
    assert est.mean() > 90.0
    lens = est.lengths()
    assert lens and sum(1 for L in lens if L == 100) > 0.9 * len(lens)


def test_shifted_lengths_change_stage1_bucket_choice():
    pol = AnalyticalPolicy()
    cfg = get_reduced("minitron-4b")
    space = TenantDesignSpace(wclass=ENCODER, max_len=128, base_slots=4,
                              tp_allowed=False)
    est = DecayedLengthEstimator()
    for _ in range(200):
        est.observe(12)
    before = pol.stage1.best(cfg, space, 8, 4, lengths=est.lengths())
    for _ in range(80):
        est.observe(100)
    after = pol.stage1.best(cfg, space, 8, 4, lengths=est.lengths())
    assert before.buckets != after.buckets
    assert before.buckets[0] <= 16      # fit to the short regime
    assert after.buckets[0] >= 96       # re-fit to the shifted regime


# ---------------------------------------------------------------------------
# analytical model: KV-read term and the prefill-padding tax
# ---------------------------------------------------------------------------

def test_step_cost_prices_kv_length():
    pol = AnalyticalPolicy()
    cfg = get_reduced("minitron-4b")
    free = pol.step_cost(cfg, 8, 4, DECODE)               # pre-kernel price
    short = pol.step_cost(cfg, 8, 4, DECODE, kv_len=16)
    full = pol.step_cost(cfg, 8, 4, DECODE, kv_len=512)
    assert free < short < full


def test_cost_of_kernel_mode_prices_true_lengths():
    """Short observed prompts make the kernel-mode decode step strictly
    cheaper than the padded path (which always streams max_len)."""
    pol = AnalyticalPolicy()
    cfg = get_reduced("minitron-4b")
    kw = dict(wclass=DECODE, max_len=512, base_slots=8, tp_allowed=False)
    on = TenantDesignSpace(use_kernels=True, **kw)
    off = TenantDesignSpace(use_kernels=False, **kw)
    from repro.core.dse import DesignPoint
    point = DesignPoint(cus=4, tp=4, slots=8)
    lengths = (12, 20, 16, 9) * 16
    c_on = pol.stage1.cost_of(cfg, on, 8, point, lengths)
    c_off = pol.stage1.cost_of(cfg, off, 8, point, lengths)
    assert c_on < c_off
    # no observations: never under-price an idle tenant
    assert pol.stage1.cost_of(cfg, on, 8, point, ()) == \
        pol.stage1.cost_of(cfg, off, 8, point, ())


def test_cost_of_prices_prefill_padding():
    """Decode-side prompt padding stops being free: a coarser prefill
    bucket on short prompts raises the Stage-1 price."""
    pol = AnalyticalPolicy()
    cfg = get_reduced("minitron-4b")
    kw = dict(wclass=DECODE, max_len=512, base_slots=8, tp_allowed=False)
    from repro.core.dse import DesignPoint
    point = DesignPoint(cus=4, tp=4, slots=8)
    lengths = (5, 9, 7, 12) * 16
    costs = [pol.stage1.cost_of(
        cfg, TenantDesignSpace(prefill_bucket=b, **kw), 8, point, lengths)
        for b in (0, 16, 256)]
    assert costs[0] < costs[1] < costs[2]


# ---------------------------------------------------------------------------
# engine streams: use_kernels on/off bit-identical through recomposition
# and tensor parallelism (8 fake host devices, subprocess)
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


@pytest.mark.slow
def test_kernel_streams_invariant_tp_and_recomposition():
    """DecodeEngine token streams with use_kernels on == off, at tp 1 and
    2, and across a mid-stream recomposition + slot retune (the KV-bound
    program swap and the dp/tp reshard must never perturb a stream)."""
    res = _run("""
    from repro.configs import get_reduced
    from repro.core.composer import MeshComposer
    from repro.core.dse import DesignPoint
    from repro.models import build_model
    from repro.serve import serve_engine_rules
    from repro.workloads import DecodeEngine, ServeConfig

    mesh = make_host_mesh((1, 8), ("data", "model"))
    comp = MeshComposer(mesh)
    cfg = dataclasses.replace(get_reduced("qwen2.5-32b"), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(2, cfg.vocab_size, size=L)
               for L in (5, 23, 40, 3, 17)]

    def run(tp, rules, use_kernels, script=None):
        sc = ServeConfig(max_slots=4, max_len=96, eos_id=-1,
                         prefill_bucket=16, use_kernels=use_kernels)
        eng = DecodeEngine(model, params, sc,
                           mesh=comp.submesh(range(tp), f"tp{tp}"),
                           rules=rules)
        for p in prompts:
            eng.submit(p, max_new_tokens=12)
        step = 0
        while eng.has_work:
            if script and step in script:
                eng.apply(comp.submesh(range(script[step]), "re"),
                          DesignPoint(cus=script[step]))
            eng.step()
            step += 1
            assert step < 300
        return {str(r): t for r, t in eng.results().items()}

    rules = serve_engine_rules()
    ref = run(1, None, False)                   # padded, replicated
    out = {
        "k1": run(1, None, True) == ref,        # kernels, replicated
        "k2": run(2, rules, True) == ref,       # kernels, 2-way TP
        "p2": run(2, rules, False) == ref,      # padded, 2-way TP
        # kernels + mid-stream recomposition (shrink -> grow -> back)
        "kdyn": run(2, rules, True, {3: 1, 7: 4, 11: 2}) == ref,
        "n": len(ref),
    }
    print(json.dumps(out))
    """)
    assert res["n"] == 5
    assert res["k1"] and res["k2"] and res["p2"] and res["kdyn"]


def test_kernel_under_a_mesh_runs_per_head_shard():
    """Traced under a multi-device abstract mesh (as the decode engine
    lowers for a TP sub-mesh), the kernel runs under shard_map: heads split
    over the model axis when the KV heads divide it, every head on every
    device otherwise — either way equal to the reference."""
    res = _run("""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.kernels.ragged_decode import (ragged_decode_attention,
                                             ragged_decode_attention_ref)

    rng = np.random.default_rng(3)
    out = {}
    for n, hkv in ((2, 4), (4, 4), (4, 2)):
        B, T, g, D = 4, 64, 3, 16
        q = jnp.asarray(rng.normal(size=(B, 1, hkv * g, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, T, hkv, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, T, hkv, D)), jnp.float32)
        lens = jnp.asarray([5, 64, 33, 1], jnp.int32)
        live = jnp.asarray([1, 1, 0, 1], bool)
        mesh = make_host_mesh((1, n))
        ax = "model" if hkv % n == 0 else None
        put = lambda x, s: jax.device_put(x, NamedSharding(mesh, s))
        args = (put(q, P(None, None, ax, None)), put(k, P(None, None, ax, None)),
                put(v, P(None, None, ax, None)), put(lens, P()), put(live, P()))
        f = jax.jit(lambda q, k, v, n_, lv: ragged_decode_attention(
            q, k, v, n_, live=lv, impl="interpret", bk=32))
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            lowered = f.lower(*args)
        got = lowered.compile()(*args)
        ref = ragged_decode_attention_ref(q, k, v, lens, live=live)
        out[f"{n}x{hkv}"] = float(jnp.abs(got - ref).max())
        out[f"{n}x{hkv}_manual"] = "manual_computation" in lowered.as_text()
    print(json.dumps(out))
    """)
    for case in ("2x4", "4x4", "4x2"):
        assert res[case] <= 2e-5, (case, res)
        assert res[f"{case}_manual"], (case, res)
