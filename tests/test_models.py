"""Per-architecture smoke tests (deliverable (f)): every assigned arch
instantiates its REDUCED config and runs one forward/train step on CPU,
asserting output shapes and finiteness; plus decode-vs-prefill consistency
(the serving path computes the same function as the parallel path)."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_reduced, cells_for
from repro.distribution import strip
from repro.models import build_model

B, S = 2, 16


def _batch(cfg, rng_key=1):
    tokens = jax.random.randint(jax.random.key(rng_key), (B, S), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(jax.random.key(rng_key + 1),
                                            (B, S, cfg.d_model))
    return batch


@pytest.fixture(scope="module")
def models():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_reduced(arch)
            m = build_model(cfg)
            cache[arch] = (cfg, m, strip(m.init(jax.random.key(0))))
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_loss(models, arch):
    cfg, m, params = models(arch)
    loss, metrics = m.loss(params, _batch(cfg))
    assert loss.shape == ()
    assert jnp.isfinite(loss), (arch, loss)
    assert jnp.isfinite(metrics["xent"])


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(models, arch):
    """One optimizer step: params change, everything stays finite."""
    from repro.optim import make_optimizer
    from repro.train.trainer import TrainConfig, make_train_step

    cfg, m, params = models(arch)
    opt = make_optimizer(cfg.optimizer)
    step = make_train_step(m, opt, TrainConfig(steps=4, lr=1e-3, warmup=1))
    opt_state = opt.init(params)
    # step 1: the cosine schedule's lr is 0 at step 0 (warmup ramp)
    new_params, _, metrics = step(params, opt_state, jnp.asarray(1),
                                  _batch(cfg))
    assert jnp.isfinite(metrics["loss"])
    assert jnp.isfinite(metrics["grad_norm"])
    delta = sum(float(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32)).sum())
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(new_params)))
    assert delta > 0.0, f"{arch}: params did not move"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_prefill(models, arch):
    cfg, m, params = models(arch)
    batch = _batch(cfg)
    tokens = batch["tokens"]
    sl = S if cfg.is_encdec else 0
    if cfg.is_encdec:
        batch["frames"] = batch["frames"]
    full_logits, _ = m.prefill(params, batch,
                               strip(m.init_cache(B, 2 * S, src_len=sl)))
    k = S // 2
    cache = strip(m.init_cache(B, 2 * S, src_len=sl))
    pre = dict(batch)
    pre["tokens"] = tokens[:, :k]
    logits, cache = m.prefill(params, pre, cache)
    for i in range(k, S):
        logits, cache = m.decode_step(params, cache, tokens[:, i:i + 1])
    err = float(jnp.max(jnp.abs(logits.astype(jnp.float32)
                                - full_logits.astype(jnp.float32))))
    assert err < 2e-1, (arch, err)


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "granite-34b",
                                  "deepseek-v2-lite-16b", "chameleon-34b"])
def test_padded_prefill_matches_exact(models, arch):
    """true_len-masked padded prefill == exact-length prefill (attention
    archs only; SSM state is padding-sensitive by design — engine uses
    exact-length prefill there)."""
    cfg, m, params = models(arch)
    batch = _batch(cfg)
    k, pad = 10, 6
    exact = dict(batch)
    exact["tokens"] = batch["tokens"][:, :k]
    le, _ = m.prefill(params, exact, strip(m.init_cache(B, 2 * S)))
    padded = dict(batch)
    padded["tokens"] = jnp.concatenate(
        [batch["tokens"][:, :k], jnp.zeros((B, pad), jnp.int32)], axis=1)
    lp, _ = m.prefill(params, padded, strip(m.init_cache(B, 2 * S)),
                      true_len=k)
    np.testing.assert_allclose(np.asarray(lp, np.float32),
                               np.asarray(le, np.float32), atol=1e-2)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_shapes(arch):
    """The FULL configs are exercised abstractly (no allocation): eval_shape
    the init and one loss; assert the declared parameter count matches the
    materialized tree within 2%."""
    cfg = get_config(arch)
    m = build_model(cfg)
    shapes = jax.eval_shape(m.init, jax.random.key(0))
    total = sum(int(np.prod(l.value.shape)) for l in jax.tree.leaves(
        shapes, is_leaf=lambda x: hasattr(x, "logical")))
    declared = cfg.param_count()
    assert abs(total - declared) / declared < 0.02, (arch, total, declared)


def test_cells_for_documented_skips():
    """long_500k runs exactly for the sub-quadratic archs (DESIGN.md §4)."""
    long_archs = {a for a in ARCH_IDS
                  if any(c.name == "long_500k"
                         for c in cells_for(get_config(a)))}
    assert long_archs == {"hymba-1.5b", "falcon-mamba-7b"}
    for a in ARCH_IDS:
        names = [c.name for c in cells_for(get_config(a))]
        assert {"train_4k", "prefill_32k", "decode_32k"} <= set(names)


def test_continuous_batching_vector_positions(models):
    """Slots at different cache depths decode correctly in one batch."""
    cfg, m, params = models("minitron-4b")
    tokens = jax.random.randint(jax.random.key(3), (2, 12), 0, cfg.vocab_size)
    # row 0 prefilled with 8 tokens, row 1 with 5 (padded prefill+true_len)
    cache = strip(m.init_cache(2, 24))
    padded = jnp.where(jnp.arange(12)[None, :] <
                       jnp.asarray([[8], [5]]), tokens, 0)
    _, cache = m.prefill(params, {"tokens": padded}, cache,
                         true_len=jnp.asarray([8, 5]))
    # decode one token per row; compare against per-row references
    nxt = tokens[:, [8]] * 0 + 7
    logits, _ = m.decode_step(params, cache, nxt)
    for r, plen in enumerate((8, 5)):
        c1 = strip(m.init_cache(1, 24))
        _, c1 = m.prefill(params, {"tokens": tokens[r:r + 1, :plen]}, c1)
        ref, _ = m.decode_step(params, c1, nxt[r:r + 1])
        np.testing.assert_allclose(np.asarray(logits[r], np.float32),
                                   np.asarray(ref[0], np.float32), atol=1e-2)


# ---------------------------------------------------------------------------
# bf16 weights: Model.init casts inside one jitted program, so the device
# never holds the float32 tree — and nothing downstream changes
# ---------------------------------------------------------------------------

def _as_param_dtype(cfg, dtype):
    return dataclasses.replace(cfg, param_dtype=dtype)


@pytest.mark.parametrize("arch", ["minitron-4b", "qwen2.5-32b",
                                  "falcon-mamba-7b", "seamless-m4t-medium"])
def test_bf16_init_equals_float32_init_cast(arch):
    cfg = get_reduced(arch)
    key = jax.random.key(4)
    ref = strip(build_model(_as_param_dtype(cfg, "float32")).init(key))
    ref = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, ref)
    got = strip(build_model(_as_param_dtype(cfg, "bfloat16")).init(key))
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert g.dtype == r.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_init_on_mesh_places_leaves_and_keeps_values():
    from repro.launch.mesh import make_host_mesh
    from repro.serve import serve_engine_rules
    cfg = get_reduced("minitron-4b")
    m = build_model(cfg)
    mesh = make_host_mesh((1, 1))
    got = strip(m.init(jax.random.key(2), mesh=mesh,
                       rules=serve_engine_rules()))
    ref = strip(m.init(jax.random.key(2)))
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert g.sharding.mesh.shape == mesh.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_bf16_weights_leave_greedy_streams_unchanged():
    """Every layer casts weights to the (bf16) activation dtype where it
    uses them, so bf16 weights serve exactly the float32 weights' tokens."""
    from repro.workloads import DecodeEngine, ServeConfig
    cfg = get_reduced("minitron-4b")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (5, 11, 3)]

    def serve(dtype):
        m = build_model(_as_param_dtype(cfg, dtype))
        eng = DecodeEngine(m, strip(m.init(jax.random.key(1))),
                           ServeConfig(max_slots=2, max_len=48, eos_id=-1))
        for p in prompts:
            eng.submit(p, max_new_tokens=8)
        return eng.run_to_completion(200)

    assert serve("bfloat16") == serve("float32")


# ---------------------------------------------------------------------------
# the decode step carries the stacked cache through its layer loop and writes
# one row per slot per layer: the same computation as stepping each layer on
# its own slice, bit for bit
# ---------------------------------------------------------------------------

def _per_layer_decoder_step(params, cfg, x1, cache, **kw):
    """Reference ``decoder_step``: a Python loop over the layers, each
    ``_layer_step`` on that layer's own sliced cache, restacked."""
    from repro.models import transformer as T
    pos = cache["pos"]
    n_pro, n_scan = T._prologue_plan(cfg)
    pro = []
    for i, (lp, lc) in enumerate(zip(params["prologue"], cache["prologue"])):
        x1, nc = T._layer_step(
            lp, cfg, x1, lc, pos,
            is_global=jnp.asarray(i in cfg.global_attn_layers), **kw)
        pro.append(nc)
    layers = []
    for l in range(n_scan):
        lp = jax.tree.map(lambda a: a[l], params["scanned"])
        lc = jax.tree.map(lambda a: a[l], cache["scanned"])
        x1, nc = T._layer_step(
            lp, cfg, x1, lc, pos,
            is_global=jnp.asarray(n_pro + l in cfg.global_attn_layers), **kw)
        layers.append(nc)
    scanned = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    return x1, {"prologue": pro, "scanned": scanned, "pos": pos + 1}


def _bits(a):
    a = np.asarray(a)
    return a.view(f"u{a.dtype.itemsize}")


@pytest.mark.parametrize("kv_bound", [None, 32, 16],
                         ids=["padded", "ragged-full", "ragged-bounded"])
@pytest.mark.parametrize("arch", ["minitron-4b", "hymba-1.5b",
                                  "deepseek-v2-lite-16b", "falcon-mamba-7b",
                                  "seamless-m4t-medium"])
def test_decode_step_carried_cache_matches_per_layer(models, arch, kv_bound,
                                                     monkeypatch):
    """Dense GQA, sliding window + SSM (hybrid), MLA with a dense prologue
    layer, SSM, and enc-dec cross-KV; the padded path and the ragged one
    with a full and a bounded KV read.  Slot 2 sits at max_len - 1 and slot
    3 past the end (its write clamps onto the last row); dead slots are
    those the bound leaves out, and slot 3 always."""
    from repro.models import transformer as T
    cfg, m, params = models(arch)
    nb, max_len, src = 4, 32, 16
    cache = strip(m.init_cache(nb, max_len,
                               src_len=src if cfg.is_encdec else 0))
    rng = np.random.default_rng(7)
    cache = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, cache)
    pos = np.array([5, 13, max_len - 1, max_len + 6], np.int32)
    cache["pos"] = jnp.asarray(pos)
    if cfg.is_encdec:
        cache["src_len"] = jnp.asarray([src, 3, 7, src], jnp.int32)
    bound = max_len if kv_bound is None else kv_bound
    live = jnp.asarray((pos + 1 <= bound) & (np.arange(nb) != 3))
    tokens = jnp.asarray([[3], [11], [7], [1]], jnp.int32)

    def run():
        step = functools.partial(m.decode_step,
                                 use_kernels=kv_bound is not None,
                                 kv_bound=kv_bound)
        # no excess precision: XLA may otherwise skip a bf16 rounding in one
        # program's fusions and not in the other's
        fn = jax.jit(lambda p, c, t, lv: step(p, c, t, live_mask=lv))
        exe = fn.lower(params, cache, tokens, live).compile(
            compiler_options={"xla_allow_excess_precision": False})
        return jax.block_until_ready(exe(params, cache, tokens, live))

    got_logits, got_cache = run()
    monkeypatch.setattr(T, "decoder_step", _per_layer_decoder_step)
    ref_logits, ref_cache = run()
    np.testing.assert_array_equal(_bits(got_logits), _bits(ref_logits))
    assert jax.tree.structure(got_cache) == jax.tree.structure(ref_cache)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(got_cache),
                            jax.tree.leaves(ref_cache)):
        assert g.shape == r.shape and g.dtype == r.dtype, path
        np.testing.assert_array_equal(_bits(g), _bits(r),
                                      err_msg=jax.tree_util.keystr(path))


# ---------------------------------------------------------------------------
# the serving engines hold Q/K/V weights heads-major (Model.heads_major); the
# model reads either layout and computes the same bits
# ---------------------------------------------------------------------------

def _qkv_leaves(tree):
    from repro.distribution import Annotated
    from repro.models.attention import QKV
    return {jax.tree_util.keystr(path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(
                tree, is_leaf=lambda x: isinstance(x, Annotated))
            if getattr(path[-1], "key", None) in QKV}


@pytest.mark.parametrize("arch", ["minitron-4b", "hymba-1.5b",
                                  "seamless-m4t-medium", "qwen2.5-32b",
                                  "chameleon-34b"])
def test_heads_major_params_serve_the_same_bits(arch):
    """Dense GQA, sliding window + SSM (hybrid), enc-dec cross attention,
    QKV bias and QK norm: prefill then decode with the heads-major tree
    gives the logits of every step and every cache leaf of the Model.init
    tree, bit for bit.  Each Q/K/V leaf is the same values with its
    (d, H, D) axes moved to (H, d, D), its logical axes with them; every
    other leaf is untouched, and the conversion of an abstract tree, or of
    a converted one, agrees."""
    cfg = get_reduced(arch)
    m = build_model(cfg)
    ann = m.init(jax.random.key(0))
    hm_ann = m.heads_major(ann)
    qkv, hm_qkv = _qkv_leaves(ann), _qkv_leaves(hm_ann)
    assert qkv.keys() == hm_qkv.keys()
    n_cross = sum("cross" in k for k in qkv)
    assert len(qkv) >= 3 and (n_cross == 3) == cfg.is_encdec, sorted(qkv)
    for k, leaf in qkv.items():
        got = hm_qkv[k]
        assert leaf.value.shape[-3] == cfg.d_model, k
        np.testing.assert_array_equal(
            np.asarray(got.value), np.swapaxes(np.asarray(leaf.value), -3, -2))
        *lead, d, h, last = leaf.logical
        assert d == "embed" and h in ("heads", "kv_heads"), (k, leaf.logical)
        assert tuple(got.logical) == (*lead, h, d, last), (k, got.logical)
    rest = [l for l in jax.tree.leaves(ann) if all(
        l is not q.value for q in qkv.values())]
    hm_rest = [l for l in jax.tree.leaves(hm_ann) if all(
        l is not q.value for q in hm_qkv.values())]
    assert len(rest) == len(hm_rest)
    assert all(a is b for a, b in zip(rest, hm_rest))
    abstract = m.heads_major(jax.eval_shape(m.init, jax.random.key(0)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), strip(abstract)) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), strip(hm_ann))
    again = m.heads_major(hm_ann)
    assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                      jax.tree.leaves(hm_ann)))

    batch = _batch(cfg)
    tokens, k = batch["tokens"], S // 2
    sl = S if cfg.is_encdec else 0

    def serve(params):
        cache = strip(m.init_cache(B, 2 * S, src_len=sl))
        logits, cache = m.prefill(params, {**batch, "tokens": tokens[:, :k]},
                                  cache)
        steps = [logits]
        for i in range(k, S):
            logits, cache = m.decode_step(params, cache, tokens[:, i:i + 1])
            steps.append(logits)
        return jnp.stack(steps), cache

    def run(params):
        exe = jax.jit(serve).lower(params).compile(
            compiler_options={"xla_allow_excess_precision": False})
        return jax.block_until_ready(exe(params))

    ref_logits, ref_cache = run(strip(ann))
    got_logits, got_cache = run(strip(hm_ann))
    np.testing.assert_array_equal(_bits(got_logits), _bits(ref_logits))
    assert jax.tree.structure(got_cache) == jax.tree.structure(ref_cache)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(got_cache),
                            jax.tree.leaves(ref_cache)):
        np.testing.assert_array_equal(_bits(g), _bits(r),
                                      err_msg=jax.tree_util.keystr(path))
