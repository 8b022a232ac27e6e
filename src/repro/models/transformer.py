"""Transformer stacks for the full architecture zoo.

One scanned layer body covers dense / MoE / SSM / hybrid / VLM decoders;
non-uniform layers (DeepSeek's first-k-dense, with a *different* FFN width)
live in an unscanned prologue so the scanned pytree stays stackable.
Per-layer behavioural differences with identical shapes (Hymba's 3 global-
attention layers) ride through the scan as boolean flag arrays.

All stacks scan over layers (bounded HLO, fast compile for 88-layer models)
and optionally remat the layer body (cfg.remat).

Caches are stacked (L, ...) pytrees.  Prefill threads them through the
scan as xs/ys; the decode step carries the stack and writes one row per
slot per layer into it.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distribution.partitioning import Annotated
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

PyTree = Any


# ---------------------------------------------------------------------------
# per-layer init / fwd
# ---------------------------------------------------------------------------

def _layer_init(rng, cfg: ModelConfig, *, dense_override_ff: int = 0,
                cross: bool = False):
    """One decoder layer.  dense_override_ff > 0 -> dense FFN of that width
    (prologue layers).  cross -> add cross-attention (enc-dec decoder)."""
    ks = jax.random.split(rng, 8)
    p: Dict[str, PyTree] = {"ln1": L.norm_init(cfg.norm, cfg.d_model)}
    if cfg.hybrid_parallel:
        p["attn"] = A.gqa_init(ks[0], cfg)
        p["ssm"] = S.mamba_init(ks[1], cfg)
        p["attn_out_norm"] = L.norm_init("rmsnorm", cfg.d_model)
        p["ssm_out_norm"] = L.norm_init("rmsnorm", cfg.d_model)
    elif cfg.ssm is not None:
        p["ssm"] = S.mamba_init(ks[1], cfg)
    elif cfg.mla is not None:
        p["attn"] = A.mla_init(ks[0], cfg)
    else:
        p["attn"] = A.gqa_init(ks[0], cfg)
    if cross:
        p["ln_cross"] = L.norm_init(cfg.norm, cfg.d_model)
        p["cross"] = A.cross_init(ks[2], cfg)
    # FFN / MoE
    if dense_override_ff:
        p["ln2"] = L.norm_init(cfg.norm, cfg.d_model)
        p["ffn"] = M.ffn_init(ks[3], cfg, dense_override_ff)
    elif cfg.moe is not None:
        p["ln2"] = L.norm_init(cfg.norm, cfg.d_model)
        p["moe"] = M.moe_init(ks[3], cfg)
    elif cfg.d_ff:
        p["ln2"] = L.norm_init(cfg.norm, cfg.d_model)
        p["ffn"] = M.ffn_init(ks[3], cfg, cfg.d_ff)
    return p


def _mixer_fwd(p, cfg: ModelConfig, h, positions, is_global, attn_impl,
               causal=True, ssm_impl="chunked", attn_block=512, kv_len=None):
    """The sequence mixer (attention / ssm / hybrid) on normed input h.
    kv_len: optional per-row valid lengths (right-padded bidirectional
    stacks mask their own key padding; see ``gqa_fwd``)."""
    if cfg.hybrid_parallel:
        a = A.gqa_fwd(p["attn"], cfg, h, positions, causal=causal,
                      is_global=is_global, attn_impl=attn_impl,
                      block_size=attn_block, kv_len=kv_len)
        s = S.mamba_fwd(p["ssm"], cfg, h, impl=ssm_impl)
        a = L.apply_norm("rmsnorm", p["attn_out_norm"], a, cfg.norm_eps)
        s = L.apply_norm("rmsnorm", p["ssm_out_norm"], s, cfg.norm_eps)
        return 0.5 * (a + s)
    if cfg.ssm is not None:
        return S.mamba_fwd(p["ssm"], cfg, h, impl=ssm_impl)
    if cfg.mla is not None:
        return A.mla_fwd(p["attn"], cfg, h, positions, attn_impl=attn_impl,
                         block_size=attn_block)
    return A.gqa_fwd(p["attn"], cfg, h, positions, causal=causal,
                     is_global=is_global, attn_impl=attn_impl,
                     block_size=attn_block, kv_len=kv_len)


def _layer_fwd(p, cfg: ModelConfig, x, positions, *, is_global=None,
               attn_impl="blockwise", enc_out=None, enc_positions=None,
               causal=True, moe_dispatch="einsum", ssm_impl="chunked",
               attn_block=512, kv_len=None):
    """Residual layer. Returns (x, aux_loss)."""
    h = L.apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    x = x + _mixer_fwd(p, cfg, h, positions, is_global, attn_impl, causal,
                       ssm_impl=ssm_impl, attn_block=attn_block,
                       kv_len=kv_len)
    if "cross" in p:
        hc = L.apply_norm(cfg.norm, p["ln_cross"], x, cfg.norm_eps)
        x = x + A.cross_fwd(p["cross"], cfg, hc, enc_out, enc_positions)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        h2 = L.apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
        y, aux = M.moe_apply(p["moe"], cfg, h2, dispatch_impl=moe_dispatch)
        x = x + y
    elif "ffn" in p:
        h2 = L.apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
        x = x + M.ffn_apply(p["ffn"], cfg, h2)
    return x, aux


# ---------------------------------------------------------------------------
# caches per layer
# ---------------------------------------------------------------------------

def _layer_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype, *,
                      cross_src: int = 0):
    c: Dict[str, PyTree] = {}
    if cfg.hybrid_parallel:
        c["attn"] = A.gqa_cache_init(cfg, batch, max_len, dtype)
        c["ssm"] = S.mamba_cache_init(cfg, batch, dtype)
    elif cfg.ssm is not None:
        c["ssm"] = S.mamba_cache_init(cfg, batch, dtype)
    elif cfg.mla is not None:
        c["attn"] = A.mla_cache_init(cfg, batch, max_len, dtype)
    else:
        c["attn"] = A.gqa_cache_init(cfg, batch, max_len, dtype)
    if cross_src:
        hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        c["cross_k"] = Annotated(jnp.zeros((batch, cross_src, hkv, hd), dtype),
                                 ("batch", None, "kv_heads", None))
        c["cross_v"] = Annotated(jnp.zeros((batch, cross_src, hkv, hd), dtype),
                                 ("batch", None, "kv_heads", None))
    return c


def _layer_prefill(p, cfg, x, positions, cache, *, is_global=None,
                   attn_impl="blockwise", enc_out=None, enc_positions=None,
                   src_len=None, moe_dispatch="einsum", attn_block=512):
    h = L.apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    new_cache = dict(cache)
    if cfg.hybrid_parallel:
        a, new_cache["attn"] = A.gqa_prefill(p["attn"], cfg, h, positions,
                                             cache["attn"], is_global=is_global,
                                             attn_impl=attn_impl,
                                             block_size=attn_block)
        s, new_cache["ssm"] = S.mamba_prefill(p["ssm"], cfg, h, cache["ssm"])
        a = L.apply_norm("rmsnorm", p["attn_out_norm"], a, cfg.norm_eps)
        s = L.apply_norm("rmsnorm", p["ssm_out_norm"], s, cfg.norm_eps)
        x = x + 0.5 * (a + s)
    elif cfg.ssm is not None:
        y, new_cache["ssm"] = S.mamba_prefill(p["ssm"], cfg, h, cache["ssm"])
        x = x + y
    elif cfg.mla is not None:
        y, new_cache["attn"] = A.mla_prefill(p["attn"], cfg, h, positions,
                                             cache["attn"], attn_impl=attn_impl,
                                             block_size=attn_block)
        x = x + y
    else:
        y, new_cache["attn"] = A.gqa_prefill(p["attn"], cfg, h, positions,
                                             cache["attn"], is_global=is_global,
                                             attn_impl=attn_impl,
                                             block_size=attn_block)
        x = x + y
    if "cross" in p:
        hc = L.apply_norm(cfg.norm, p["ln_cross"], x, cfg.norm_eps)
        x = x + A.cross_fwd(p["cross"], cfg, hc, enc_out, enc_positions,
                            src_len=src_len)
        ck, cv = A.cross_kv(p["cross"], cfg, enc_out)
        new_cache["cross_k"] = ck.astype(cache["cross_k"].dtype)
        new_cache["cross_v"] = cv.astype(cache["cross_v"].dtype)
    if "moe" in p:
        h2 = L.apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
        y, _ = M.moe_apply(p["moe"], cfg, h2, dispatch_impl=moe_dispatch)
        x = x + y
    elif "ffn" in p:
        h2 = L.apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
        x = x + M.ffn_apply(p["ffn"], cfg, h2)
    return x, new_cache


def _layer_step(p, cfg, x1, cache, pos, *, is_global=None, src_len=None,
                moe_dispatch="einsum", use_kernels=False, kv_bound=None,
                src_bound=None, live=None, layer=None):
    """One decoder layer on one token.  cache: this layer's cache, or with
    ``layer`` (an int32 index) the whole layer stack: attention writes its
    new rows into layer ``layer`` where the stack lies, the SSM's state is
    replaced in that layer, cross-attention KV is only read, and the stack
    is returned."""
    h = L.apply_norm(cfg.norm, p["ln1"], x1, cfg.norm_eps)
    new_cache = dict(cache)

    def ssm(h):
        y, st = S.mamba_step(p["ssm"], cfg, h,
                             jax.tree.map(lambda a: L.layer_of(a, layer),
                                          cache["ssm"]),
                             use_kernels=use_kernels, live=live)
        if layer is not None:
            st = jax.tree.map(
                lambda a, n: jax.lax.dynamic_update_index_in_dim(
                    a, n.astype(a.dtype), layer, 0), cache["ssm"], st)
        return y, st

    if cfg.hybrid_parallel:
        a, new_cache["attn"] = A.gqa_step(p["attn"], cfg, h, cache["attn"],
                                          pos, is_global=is_global,
                                          use_kernels=use_kernels,
                                          kv_bound=kv_bound, live=live,
                                          layer=layer)
        s, new_cache["ssm"] = ssm(h)
        a = L.apply_norm("rmsnorm", p["attn_out_norm"], a, cfg.norm_eps)
        s = L.apply_norm("rmsnorm", p["ssm_out_norm"], s, cfg.norm_eps)
        x1 = x1 + 0.5 * (a + s)
    elif cfg.ssm is not None:
        y, new_cache["ssm"] = ssm(h)
        x1 = x1 + y
    elif cfg.mla is not None:
        y, new_cache["attn"] = A.mla_step(p["attn"], cfg, h, cache["attn"],
                                          pos, use_kernels=use_kernels,
                                          kv_bound=kv_bound, layer=layer)
        x1 = x1 + y
    else:
        y, new_cache["attn"] = A.gqa_step(p["attn"], cfg, h, cache["attn"],
                                          pos, is_global=is_global,
                                          use_kernels=use_kernels,
                                          kv_bound=kv_bound, live=live,
                                          layer=layer)
        x1 = x1 + y
    if "cross" in p:
        hc = L.apply_norm(cfg.norm, p["ln_cross"], x1, cfg.norm_eps)
        x1 = x1 + A.cross_step(p["cross"], cfg, hc,
                               L.layer_of(cache["cross_k"], layer),
                               L.layer_of(cache["cross_v"], layer), src_len,
                               use_kernels=use_kernels, src_bound=src_bound,
                               live=live)
    if "moe" in p:
        h2 = L.apply_norm(cfg.norm, p["ln2"], x1, cfg.norm_eps)
        y, _ = M.moe_apply(p["moe"], cfg, h2, dispatch_impl=moe_dispatch)
        x1 = x1 + y
    elif "ffn" in p:
        h2 = L.apply_norm(cfg.norm, p["ln2"], x1, cfg.norm_eps)
        x1 = x1 + M.ffn_apply(p["ffn"], cfg, h2)
    return x1, new_cache


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------

def _prologue_plan(cfg: ModelConfig) -> Tuple[int, int]:
    """(num_prologue, num_scanned)."""
    k = cfg.moe.first_k_dense if cfg.moe is not None else 0
    return k, cfg.num_layers - k


def _global_flags(cfg: ModelConfig, start: int, count: int):
    flags = [li in cfg.global_attn_layers for li in range(start, start + count)]
    return jnp.asarray(flags)


def decoder_init(rng, cfg: ModelConfig, *, cross: bool = False):
    n_pro, n_scan = _prologue_plan(cfg)
    ks = jax.random.split(rng, cfg.num_layers)
    prologue = [
        _layer_init(ks[i], cfg, cross=cross,
                    dense_override_ff=cfg.moe.first_dense_d_ff if cfg.moe else 0)
        for i in range(n_pro)
    ]
    # one vmapped init (the same values as a per-layer loop + stack) keeps
    # the init program's size flat in depth: it compiles in seconds, not
    # minutes, at 32+ layers
    scanned = jax.vmap(lambda k: _layer_init(k, cfg, cross=cross))(
        ks[n_pro:])
    # annotate stacked leaves with the leading layer axis
    scanned = jax.tree.map(
        lambda a: Annotated(a.value, ("layers",) + tuple(a.logical)),
        scanned, is_leaf=lambda x: isinstance(x, Annotated))
    return {"prologue": prologue, "scanned": scanned}


def _constrain(x, spec):
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def decoder_fwd(params, cfg: ModelConfig, x, positions, *,
                attn_impl="blockwise", enc_out=None, enc_positions=None,
                causal=True, moe_dispatch="einsum", residual_spec=None,
                ssm_impl="chunked", attn_block=512):
    """Full-sequence decoder pass. Returns (x, total_aux).

    residual_spec: optional PartitionSpec pinned onto the residual stream at
    every layer boundary (sequence parallelism: the remat-saved per-layer
    residuals shard over the model axis; DESIGN.md §6).
    """
    n_pro, n_scan = _prologue_plan(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    x = _constrain(x, residual_spec)
    for i, lp in enumerate(params["prologue"]):
        x, aux = _layer_fwd(lp, cfg, x, positions,
                            is_global=jnp.asarray(i in cfg.global_attn_layers),
                            attn_impl=attn_impl, enc_out=enc_out,
                            enc_positions=enc_positions, causal=causal,
                            moe_dispatch=moe_dispatch, ssm_impl=ssm_impl,
                            attn_block=attn_block)
        x = _constrain(x, residual_spec)
        aux_total = aux_total + aux

    flags = _global_flags(cfg, n_pro, n_scan)

    def body(carry, xs):
        h = carry
        lp, is_global = xs
        h, aux = _layer_fwd(lp, cfg, h, positions, is_global=is_global,
                            attn_impl=attn_impl, enc_out=enc_out,
                            enc_positions=enc_positions, causal=causal,
                            moe_dispatch=moe_dispatch, ssm_impl=ssm_impl,
                            attn_block=attn_block)
        return _constrain(h, residual_spec), aux

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    x, auxs = jax.lax.scan(body, x, (params["scanned"], flags))
    return x, aux_total + jnp.sum(auxs)


def decoder_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype, *,
                       cross_src: int = 0):
    n_pro, n_scan = _prologue_plan(cfg)
    pro = [_layer_cache_init(cfg, batch, max_len, dtype, cross_src=cross_src)
           for _ in range(n_pro)]
    one = _layer_cache_init(cfg, batch, max_len, dtype, cross_src=cross_src)
    scanned = jax.tree.map(
        lambda a: Annotated(
            jnp.zeros((n_scan,) + a.value.shape, a.value.dtype),
            ("layers",) + tuple(a.logical)),
        one, is_leaf=lambda x: isinstance(x, Annotated))
    # per-row positions: slots at different depths (continuous batching)
    return {"prologue": pro, "scanned": scanned,
            "pos": Annotated(jnp.zeros((batch,), jnp.int32), ("batch",))}


def cache_slot_axes(cache) -> PyTree:
    """Explicit batch-slot axis index per cache leaf, -1 for leaves without
    one (scalar bookkeeping).

    Scanned stacks carry the layer axis leading, so their slot axis is 1;
    every other leaf (prologue layers, per-row ``pos`` and ``src_len``,
    cross-attention KV) is slot-leading.  Serving code writes single-request prefill results into
    the pooled cache along these axes — positional, never inferred from shape
    mismatch, so a 1-slot pool updates exactly like an N-slot one.
    """
    def axis(path, leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim == 0:
            return -1
        head = path[0]
        name = getattr(head, "key", None)
        return 1 if name == "scanned" else 0

    return jax.tree_util.tree_map_with_path(axis, cache)


def decoder_prefill(params, cfg: ModelConfig, x, positions, cache, *,
                    attn_impl="blockwise", enc_out=None, enc_positions=None,
                    src_len=None, moe_dispatch="einsum", residual_spec=None,
                    true_len=None, attn_block=512):
    """src_len: optional valid source lengths for the cross-attention mask
    when ``enc_out`` is right-padded (serving's bucketed encode programs);
    None attends the full encoder output (training, exact lengths)."""
    n_pro, n_scan = _prologue_plan(cfg)
    new_pro = []
    x = _constrain(x, residual_spec)
    for i, (lp, lc) in enumerate(zip(params["prologue"], cache["prologue"])):
        x, nc = _layer_prefill(lp, cfg, x, positions, lc,
                               is_global=jnp.asarray(i in cfg.global_attn_layers),
                               attn_impl=attn_impl, enc_out=enc_out,
                               enc_positions=enc_positions, src_len=src_len,
                               moe_dispatch=moe_dispatch,
                               attn_block=attn_block)
        x = _constrain(x, residual_spec)
        new_pro.append(nc)
    flags = _global_flags(cfg, n_pro, n_scan)

    def body(h, xs):
        lp, lc, is_global = xs
        h, nc = _layer_prefill(lp, cfg, h, positions, lc, is_global=is_global,
                               attn_impl=attn_impl, enc_out=enc_out,
                               enc_positions=enc_positions, src_len=src_len,
                               moe_dispatch=moe_dispatch,
                               attn_block=attn_block)
        return _constrain(h, residual_spec), nc

    x, new_scanned = jax.lax.scan(body, x, (params["scanned"],
                                            cache["scanned"], flags))
    if true_len is None:
        pos = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    else:
        pos = jnp.broadcast_to(jnp.asarray(true_len, jnp.int32),
                               (x.shape[0],))
    new_cache = {"prologue": new_pro, "scanned": new_scanned, "pos": pos}
    return x, new_cache


def decoder_step(params, cfg: ModelConfig, x1, cache, *, src_len=None,
                 moe_dispatch="einsum", use_kernels=False, kv_bound=None,
                 src_bound=None, live=None):
    """use_kernels/kv_bound/src_bound/live: ragged decode hot path — the
    serving engine threads a static KV bound covering every live row and a
    per-row live mask; attention reads only the bounded prefix (bit-identical
    for live rows) and kernels skip dead slots entirely."""
    n_pro, n_scan = _prologue_plan(cfg)
    pos = cache["pos"]
    new_pro = []
    for i, (lp, lc) in enumerate(zip(params["prologue"], cache["prologue"])):
        x1, nc = _layer_step(lp, cfg, x1, lc, pos,
                             is_global=jnp.asarray(i in cfg.global_attn_layers),
                             src_len=src_len, moe_dispatch=moe_dispatch,
                             use_kernels=use_kernels, kv_bound=kv_bound,
                             src_bound=src_bound, live=live)
        new_pro.append(nc)
    flags = _global_flags(cfg, n_pro, n_scan)

    # the stacked cache rides in the carry, not in xs/ys: each layer writes
    # only its new rows into the stack where it lies (XLA slices an xs/ys
    # cache out, restacks it whole and copies it back, every step)
    def body(carry, xs):
        h, stack = carry
        lp, is_global, layer = xs
        h, stack = _layer_step(lp, cfg, h, stack, pos, is_global=is_global,
                               src_len=src_len, moe_dispatch=moe_dispatch,
                               use_kernels=use_kernels, kv_bound=kv_bound,
                               src_bound=src_bound, live=live, layer=layer)
        return (h, stack), None

    (x1, new_scanned), _ = jax.lax.scan(
        body, (x1, cache["scanned"]),
        (params["scanned"], flags, jnp.arange(n_scan, dtype=jnp.int32)))
    new_cache = {"prologue": new_pro, "scanned": new_scanned, "pos": pos + 1}
    return x1, new_cache


# ---------------------------------------------------------------------------
# encoder (bidirectional, for enc-dec)
# ---------------------------------------------------------------------------

def encoder_init(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, cfg.encoder_layers)
    scanned = jax.vmap(lambda k: _layer_init(k, cfg))(ks)
    scanned = jax.tree.map(
        lambda a: Annotated(a.value, ("layers",) + tuple(a.logical)),
        scanned, is_leaf=lambda x: isinstance(x, Annotated))
    return {"scanned": scanned, "final_norm": L.norm_init(cfg.norm, cfg.d_model)}


def encoder_fwd(params, cfg: ModelConfig, x, positions, *,
                attn_impl="blockwise", kv_len=None):
    """Bidirectional encoder stack.  kv_len: optional per-row (B,) valid
    source lengths — when the batch is right-padded (serving's bucketed
    encode programs), each row's attention masks its own key padding, making
    the valid rows of the output independent of the padded program shape
    (bucket-invariant encodes).  None keeps the unmasked exact-length path
    (training)."""
    def body(h, lp):
        h, _ = _layer_fwd(lp, cfg, h, positions, causal=False,
                          attn_impl=attn_impl, kv_len=kv_len)
        return h, None

    if cfg.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["scanned"])
    return L.apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def chunked_softmax_xent(x, w_head, labels, mask, *, chunk: int = 512,
                         logit_softcap: float = 0.0):
    """Cross-entropy over huge vocabularies without materializing (B,S,V).

    x: (B,S,d); w_head: (d,V); labels,mask: (B,S).  lax.scan over sequence
    chunks; per chunk only (B,chunk,V) logits exist.
    """
    B, S, d = x.shape
    nchunk = -(-S // chunk)
    pad = nchunk * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    xc = x.reshape(B, nchunk, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, nchunk, chunk).transpose(1, 0, 2)
    mc = mask.reshape(B, nchunk, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        tot, cnt = carry
        xb, lb, mb = xs
        logits = jnp.einsum("bcd,dv->bcv", xb, w_head.astype(xb.dtype))
        logits = logits.astype(jnp.float32)
        if logit_softcap > 0.0:
            logits = logit_softcap * jnp.tanh(logits / logit_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        # gold logit via one-hot contraction, NOT take_along_axis: a gather
        # on the vocab dim defeats the vocab sharding and makes XLA
        # replicate full-vocab fp32 logits in the backward (4.2 GiB/device
        # per chunk for a 256k vocab).  The one-hot einsum partitions.
        oh = jax.nn.one_hot(lb, logits.shape[-1], dtype=logits.dtype)
        gold = jnp.sum(logits * oh, axis=-1)
        nll = (lse - gold) * mb
        return (tot + jnp.sum(nll), cnt + jnp.sum(mb)), None

    # checkpoint: the backward recomputes the (B,chunk,V) logits per chunk
    # instead of saving them (33 GiB/device for a 256k vocab otherwise).
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)
