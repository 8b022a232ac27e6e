"""Attention blocks: GQA/MQA (with QKV bias, QK-norm, sliding window) and MLA.

Each block exposes:
  init(rng, cfg)                              -> params (Annotated pytree)
  fwd(params, cfg, x, positions, ...)         -> y           (train/prefill)
  fwd_cached(params, cfg, x, cache, ...)      -> y, cache    (prefill w/ cache)
  step(params, cfg, x1, cache, ...)           -> y1, cache   (decode)

Cache layout (per layer): {"k": (B,T,Hkv,D), "v": (B,T,Hkv,D)} annotated with
kv_seq on the T dim so serving rules shard it over the model axis (split-K
decode).  MLA caches the *compressed* latent instead: {"ckv": (B,T,R),
"krope": (B,T,Dr)} — 1.7 MB/token -> 36 KB/token for deepseek-v2-lite.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distribution.partitioning import Annotated
from repro.kernels.ragged_decode import ragged_decode_attention
from repro.models import layers as L


# ---------------------------------------------------------------------------
# standard GQA attention
# ---------------------------------------------------------------------------

def gqa_init(rng, cfg: ModelConfig):
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(rng, 8)
    p = {
        "wq": L.dense_init(ks[0], d, (hq, hd), ("embed", "heads", None)),
        "wk": L.dense_init(ks[1], d, (hkv, hd), ("embed", "kv_heads", None)),
        "wv": L.dense_init(ks[2], d, (hkv, hd), ("embed", "kv_heads", None)),
        "wo": L.dense_init(ks[3], hq * hd, d, ("heads_flat", "embed")),
    }
    # wo is stored flat (Hq*hd, d) and reshaped at use; annotate the flat dim
    p["wo"] = Annotated(p["wo"].value.reshape(hq, hd, d), ("heads", None, "embed"))
    if cfg.qkv_bias:
        p["bq"] = L.bias_init((hq, hd), ("heads", None))
        p["bk"] = L.bias_init((hkv, hd), ("kv_heads", None))
        p["bv"] = L.bias_init((hkv, hd), ("kv_heads", None))
    if cfg.qk_norm:
        p["q_norm"] = L.scale_init(hd, (None,))
        p["k_norm"] = L.scale_init(hd, (None,))
    return p


QKV = ("wq", "wk", "wv")


def project_heads(x, w):
    """x (B, S, d) times a Q/K/V weight cast to x's dtype -> (B, S, H, D).

    The weight is (..., d, H, D) as ``gqa_init`` draws it, or heads-major
    (..., H, d, D) as the serving engines hold it (``heads_major``); its
    shape tells which.  The TPU compiler lowers this projection as a
    convolution windowed over heads that reads the weight heads-major: a
    (d, H, D) layer of the stacked params is staged into VMEM and relaid
    out before its dot, a heads-major one streams from HBM into it."""
    spec = "bsd,dhk->bshk" if w.shape[-3] == x.shape[-1] else "bsd,hdk->bshk"
    return jnp.einsum(spec, x, w.astype(x.dtype))


_swap_heads = jax.jit(lambda a: jnp.swapaxes(a, -3, -2))


def heads_major(params, d_model: int):
    """``params`` (as ``Model.init`` returns it: Annotated or stripped,
    concrete or abstract) with every GQA self- and cross-attention
    ``wq``/``wk``/``wv`` moved from (..., d, H, D) to (..., H, d, D), its
    logical axes permuted with it, so sharding plans keep the heads on the
    model axis.  One leaf at a time; leaves already heads-major and every
    other leaf (MLA, ``wo``, biases, norms) come back as they are."""
    def leaf(path, x):
        if getattr(path[-1], "key", None) not in QKV:
            return x
        v = x.value if isinstance(x, Annotated) else x
        if v.shape[-3] != d_model:
            return x
        v = (jax.eval_shape(_swap_heads, v)
             if isinstance(v, jax.ShapeDtypeStruct) else _swap_heads(v))
        if not isinstance(x, Annotated):
            return v
        *lead, a, b, last = x.logical
        return Annotated(v, (*lead, b, a, last))

    return jax.tree_util.tree_map_with_path(
        leaf, params, is_leaf=lambda x: isinstance(x, Annotated))


def _project_qkv(p, cfg: ModelConfig, x, positions):
    q = project_heads(x, p["wq"])
    k = project_heads(x, p["wk"])
    v = project_heads(x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_fwd(p, cfg: ModelConfig, x, positions, *, causal=True, is_global=None,
            attn_impl: str = "blockwise", block_size: int = 512,
            kv_len=None):
    """Full-sequence attention (train / encoder).  is_global: scalar bool for
    hybrid stacks whose scanned body switches window on/off per layer.
    kv_len: optional per-row (B,) valid lengths — a bidirectional stack over
    right-padded rows masks each row's own key padding so outputs are
    independent of the padded program shape (bucket-invariant encodes)."""
    q, k, v = _project_qkv(p, cfg, x, positions)
    window = cfg.window_size if cfg.attn_type == "sliding" else 0
    if attn_impl == "triangular" and causal and kv_len is None:
        o = L.triangular_attention(q, k, v, window=window,
                                   block_size=block_size, is_global=is_global,
                                   logit_cap=cfg.logit_softcap)
    else:
        o = L.blockwise_attention(q, k, v, causal=causal, window=window,
                                  block_size=block_size, is_global=is_global,
                                  logit_cap=cfg.logit_softcap, kv_len=kv_len)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))


def gqa_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype):
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": Annotated(jnp.zeros((batch, max_len, hkv, hd), dtype),
                       ("batch", "kv_seq", "kv_heads", None)),
        "v": Annotated(jnp.zeros((batch, max_len, hkv, hd), dtype),
                       ("batch", "kv_seq", "kv_heads", None)),
    }


def gqa_prefill(p, cfg: ModelConfig, x, positions, cache, *, is_global=None,
                attn_impl: str = "blockwise", block_size: int = 512):
    """Prefill: run causal attention and write K/V into the cache at [0, S)."""
    q, k, v = _project_qkv(p, cfg, x, positions)
    window = cfg.window_size if cfg.attn_type == "sliding" else 0
    if attn_impl == "triangular":
        o = L.triangular_attention(q, k, v, window=window, is_global=is_global,
                                   logit_cap=cfg.logit_softcap,
                                   block_size=block_size)
    else:
        o = L.blockwise_attention(q, k, v, causal=True, window=window,
                                  is_global=is_global,
                                  logit_cap=cfg.logit_softcap,
                                  block_size=block_size)
    new_cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, 0, 0, 0)),
    }
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype)), new_cache


def gqa_step(p, cfg: ModelConfig, x1, cache, pos, *, is_global=None,
             use_kernels=False, kv_bound=None, live=None, layer=None):
    """Decode one token.  x1: (B, 1, d); pos: int32 (B,) per-row positions
    (continuous batching) or scalar.

    use_kernels selects the ragged decode-attention path: the KV read is
    bounded to ``kv_bound`` rows (a static bound >= every live row's
    ``pos + 1``, threaded by the engine) and ``live`` marks empty slots.
    Live rows stay bit-identical to the padded read; the full-size cache is
    still written so retunes/migrations see the same state either way.

    layer: with an index, ``cache`` holds the whole (L, B, T, Hkv, D) layer
    stack; the new row of every slot is written into layer ``layer`` and
    that layer is read back, and the stack is returned (see ``scatter_kv``)."""
    B = x1.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos[:, None]
    q, k, v = _project_qkv(p, cfg, x1, positions)
    ck = L.scatter_kv(cache["k"], k, pos, layer)
    cv = L.scatter_kv(cache["v"], v, pos, layer)
    window = cfg.window_size if cfg.attn_type == "sliding" else 0
    if use_kernels:
        o = ragged_decode_attention(
            q, L.layer_of(ck, layer, kv_bound),
            L.layer_of(cv, layer, kv_bound), pos + 1, window=window,
            is_global=is_global, logit_cap=cfg.logit_softcap, live=live)
    else:
        o = L.decode_attention(q, L.layer_of(ck, layer),
                               L.layer_of(cv, layer), pos + 1, window=window,
                               is_global=is_global,
                               logit_cap=cfg.logit_softcap)
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x1.dtype))
    return y, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# cross-attention (enc-dec decoder).  KV come from the encoder output; during
# decoding they are precomputed once at prefill.
# ---------------------------------------------------------------------------

def cross_init(rng, cfg: ModelConfig):
    return gqa_init(rng, cfg)


def cross_fwd(p, cfg: ModelConfig, x, enc_out, enc_positions, src_len=None):
    """Cross-attention over encoder outputs (train / prefill).

    src_len: optional int32 scalar or (B,) valid source lengths.  When the
    encoder output is right-padded to a bucketed program shape (serving),
    positions >= src_len are masked out of the softmax so the decoder only
    attends real source frames — the full-sequence counterpart of
    ``cross_step``'s masked ``decode_attention`` read.  None keeps the
    unmasked training path (exact-length encoder outputs).
    """
    q = project_heads(x, p["wq"])
    k = project_heads(enc_out, p["wk"])
    v = project_heads(enc_out, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if src_len is None:
        o = L.blockwise_attention(q, k, v, causal=False)
    else:
        # masked source padding: scores are (B, Hq, Sq, Ssrc) — tiny for the
        # single-token decoder prompts the serving engine prefills
        B, Sq, Hq, D = q.shape
        Ss, Hkv = k.shape[1], k.shape[2]
        groups = Hq // Hkv
        kexp = jnp.repeat(k, groups, axis=2)
        vexp = jnp.repeat(v, groups, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kexp,
                       preferred_element_type=jnp.float32) / math.sqrt(D)
        lens = jnp.broadcast_to(jnp.asarray(src_len, jnp.int32), (B,))
        mask = jnp.arange(Ss)[None, None, None, :] < lens[:, None, None, None]
        s = jnp.where(mask, s, -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(vexp.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, vexp)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))


def cross_kv(p, cfg: ModelConfig, enc_out):
    k = project_heads(enc_out, p["wk"])
    v = project_heads(enc_out, p["wv"])
    if cfg.qkv_bias:
        k = k + p["bk"].astype(enc_out.dtype)
        v = v + p["bv"].astype(enc_out.dtype)
    return k, v


def cross_step(p, cfg: ModelConfig, x1, ck, cv, src_len, *,
               use_kernels=False, src_bound=None, live=None):
    q = project_heads(x1, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x1.dtype)
    if use_kernels:
        # bound the cross-KV read to the batch's true source lengths
        sb = ck.shape[1] if src_bound is None else src_bound
        o = ragged_decode_attention(q, ck[:, :sb], cv[:, :sb], src_len,
                                    live=live)
    else:
        o = L.decode_attention(q, ck, cv, src_len)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x1.dtype))


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434 §2.1).
#
# Projections:
#   c_kv   = x @ W_dkv                      (B,S,R)        latent KV
#   k_rope = rope(x @ W_kr)                 (B,S,Dr)       shared across heads
#   k_nope = c_kv @ W_uk  -> (B,S,H,Dn);  v = c_kv @ W_uv -> (B,S,H,Dv)
#   q      = x @ W_q -> (B,S,H,Dn+Dr)   (lite model: full-rank q)
# Decode caches (c_kv, k_rope) only and uses the *absorbed* form:
#   score = q_nope @ W_uk^T @ c_kv + q_rope @ k_rope
#   out   = (attn @ c_kv) @ W_uv
# ---------------------------------------------------------------------------

def mla_init(rng, cfg: ModelConfig):
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    dn, dr, dv, r = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim, m.kv_lora_rank
    ks = jax.random.split(rng, 8)
    p = {
        "w_dkv": L.dense_init(ks[0], d, r, ("embed", "lora")),
        "w_kr": L.dense_init(ks[1], d, dr, ("embed", None)),
        "w_uk": L.dense_init(ks[2], r, (h, dn), ("lora", "heads", None)),
        "w_uv": L.dense_init(ks[3], r, (h, dv), ("lora", "heads", None)),
        "wo": Annotated(
            L.dense_init(ks[4], h * dv, d, (None, "embed")).value.reshape(h, dv, d),
            ("heads", None, "embed")),
        "kv_norm": L.scale_init(r, (None,)),
    }
    if m.q_lora_rank:
        p["w_dq"] = L.dense_init(ks[5], d, m.q_lora_rank, ("embed", "lora"))
        p["w_uq"] = L.dense_init(ks[6], m.q_lora_rank, (h, dn + dr), ("lora", "heads", None))
        p["q_norm"] = L.scale_init(m.q_lora_rank, (None,))
    else:
        p["w_q"] = L.dense_init(ks[5], d, (h, dn + dr), ("embed", "heads", None))
    return p


def _mla_q(p, cfg, x, positions):
    m = cfg.mla
    dn, dr = m.qk_nope_head_dim, m.qk_rope_head_dim
    if m.q_lora_rank:
        cq = jnp.einsum("bsd,dr->bsr", x, p["w_dq"].astype(x.dtype))
        cq = L.rms_norm(cq, p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"].astype(x.dtype))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"].astype(x.dtype))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latents(p, cfg, x, positions):
    ckv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(x.dtype))
    ckv = L.rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    kr = jnp.einsum("bsd,dk->bsk", x, p["w_kr"].astype(x.dtype))
    kr = L.apply_rope(kr[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return ckv, kr


def mla_fwd(p, cfg: ModelConfig, x, positions, *, attn_impl: str = "blockwise",
            block_size: int = 512):
    """Prefill/train MLA: expand latents to per-head K/V and run flash attn."""
    m = cfg.mla
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    ckv, kr = _mla_latents(p, cfg, x, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uk"].astype(x.dtype))
    v = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uv"].astype(x.dtype))
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        kr[:, :, None, :], (*k_nope.shape[:3], m.qk_rope_head_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    # pad v head dim up to qk dim for the shared flash kernel, slice after.
    dqk = m.qk_nope_head_dim + m.qk_rope_head_dim
    vpad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dqk - m.v_head_dim)))
    if attn_impl == "triangular":
        o = L.triangular_attention(q, k, vpad, block_size=block_size)
    else:
        o = L.blockwise_attention(q, k, vpad, causal=True,
                                  block_size=block_size)
    o = o[..., : m.v_head_dim]
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))


def mla_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype):
    m = cfg.mla
    return {
        "ckv": Annotated(jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
                         ("batch", "kv_seq", None)),
        "krope": Annotated(jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
                           ("batch", "kv_seq", None)),
    }


def mla_prefill(p, cfg: ModelConfig, x, positions, cache, *,
                attn_impl="blockwise", block_size: int = 512):
    ckv, kr = _mla_latents(p, cfg, x, positions)
    y = mla_fwd(p, cfg, x, positions, attn_impl=attn_impl,
                block_size=block_size)
    new_cache = {
        "ckv": jax.lax.dynamic_update_slice(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, 0, 0)),
        "krope": jax.lax.dynamic_update_slice(
            cache["krope"], kr.astype(cache["krope"].dtype), (0, 0, 0)),
    }
    return y, new_cache


def mla_step(p, cfg: ModelConfig, x1, cache, pos, *, use_kernels=False,
             kv_bound=None, layer=None):
    """Absorbed-matmul MLA decode: attends in the R-dim latent space.
    pos: int32 (B,) per-row positions or scalar.  With use_kernels, the
    latent read is bounded to ``kv_bound`` rows (bit-identical: the masked
    softmax ignores the dropped zero-score suffix).  layer: as in
    ``gqa_step``, for stacked (L, B, T, ...) latent caches."""
    m = cfg.mla
    B = x1.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos[:, None]
    q_nope, q_rope = _mla_q(p, cfg, x1, positions)          # (B,1,H,Dn/Dr)
    ckv1, kr1 = _mla_latents(p, cfg, x1, positions)
    cckv = L.scatter_kv(cache["ckv"], ckv1, pos, layer)
    ckr = L.scatter_kv(cache["krope"], kr1, pos, layer)
    rows = kv_bound if use_kernels else None
    att_ckv = L.layer_of(cckv, layer, rows)
    att_kr = L.layer_of(ckr, layer, rows)
    # absorb W_uk into q: (B,H,R)
    q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"].astype(x1.dtype))[:, 0]
    scale = 1.0 / jnp.sqrt(jnp.asarray(m.qk_nope_head_dim + m.qk_rope_head_dim,
                                       jnp.float32))
    s = (jnp.einsum("bhr,btr->bht", q_abs.astype(jnp.float32),
                    att_ckv.astype(jnp.float32))
         + jnp.einsum("bhk,btk->bht", q_rope[:, 0].astype(jnp.float32),
                      att_kr.astype(jnp.float32))) * scale
    mask = jnp.arange(att_ckv.shape[1])[None, None, :] < (pos + 1)[:, None, None]
    s = jnp.where(mask, s, L.NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bht,btr->bhr", w, att_ckv.astype(jnp.float32))  # (B,H,R)
    o = jnp.einsum("bhr,rhk->bhk", o_lat.astype(x1.dtype), p["w_uv"].astype(x1.dtype))
    y = jnp.einsum("bhk,hkd->bd", o, p["wo"].astype(x1.dtype))[:, None]
    return y, {"ckv": cckv, "krope": ckr}
