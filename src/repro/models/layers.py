"""Core neural-net layers: norms, RoPE, dense, activations, blockwise attention.

All layers are functional: ``*_init(rng, ...) -> Annotated param pytree`` and
``*_apply(params, x, ...) -> y``.  Parameters carry logical sharding
annotations (repro.distribution.partitioning.Annotated) consumed by the
launcher when placing them on a mesh.

The attention here is the *portable* (pure-jnp) path: a lax.scan over KV
blocks with running logsumexp — the flash-attention algorithm — so that
``prefill_32k`` never materializes an S x S score matrix and
``memory_analysis()`` stays honest.  The Pallas kernel in
``repro.kernels.flash_attention`` implements the same contract for TPU.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distribution.partitioning import Annotated

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def _normal(rng, shape, std, dtype=jnp.float32):
    return jax.random.normal(rng, shape, dtype) * jnp.asarray(std, dtype)


def dense_init(rng, in_dim: int, out_dim, logical: Tuple, *, std: Optional[float] = None,
               dtype=jnp.float32) -> Annotated:
    """Weight of shape (in_dim, *out_dims) with fan-in scaled init."""
    out_dims = out_dim if isinstance(out_dim, tuple) else (out_dim,)
    std = std if std is not None else 1.0 / math.sqrt(in_dim)
    return Annotated(_normal(rng, (in_dim, *out_dims), std, dtype), logical)


def bias_init(out_dim, logical: Tuple, dtype=jnp.float32) -> Annotated:
    out_dims = out_dim if isinstance(out_dim, tuple) else (out_dim,)
    return Annotated(jnp.zeros(out_dims, dtype), logical)


def scale_init(dim: int, logical: Tuple, value: float = 1.0, dtype=jnp.float32) -> Annotated:
    return Annotated(jnp.full((dim,), value, dtype), logical)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm_init(kind: str, dim: int, dtype=jnp.float32):
    if kind == "rmsnorm":
        return {"scale": scale_init(dim, (None,), 1.0, dtype)}
    return {"scale": scale_init(dim, (None,), 1.0, dtype),
            "bias": bias_init(dim, (None,), dtype)}


def apply_norm(kind: str, params, x, eps: float):
    if kind == "rmsnorm":
        return rms_norm(x, params["scale"], eps)
    return layer_norm(x, params["scale"], params["bias"], eps)


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

def activation(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "relu": jax.nn.relu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    }[name]


# ---------------------------------------------------------------------------
# rotary embedding — computed on the fly from positions (no 500k-entry table).
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., S, H, D) (D even); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                                   # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * inv         # (..., S, D/2)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# blockwise (flash) attention — pure jnp, scan over KV blocks, with a
# custom_vjp backward that saves only (q, k, v, out, lse) and recomputes
# block scores (the flash-attention backward).  Without the custom backward,
# autodiff through the forward scan saves the fp32 (B,Sq,Hq,D) accumulator
# carry at EVERY block step — tens of GiB per layer at 4k+ sequence lengths.
#
# q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D), Hq % Hkv == 0.
# Supports causal masking, sliding window and explicit kv-length masking.
# Double differentiation through attention is unsupported (first-order vjp).
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _gqa_expand(k, groups: int):
    # (B, S, Hkv, D) -> (B, S, Hkv*groups, D) by repeat; done blockwise so the
    # expansion is only ever (block) wide.
    return jnp.repeat(k, groups, axis=2)


def _block_mask(qpos, kpos, valid_len, *, causal, window, is_global):
    """(B|1, Sq, blk) mask shared by the fwd and bwd passes.

    valid_len may be a scalar (one kv length for the whole batch — decode
    with a uniform cache, or Skv itself) or a per-row (B,) vector (serving's
    right-padded batches: each row masks its own key padding, so a job's
    attention never reads another bucket's pad region and encodes are
    bucket-invariant)."""
    vl = jnp.asarray(valid_len)
    if vl.ndim == 0:
        vl = vl[None]                                     # (1,)
    mask = kpos[None, None, :] < vl[:, None, None]        # (B|1, 1, blk)
    if causal:
        mask = mask & (kpos[None, None, :] <= qpos[None, :, None])
    if window:
        w_ok = kpos[None, None, :] > (qpos[None, :, None] - window)
        if is_global is not None:
            w_ok = w_ok | is_global
        mask = mask & w_ok
    return mask


def _flash_fwd_pass(causal, window, block_size, logit_cap, q, k, v, q_offset,
                    valid_len, is_global):
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    groups = Hq // Hkv
    nblk = Skv // block_size
    kb = k.reshape(B, nblk, block_size, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, block_size, Hkv, D).transpose(1, 0, 2, 3, 4)
    # operands stay in the input dtype (bf16 on TPU: half the HBM/ICI bytes);
    # the MXU accumulates in f32 via preferred_element_type — upcasting the
    # operands instead gets the convert hoisted above the SP all-gathers and
    # doubles wire traffic (EXPERIMENTS.md §Perf).
    scale = 1.0 / math.sqrt(D)
    qpos = jnp.arange(Sq) + q_offset

    def body(carry, blk):
        acc, m, l = carry
        kblk, vblk, bidx = blk
        kpos = bidx * block_size + jnp.arange(block_size)
        kexp = _gqa_expand(kblk, groups)
        s = jnp.einsum("bqhd,bkhd->bqhk", q, kexp,
                       preferred_element_type=jnp.float32) * scale
        if logit_cap > 0.0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        mask = _block_mask(qpos, kpos, valid_len, causal=causal,
                           window=window, is_global=is_global)
        s = jnp.where(mask[:, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        resc = jnp.exp(m - m_new)
        vexp = _gqa_expand(vblk, groups)
        acc = acc * resc[..., None] + jnp.einsum(
            "bqhk,bkhd->bqhd", p.astype(v.dtype), vexp,
            preferred_element_type=jnp.float32)
        l = l * resc + jnp.sum(p, axis=-1)
        return (acc, m_new, l), None

    acc0 = jnp.zeros((B, Sq, Hq, D), jnp.float32)
    m0 = jnp.full((B, Sq, Hq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0), (kb, vb, jnp.arange(nblk)))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))               # (B, Sq, Hq)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _flash(causal, window, block_size, logit_cap, q, k, v, q_offset,
           valid_len, is_global):
    out, _ = _flash_fwd_pass(causal, window, block_size, logit_cap, q, k, v,
                             q_offset, valid_len, is_global)
    return out


def _flash_fwd(causal, window, block_size, logit_cap, q, k, v, q_offset,
               valid_len, is_global):
    out, lse = _flash_fwd_pass(causal, window, block_size, logit_cap, q, k, v,
                               q_offset, valid_len, is_global)
    return out, (q, k, v, out, lse, q_offset, valid_len, is_global)


def _flash_bwd(causal, window, block_size, logit_cap, res, dout):
    q, k, v, out, lse, q_offset, valid_len, is_global = res
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    groups = Hq // Hkv
    nblk = Skv // block_size
    scale = 1.0 / math.sqrt(D)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                    # (B,Sq,Hq)
    qpos = jnp.arange(Sq) + q_offset
    kb = k.reshape(B, nblk, block_size, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, block_size, Hkv, D).transpose(1, 0, 2, 3, 4)

    def body(dq, blk):
        kblk, vblk, bidx = blk
        kpos = bidx * block_size + jnp.arange(block_size)
        kexp = _gqa_expand(kblk, groups)
        vexp = _gqa_expand(vblk, groups)
        s_raw = jnp.einsum("bqhd,bkhd->bqhk", q, kexp,
                           preferred_element_type=jnp.float32) * scale
        if logit_cap > 0.0:
            s = logit_cap * jnp.tanh(s_raw / logit_cap)
        else:
            s = s_raw
        mask = _block_mask(qpos, kpos, valid_len, causal=causal,
                           window=window, is_global=is_global)
        s = jnp.where(mask[:, :, None, :], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                       # (B,Sq,Hq,blk)
        pc = p.astype(v.dtype)
        dv_h = jnp.einsum("bqhk,bqhd->bkhd", pc, dout,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqhd,bkhd->bqhk", dout, vexp,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None])
        if logit_cap > 0.0:
            t = jnp.tanh(s_raw / logit_cap)
            ds = ds * (1.0 - jnp.square(t))
        ds = jnp.where(mask[:, :, None, :], ds, 0.0)
        dsc = ds.astype(k.dtype)
        dq = dq + jnp.einsum("bqhk,bkhd->bqhd", dsc, kexp,
                             preferred_element_type=jnp.float32) * scale
        dk_h = jnp.einsum("bqhk,bqhd->bkhd", dsc, q,
                          preferred_element_type=jnp.float32) * scale
        # fold GQA: sum q-head groups back to kv heads
        dk = dk_h.reshape(B, block_size, Hkv, groups, D).sum(3)
        dv = dv_h.reshape(B, block_size, Hkv, groups, D).sum(3)
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, Sq, Hq, D), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, (kb, vb, jnp.arange(nblk)))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, Skv, Hkv, D)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, Skv, Hkv, D)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None, None)


_flash.defvjp(_flash_fwd, _flash_bwd)


def blockwise_attention(
    q, k, v, *,
    causal: bool,
    q_offset=0,
    window: int = 0,
    kv_len=None,
    block_size: int = 512,
    logit_cap: float = 0.0,
    is_global=None,
):
    """Flash-attention algorithm in jnp (memory-efficient fwd AND bwd).

    q_offset: position of q[0] within the kv timeline (prefill: 0; decode:
      cache length).  window: sliding-window size (0 = unlimited).  kv_len:
      optional dynamic valid kv length — a scalar (decode with a
      preallocated cache) or a per-row (B,) vector (right-padded serving
      batches: each row masks its own key padding).
    is_global: optional scalar bool — when True, ignore ``window`` (hybrid
      models with a few global layers inside a scanned stack).
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    block_size = min(block_size, Skv)
    nblk = -(-Skv // block_size)
    pad = nblk * block_size - Skv
    valid_len = jnp.asarray(Skv if kv_len is None else kv_len)
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q_offset = jnp.asarray(q_offset)
    is_global_arr = None if is_global is None else jnp.asarray(is_global)
    return _flash(causal, window, block_size, logit_cap, q, k, v, q_offset,
                  valid_len, is_global_arr)


def triangular_attention(
    q, k, v, *,
    q_offset=0,
    window: int = 0,
    block_size: int = 512,
    logit_cap: float = 0.0,
    is_global=None,
):
    """Causal blockwise attention over the *triangular pair list* — computes
    only (i, j<=i) blocks, eliminating the ~2x masked-FLOP waste of the
    rectangular scan.  Beyond-paper optimization (EXPERIMENTS.md §Perf).

    FORWARD/PREFILL ONLY: differentiating through the pair scan would save
    the full fp32 accumulator per pair step; training uses
    ``blockwise_attention`` (custom_vjp flash backward) instead.
    Requires Sq == Skv (prefill/train) and Sq % block_size == 0.
    """
    B, S, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    assert S == Skv and S % block_size == 0, (S, Skv, block_size)
    groups = Hq // Hkv
    nb = S // block_size
    # static (i, j) pair list, j <= i, ordered by i then j so the running
    # softmax state for q-block i is finalized before i+1 begins.
    pairs = [(i, j) for i in range(nb) for j in range(i + 1)]
    if window:
        wblk = -(-window // block_size)
        if is_global is None:
            pairs = [(i, j) for (i, j) in pairs if i - j <= wblk]
    pi = jnp.asarray([p[0] for p in pairs], jnp.int32)
    pj = jnp.asarray([p[1] for p in pairs], jnp.int32)

    qb = q.reshape(B, nb, block_size, Hq, D)
    kb = k.reshape(B, nb, block_size, Hkv, D)
    vb = v.reshape(B, nb, block_size, Hkv, D)
    scale = 1.0 / math.sqrt(D)

    def body(carry, idx):
        acc, m, l = carry                       # (B, nb, blk, Hq, D/·)
        i, j = idx
        qi = jax.lax.dynamic_index_in_dim(qb, i, 1, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(kb, j, 1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vb, j, 1, keepdims=False)
        qpos = i * block_size + jnp.arange(block_size) + q_offset
        kpos = j * block_size + jnp.arange(block_size) + q_offset
        s = jnp.einsum("bqhd,bkhd->bqhk", qi, _gqa_expand(kj, groups),
                       preferred_element_type=jnp.float32) * scale
        if logit_cap > 0.0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            w_ok = kpos[None, :] > (qpos[:, None] - window)
            if is_global is not None:
                w_ok = w_ok | is_global
            mask = mask & w_ok
        s = jnp.where(mask[None, :, None, :], s, NEG_INF)
        mi = jax.lax.dynamic_index_in_dim(m, i, 1, keepdims=False)
        li = jax.lax.dynamic_index_in_dim(l, i, 1, keepdims=False)
        ai = jax.lax.dynamic_index_in_dim(acc, i, 1, keepdims=False)
        m_new = jnp.maximum(mi, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        resc = jnp.exp(mi - m_new)
        ai = ai * resc[..., None] + jnp.einsum(
            "bqhk,bkhd->bqhd", p.astype(v.dtype), _gqa_expand(vj, groups),
            preferred_element_type=jnp.float32)
        li = li * resc + jnp.sum(p, axis=-1)
        acc = jax.lax.dynamic_update_index_in_dim(acc, ai, i, 1)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, 1)
        l = jax.lax.dynamic_update_index_in_dim(l, li, i, 1)
        return (acc, m, l), None

    acc0 = jnp.zeros((B, nb, block_size, Hq, D), jnp.float32)
    m0 = jnp.full((B, nb, block_size, Hq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, nb, block_size, Hq), jnp.float32)
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (pi, pj))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, S, Hq, D).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, window: int = 0,
                     logit_cap: float = 0.0, is_global=None):
    """Single-token attention against a preallocated cache.

    q: (B, 1, Hq, D); caches: (B, T, Hkv, D); cache_len: int32 scalar or (B,)
    vector — number of valid cache entries *including* the current token
    (already written).  Per-row lengths support continuous batching (slots
    at different positions).  Scores are (B, Hq, T): tiny, computed directly.
    Under a kv_seq-sharded cache this lowers to partial softmax + combine
    collectives (split-K decode, DESIGN.md §6.3).
    """
    B, _, Hq, D = q.shape
    _, T, Hkv, _ = k_cache.shape
    groups = Hq // Hkv
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len), (B,))
    kexp = jnp.repeat(k_cache, groups, axis=2)
    s = jnp.einsum("bhd,bthd->bht", q[:, 0], kexp,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    pos = jnp.arange(T)
    mask = pos[None, None, :] < cache_len[:, None, None]
    if window:
        w_ok = pos[None, None, :] > (cache_len[:, None, None] - 1 - window)
        if is_global is not None:
            w_ok = w_ok | is_global
        mask = mask & w_ok
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    vexp = jnp.repeat(v_cache, groups, axis=2)
    out = jnp.einsum("bht,bthd->bhd", p.astype(v_cache.dtype), vexp,
                     preferred_element_type=jnp.float32)
    return out[:, None].astype(q.dtype)


def layer_of(cache, layer=None, rows=None):
    """One layer's cache: ``cache`` itself, or with ``layer`` that entry of
    a stacked (L, B, T, ...) cache.  rows: keep only the first ``rows`` of
    T, read by the same one slice."""
    if layer is None:
        return cache if rows is None else cache[:, :rows]
    size = list(cache.shape)
    size[0] = 1
    if rows is not None:
        size[2] = rows
    start = (layer,) + (0,) * (cache.ndim - 1)
    return jax.lax.dynamic_slice(cache, start, size)[0]


def scatter_kv(cache, new, pos, layer=None):
    """Write `new` (B, 1, ...) into `cache` (B, T, ...) at per-row positions
    `pos` (B,) — the continuous-batching cache update (vmapped DUS lowers to
    an efficient scatter).

    With ``layer``, `cache` is a layer stack (L, B, T, ...) and only the rows
    ``[layer, b, pos[b]]`` are written, by one scatter: under a scan carry
    the stack is updated where it lies.  The slot index is an iota, so the
    TPU compiler keeps the scatter native (one with the slot axis as a
    batching dimension it expands into a loop over slots); heads sharded
    over a model axis partition it with no collective, and slots sharded
    over a data axis gather only the indices and new rows, never the stack.
    Indices clamp as DUS's do: a row past the end lands on row T-1."""
    if layer is None:
        def one(c, n, p):
            return jax.lax.dynamic_update_slice(
                c, n.astype(c.dtype), (p,) + (0,) * (c.ndim - 1))

        return jax.vmap(one)(cache, new, pos)
    slots = jnp.arange(new.shape[0])
    return cache.at[layer, slots, pos].set(
        new[:, 0].astype(cache.dtype), mode="clip", indices_are_sorted=True,
        unique_indices=True)
