"""Plain float32 forward of a dense decoder with grouped-query attention,
and the weights it is served with, drawn from the seed.

This is the yardstick the benchmark compares the served tokens with.  It
imports nothing of the program under test and takes nothing the program has
made: it draws the weights itself, from the same seed, by the recipe the
configuration states, and computes in float32 with
``jax.lax.Precision.HIGHEST`` on every matrix product.  No kernels, no
cache, no batching across requests: each sequence is one full causal
forward.  Layers run one at a time, their weights drawn just before use, so
that a 4B-parameter model fits one chip beside its activations.

The block (Minitron-4B, arXiv:2407.14679, as the configuration runs it):
token embedding; per layer x += Wo·attn(rope(Wq·n(x)), rope(Wk·n(x)),
Wv·n(x)) with causal softmax over head_dim^-1/2-scaled scores, query head h
reading KV head h // (heads / kv_heads); x += Wdown·relu(Wup·n(x))^2; then
n(x)·Wlm.  n is RMSNorm with unit scale.  Departures from the published
model, all the program's and stated in the configuration file: RMSNorm
where Nemotron has LayerNorm1p, and rotary embedding over the whole head
(rotate-half form, theta from the file) where Nemotron rotates half.

Weights: key = jax.random.key(seed); keys = split(key, 6); the embedding is
normal(keys[0], (vocab, d)) · d^-1/2, the LM head normal(keys[2], (d, vocab))
· d^-1/2; layer i draws from split(split(keys[1], layers)[i], 8): attention
from split(.[0], 8) (Wq, Wk, Wv, Wo in that order, each normal · fan_in^-1/2,
Wo drawn (heads·head_dim, d)), the MLP from split(.[3], 3) (Wup, Wdown).
Each is drawn in float32 and rounded to the configuration's
``torch_dtype``; the forward reads the rounded values in float32.

``quant=True`` gives the control: the same forward with every matrix
product's weight (per output channel) and input (per row) rounded to int8,
the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int          # rows of the embedding: vocab_size padded to 256
    real_vocab: int
    eps: float
    theta: float
    dtype: str


def dims(cfg: dict) -> Dims:
    return Dims(cfg["num_hidden_layers"], cfg["hidden_size"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], cfg["intermediate_size"],
                -(-cfg["vocab_size"] // 256) * 256, cfg["vocab_size"],
                float(cfg["norm_eps"]),
                float(cfg["rope_theta"]), cfg["torch_dtype"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _draw(key, shape, std, dtype: str):
    w = jax.random.normal(key, shape, jnp.float32) * jnp.float32(std)
    return w.astype(dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0,))
def layer_weights(m: Dims, seed_key, i):
    kl = jax.random.split(
        jax.random.split(jax.random.split(seed_key, 6)[1], m.layers)[i], 8)
    ka = jax.random.split(kl[0], 8)
    kf = jax.random.split(kl[3], 3)
    d, hq, hkv, hd = m.d, m.heads, m.kv_heads, m.head_dim
    return {
        "wq": _draw(ka[0], (d, hq, hd), 1.0 / math.sqrt(d), m.dtype),
        "wk": _draw(ka[1], (d, hkv, hd), 1.0 / math.sqrt(d), m.dtype),
        "wv": _draw(ka[2], (d, hkv, hd), 1.0 / math.sqrt(d), m.dtype),
        "wo": _draw(ka[3], (hq * hd, d), 1.0 / math.sqrt(hq * hd),
                    m.dtype).reshape(hq, hd, d),
        "w_up": _draw(kf[0], (d, m.d_ff), 1.0 / math.sqrt(d), m.dtype),
        "w_down": _draw(kf[1], (m.d_ff, d), 1.0 / math.sqrt(m.d_ff), m.dtype),
    }


@functools.partial(jax.jit, static_argnums=(0,))
def embed_table(m: Dims, seed_key):
    return _draw(jax.random.split(seed_key, 6)[0], (m.vocab, m.d),
                 m.d ** -0.5, m.dtype)


@functools.partial(jax.jit, static_argnums=(0,))
def lm_head(m: Dims, seed_key):
    return _draw(jax.random.split(seed_key, 6)[2], (m.d, m.vocab),
                 m.d ** -0.5, m.dtype)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _q8(x, axis):
    """Round ``x`` to int8 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(spec, x, w, quant: bool, w_in_axes):
    if quant:
        x = _q8(x, -1)
        w = _q8(w, w_in_axes)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x: (S, H, D), positions 0 .. S-1, rotate-half form."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(m: Dims, w, h, quant: bool):
    """One sequence: h (S, d) -> (S, d)."""
    q = _rope(_mm("sd,dhk->shk", h, w["wq"], quant, 0), m.theta)
    k = _rope(_mm("sd,dhk->shk", h, w["wk"], quant, 0), m.theta)
    v = _mm("sd,dhk->shk", h, w["wv"], quant, 0)
    g = m.heads // m.kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhk,thk->hqt", q, k, precision=HIGHEST)
    s = s / math.sqrt(m.head_dim)
    S = h.shape[0]
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqt,thk->qhk", p, v, precision=HIGHEST)
    return _mm("qhk,hkd->qd", o, w["wo"], quant, (0, 1))


@functools.partial(jax.jit, static_argnums=(0, 3), donate_argnums=(1,))
def layer(m: Dims, x, w, quant: bool):
    """One decoder layer over a batch of sequences x (B, S, d)."""
    def one(xs):
        xs = xs + _attention(m, w, _norm(xs, m.eps), quant)
        up = _mm("sd,df->sf", _norm(xs, m.eps), w["w_up"], quant, 0)
        return xs + _mm("sf,fd->sd", jnp.square(jax.nn.relu(up)),
                        w["w_down"], quant, 0)
    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(m: Dims, table, tokens):
    return jnp.take(table, tokens, axis=0)


@functools.partial(jax.jit, static_argnums=(0,))
def final_norm(m: Dims, x):
    return _norm(x, m.eps)


def hidden(cfg: dict, seed: int, tokens: np.ndarray, quant: bool = False):
    """Final-normed hidden states (B, S, d), float32, of the token rows
    ``tokens`` (B, S) under the weights of ``seed``."""
    m = dims(cfg)
    key = jax.random.key(seed)
    x = _embed(m, embed_table(m, key), jnp.asarray(tokens, jnp.int32))
    for i in range(m.layers):
        x = layer(m, x, layer_weights(m, key, i), quant)
    return final_norm(m, x)


def _logits(m: Dims, head, h, quant: bool):
    out = _mm("nd,dv->nv", h, head, quant, 0)
    return jnp.where(jnp.arange(m.vocab) < m.real_vocab, out, -jnp.inf)


@functools.partial(jax.jit, static_argnums=(0,))
def _gaps(m: Dims, head, h_ref, served):
    """Per row: how far the reference logit of ``served`` lies below the
    reference's best."""
    ref = _logits(m, head, h_ref, False)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - got


@functools.partial(jax.jit, static_argnums=(0,))
def _control_gaps(m: Dims, head, h_ref, h_ctl):
    """Per row: how far the reference logit of the token the control puts
    first lies below the reference's best."""
    ref = _logits(m, head, h_ref, False)
    top = jnp.argmax(_logits(m, head, h_ctl, True), axis=-1)
    got = jnp.take_along_axis(ref, top[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - got


def gaps(cfg: dict, seed: int, h_ref, served: Optional[np.ndarray] = None,
         h_ctl=None, block: int = 512) -> np.ndarray:
    """Gaps over rows (N, d) of final hidden states, ``block`` rows at a
    time: of the ``served`` tokens, or, given the control's hidden states
    ``h_ctl``, of the tokens the control puts first.  float32, length N."""
    m = dims(cfg)
    head = lm_head(m, jax.random.key(seed))
    n = h_ref.shape[0]
    pad = -(-n // block) * block - n
    h_r = jnp.pad(h_ref, ((0, pad), (0, 0)))
    if h_ctl is None:
        other = jnp.pad(jnp.asarray(served, jnp.int32), (0, pad))
        fn = _gaps
    else:
        other = jnp.pad(h_ctl, ((0, pad), (0, 0)))
        fn = _control_gaps
    out = [fn(m, head, h_r[b:b + block], other[b:b + block])
           for b in range(0, n + pad, block)]
    return np.asarray(jnp.concatenate(out))[:n]
