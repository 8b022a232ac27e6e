"""mamba_scan — fused selective-scan Pallas kernel (Mamba-1, arXiv:2312.00752).

Fuses discretization (dt, A -> deltaA), the recurrence
``h_t = deltaA_t * h_{t-1} + dt_t * B_t * x_t`` and the output projection
``y_t = C_t . h_t + D * x_t`` in VMEM, so the (S, D, N) state expansion never
touches HBM — the TPU re-derivation of Mamba's hardware-aware scan and the
kind of bandwidth-bound hot spot FILCO assigns to a dedicated CU.

Grid: (B, D/bd, S/bs) with the last (sequence) dimension sequential; the
(bd, N) hidden state lives in VMEM scratch across sequence steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, h_ref, *,
                 bs):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[...].astype(jnp.float32)        # (bs, bd)
    dt = dt_ref[...].astype(jnp.float32)      # (bs, bd)
    bmat = b_ref[...].astype(jnp.float32)     # (bs, N)
    cmat = c_ref[...].astype(jnp.float32)     # (bs, N)
    a = a_ref[...].astype(jnp.float32)        # (bd, N)
    dvec = d_ref[...].astype(jnp.float32)     # (1, bd)

    def step(t, carry):
        h, y = carry                          # h: (bd, N); y: (bs, bd)
        dt_t = dt[t][:, None]                 # (bd, 1)
        da = jnp.exp(dt_t * a)                # (bd, N)
        dbx = (dt_t * x[t][:, None]) * bmat[t][None, :]
        h = da * h + dbx
        y_t = jnp.sum(h * cmat[t][None, :], axis=1) + dvec[0] * x[t]
        y = jax.lax.dynamic_update_index_in_dim(y, y_t, t, 0)
        return h, y

    h0 = h_ref[...]
    y0 = jnp.zeros(x.shape, jnp.float32)
    h_last, y = jax.lax.fori_loop(0, bs, step, (h0, y0))
    h_ref[...] = h_last
    y_ref[...] = y.astype(y_ref.dtype)


def _step_kernel(live_ref, x_ref, conv_ref, h_ref, inproj_ref, convw_ref,
                 convb_ref, xproj_ref, dtproj_ref, dtbias_ref, alog_ref,
                 dvec_ref, outproj_ref, o_ref, nconv_ref, nh_ref, *,
                 dt_rank, state_dim):
    b = pl.program_id(0)
    live = live_ref[b] != 0
    f32 = jnp.float32

    @pl.when(live)
    def _step():
        x = x_ref[...]                                       # (1, d_model)
        dtype = x.dtype
        # every dot accumulates in f32 (Mosaic's MXU contract) and rounds
        # to the activation dtype where the unfused chain does
        xz = jax.lax.dot_general(
            x, inproj_ref[...].astype(dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=f32).astype(dtype)
        d_in = xz.shape[1] // 2
        xp, z = xz[:, :d_in], xz[:, d_in:]                   # (1, d_in)
        window = jnp.concatenate(
            [conv_ref[...].astype(dtype), xp], axis=0)       # (w, d_in)
        xc = jnp.sum(window.astype(f32) * convw_ref[...].astype(f32),
                     axis=0, keepdims=True) + convb_ref[...].astype(f32)
        x_conv = jax.nn.silu(xc).astype(dtype)               # (1, d_in)
        dbc = jax.lax.dot_general(
            x_conv, xproj_ref[...].astype(dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=f32).astype(dtype)
        dt_raw = dbc[:, :dt_rank]
        b_ssm = dbc[:, dt_rank:dt_rank + state_dim].astype(f32)
        c_ssm = dbc[:, dt_rank + state_dim:].astype(f32)     # (1, N)
        dt = jax.nn.softplus(
            jax.lax.dot_general(dt_raw, dtproj_ref[...].astype(dtype),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=f32)
            .astype(dtype).astype(f32)
            + dtbias_ref[...].astype(f32))                   # (1, d_in)
        a = -jnp.exp(alog_ref[...].astype(f32))              # (d_in, N)
        dt_col = jnp.reshape(dt, (d_in, 1))
        da = jnp.exp(dt_col * a)
        xcol = jnp.reshape(x_conv.astype(f32), (d_in, 1))
        h_new = da * h_ref[...] + (dt_col * xcol) * b_ssm    # (d_in, N)
        y = jax.lax.dot_general(h_new, c_ssm, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32)
        y = jnp.reshape(y, (1, d_in)) \
            + dvec_ref[...].astype(f32) * x_conv.astype(f32)
        y = (y * jax.nn.silu(z.astype(f32))).astype(dtype)
        o_ref[...] = jax.lax.dot_general(
            y, outproj_ref[...].astype(dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=f32).astype(o_ref.dtype)
        nconv_ref[...] = window[1:].astype(nconv_ref.dtype)
        nh_ref[...] = h_new

    @pl.when(jnp.logical_not(live))
    def _dead():
        # empty slot: no SSM work, output zeros, state carried unchanged
        o_ref[...] = jnp.zeros_like(o_ref)
        nconv_ref[...] = conv_ref[...]
        nh_ref[...] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba_step_kernel(x1, conv, h, live, in_proj, conv_w, conv_b, x_proj,
                      dt_proj, dt_bias, a_log, d, out_proj, *,
                      interpret: bool = False):
    """Fused single-token Mamba step: in_proj + conv shift + selective-scan
    update + gate + out_proj in one kernel, one row per grid step.

    x1: (B, 1, d_model); conv: (B, w-1, d_in); h: (B, d_in, N) fp32;
    live: (B,) int32 row mask -> (out (B, 1, d_model), new_conv, new_h).

    Every weight rides VMEM whole, so the op is bound by
    ``d_model * d_in``-scale weights fitting VMEM — fine for serving-sized
    blocks, not a training kernel.  Rows with ``live == 0`` skip all work
    and carry their state through unchanged (output rows are zero).
    """
    B = x1.shape[0]
    w1, d_in = conv.shape[1], conv.shape[2]
    n = h.shape[2]
    dt_rank = dt_proj.shape[0]
    full = lambda b, *_: (0, 0)
    row3 = lambda b, *_: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, 1, x1.shape[2]), row3),       # x1
            pl.BlockSpec((None, w1, d_in), row3),             # conv window
            pl.BlockSpec((None, d_in, n), row3),              # h
            pl.BlockSpec(in_proj.shape, full),
            pl.BlockSpec(conv_w.shape, full),
            pl.BlockSpec((1, d_in), full),                    # conv_b
            pl.BlockSpec(x_proj.shape, full),
            pl.BlockSpec(dt_proj.shape, full),
            pl.BlockSpec((1, d_in), full),                    # dt_bias
            pl.BlockSpec(a_log.shape, full),
            pl.BlockSpec((1, d_in), full),                    # D
            pl.BlockSpec(out_proj.shape, full),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, x1.shape[2]), row3),
            pl.BlockSpec((None, w1, d_in), row3),
            pl.BlockSpec((None, d_in, n), row3),
        ],
    )
    kernel = functools.partial(_step_kernel, dt_rank=dt_rank, state_dim=n)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(x1.shape, x1.dtype),
            jax.ShapeDtypeStruct(conv.shape, conv.dtype),
            jax.ShapeDtypeStruct(h.shape, jnp.float32),
        ],
        interpret=interpret,
    )(live, x1, conv, h, in_proj, conv_w, conv_b.reshape(1, d_in), x_proj,
      dt_proj, dt_bias.reshape(1, d_in), a_log, d.reshape(1, d_in), out_proj)


@functools.partial(jax.jit, static_argnames=("bd", "bs", "interpret"))
def mamba_scan(x, dt, b, c, a_log, d, *, bd: int = 512, bs: int = 128,
               interpret: bool = False):
    """Fused selective scan.

    x, dt: (B, S, D); b, c: (B, S, N); a_log: (D, N); d: (D,) -> y: (B, S, D).
    dt must already be softplus'd (positive step sizes).
    """
    B, S, D = x.shape
    N = b.shape[-1]
    bd = min(bd, D)
    bs = min(bs, S)
    assert D % bd == 0 and S % bs == 0, (D, bd, S, bs)
    grid = (B, D // bd, S // bs)
    a = -jnp.exp(a_log.astype(jnp.float32))
    d2 = d.reshape(1, D)
    kernel = functools.partial(_scan_kernel, bs=bs)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bs, bd), lambda bi, di, si: (bi, si, di)),  # x
            pl.BlockSpec((None, bs, bd), lambda bi, di, si: (bi, si, di)),  # dt
            pl.BlockSpec((None, bs, N), lambda bi, di, si: (bi, si, 0)),    # B
            pl.BlockSpec((None, bs, N), lambda bi, di, si: (bi, si, 0)),    # C
            pl.BlockSpec((bd, N), lambda bi, di, si: (di, 0)),              # A
            pl.BlockSpec((1, bd), lambda bi, di, si: (0, di)),              # D
        ],
        out_specs=pl.BlockSpec((None, bs, bd), lambda bi, di, si: (bi, si, di)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, b, c, a, d2)
