"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) ``v5e:2x2`` topology, which is what the chip's compiler would
accept or refuse.  Interpret-mode tests cannot see Mosaic's tiling rules or
its VMEM limit; these can.  A compile takes a second or two.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.kernels.mamba_scan import mamba_step_kernel
from repro.kernels.ragged_decode import ops
from repro.models.ssm import dims as ssm_dims


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _aval(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ragged_avals(B, T, hkv, g, D, q_sh, kv_sh, vec_sh):
    return (_aval((B, 1, hkv * g, D), jnp.bfloat16, q_sh),
            _aval((B, T, hkv, D), jnp.bfloat16, kv_sh),
            _aval((B, T, hkv, D), jnp.bfloat16, kv_sh),
            _aval((B,), jnp.int32, vec_sh),
            _aval((B,), jnp.bool_, vec_sh))


def _ragged(q, k, v, lens, live):
    return ops.ragged_decode_attention(q, k, v, lens, live=live)


def _widths(arch):
    cfg = get_config(arch)
    hkv = cfg.num_kv_heads
    return hkv, cfg.num_heads // hkv, cfg.resolved_head_dim


@pytest.mark.parametrize("arch", ["minitron-4b", "seamless-m4t-medium"])
def test_ragged_decode_kernel_compiles(topo, one_chip, arch, monkeypatch):
    """Self-attention at minitron-4b widths (Hkv 8, G 3, D 128) and
    cross-attention at seamless-m4t-medium widths (Hkv 16, G 1, D 64), bf16
    cache, T 512: Mosaic accepts the kernel's block layout."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    hkv, g, D = _widths(arch)
    avals = _ragged_avals(8, 512, hkv, g, D, one_chip, one_chip, one_chip)
    compiled = jax.jit(_ragged).lower(*avals).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ragged_decode_kernel_compiles_tensor_parallel(topo, monkeypatch):
    """Two chips, heads split over the model axis as the serving rules
    shard them: the kernel runs under shard_map (XLA cannot partition a
    Mosaic kernel) and compiles with no collective around it."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices[:2]).reshape(1, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    heads = NamedSharding(mesh, P(None, None, "model", None))
    hkv, g, D = _widths("minitron-4b")
    avals = _ragged_avals(8, 512, hkv, g, D, heads, heads,
                          NamedSharding(mesh, P()))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        lowered = jax.jit(_ragged).lower(*avals)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text


def _mamba_avals(d_model, d_in, dt_rank, n, w, sharding, B=8):
    f32, bf16 = jnp.float32, jnp.bfloat16
    return dict(
        x1=_aval((B, 1, d_model), bf16, sharding),
        conv=_aval((B, w - 1, d_in), bf16, sharding),
        h=_aval((B, d_in, n), f32, sharding),
        live=_aval((B,), jnp.int32, sharding),
        in_proj=_aval((d_model, 2 * d_in), f32, sharding),
        conv_w=_aval((w, d_in), f32, sharding),
        conv_b=_aval((d_in,), f32, sharding),
        x_proj=_aval((d_in, dt_rank + 2 * n), f32, sharding),
        dt_proj=_aval((dt_rank, d_in), f32, sharding),
        dt_bias=_aval((d_in,), f32, sharding),
        a_log=_aval((d_in, n), f32, sharding),
        d=_aval((d_in,), f32, sharding),
        out_proj=_aval((d_in, d_model), f32, sharding))


@pytest.mark.parametrize("width", [
    pytest.param("falcon-mamba-7b", marks=pytest.mark.xfail(
        strict=True, raises=jax.errors.JaxRuntimeError,
        reason="RESOURCE_EXHAUSTED: Allocation (size=268435456) would exceed "
               "memory (size=134217728): input window allocation for "
               "operator input 4, f32[4096,16384] in_proj as one VMEM "
               "block; the fused step needs its weights tiled")),
    "d_model-256",
])
def test_mamba_step_kernel_compiles(topo, one_chip, width):
    """The fused Mamba step at falcon-mamba-7b widths (d_model 4096, d_in
    8192, N 16) is refused for VMEM: every weight rides one whole-array
    block.  At d_model 256 it compiles, which pins the f32-accumulating
    dots Mosaic's matmul requires."""
    if width == "falcon-mamba-7b":
        cfg = get_config(width)
        d_in, dt_rank, n, w = ssm_dims(cfg)
        d_model = cfg.d_model
    else:
        d_model, d_in, dt_rank, n, w = 256, 512, 16, 16, 4
    avals = _mamba_avals(d_model, d_in, dt_rank, n, w, one_chip)
    compiled = jax.jit(mamba_step_kernel).lower(**avals).compile()
    assert "tpu_custom_call" in compiled.as_text()
