"""What a run on the chip relies on before the first step: the persistent
compile cache lands where it can be placed from outside, the device kind
maps to exactly one platform profile, and meshes are built with Auto axes."""
import json
import os
import subprocess
import sys
import textwrap
import types

import jax
import pytest
from jax.sharding import AxisType

from repro.common.jax_cache import CACHE_ENV, DEFAULT_CACHE_DIR
from repro.common.platform import DEVICE_PROFILES, TPU_V5E, device_profile
from repro.launch.mesh import make_host_mesh
from repro.serve.fabric import AnalyticalPolicy


def _run(body: str, env: dict) -> dict:
    out = subprocess.run(
        [sys.executable, "-c",
         'import sys; sys.path.insert(0, "src")\n' + textwrap.dedent(body)],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if k != CACHE_ENV}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_compile_cache_env_dir_is_what_jax_uses(tmp_path):
    """A set JAX_COMPILATION_CACHE_DIR wins: nothing overrides it, and a
    compilation is written there."""
    cache = tmp_path / "cache"
    res = _run("""
    import json
    from repro.common.jax_cache import setup_compile_cache
    path = setup_compile_cache()
    import jax, jax.numpy as jnp
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 3).lower(jnp.ones(7)).compile()
    print(json.dumps({"path": path,
                      "config": jax.config.jax_compilation_cache_dir}))
    """, _env(**{CACHE_ENV: str(cache)}))
    assert res["path"] == res["config"] == str(cache)
    assert any(cache.iterdir()), "no cache entry was written"


def test_compile_cache_defaults_to_checkout_dir():
    res = _run("""
    import json, jax
    from repro.common.jax_cache import setup_compile_cache
    path = setup_compile_cache()
    print(json.dumps({"path": path,
                      "config": jax.config.jax_compilation_cache_dir}))
    """, _env())
    assert res["path"] == res["config"] == str(DEFAULT_CACHE_DIR)
    assert DEFAULT_CACHE_DIR.name == ".jax_cache"
    assert (DEFAULT_CACHE_DIR.parent / "chip_smoke.py").exists()


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("kind", sorted(DEVICE_PROFILES))
def test_device_kind_maps_to_profile(kind):
    assert device_profile(_device("tpu", kind)) is DEVICE_PROFILES[kind]


def test_unknown_device_kind_is_an_error(monkeypatch):
    """No silent v5e peaks on another chip: the policy refuses to price an
    unknown kind rather than defaulting."""
    other = _device("tpu", "TPU v4")
    with pytest.raises(ValueError, match="TPU v4"):
        device_profile(other)
    monkeypatch.setattr(jax, "devices", lambda *a: [other])
    with pytest.raises(ValueError, match="DEVICE_PROFILES"):
        AnalyticalPolicy()


def test_cpu_backend_prices_the_deployment_target():
    assert device_profile(_device("cpu", "cpu")) is TPU_V5E
    assert AnalyticalPolicy().platform is TPU_V5E


def test_host_mesh_axes_are_auto():
    mesh = make_host_mesh((1, 1))
    assert mesh.axis_names == ("data", "model")
    assert all(t == AxisType.Auto for t in mesh.axis_types)
