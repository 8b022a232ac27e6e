"""Distributed-feature tests under an 8-host-device subprocess: sharded
training step, elastic checkpoint resharding, compressed cross-pod psum,
mesh composition.  Each scenario runs in its own subprocess because the
device count must be fixed before jax initializes."""
import json
import subprocess
import sys
import textwrap

import pytest

_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import json
import jax
import jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_host_mesh
"""


def _run(body: str, timeout=900):
    out = subprocess.run([sys.executable, "-c",
                          _PRELUDE + textwrap.dedent(body)],
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_train_step_matches_single_device():
    res = _run("""
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.distribution import partitioning as part
    from repro.optim import make_optimizer
    from repro.train.trainer import TrainConfig, make_train_step, \\
        setup_sharded_state
    from repro.launch.mesh import fit_spec

    cfg = get_reduced("qwen2.5-32b")
    model = build_model(cfg)
    opt = make_optimizer(cfg.optimizer)
    tc = TrainConfig(steps=4, lr=1e-3, warmup=1)
    step = make_train_step(model, opt, tc)
    batch = {
        "tokens": jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, size=(4, 16)), jnp.int32),
    }
    batch["labels"] = batch["tokens"]

    # single-device reference
    params0 = part.strip(model.init(jax.random.key(0)))
    opt0 = opt.init(params0)
    p1, o1, m1 = step(params0, opt0, jnp.asarray(0), batch)

    # sharded on a (2 data, 4 model) mesh
    mesh = make_host_mesh((2, 4), ("data", "model"))
    rules = part.train_rules(sequence_parallel=False)
    params, opt_state, psh, osh = setup_sharded_state(
        model, opt, mesh, rules, jax.random.key(0))
    with mesh:
        p2, o2, m2 = jax.jit(step)(params, opt_state, jnp.asarray(0), batch)
    diff = max(float(jnp.abs(a.astype(jnp.float32) -
                             b.astype(jnp.float32)).max())
               for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    print(json.dumps({"loss1": float(m1["loss"]), "loss2": float(m2["loss"]),
                      "param_diff": diff}))
    """)
    assert abs(res["loss1"] - res["loss2"]) < 5e-2
    assert res["param_diff"] < 5e-2


def test_elastic_checkpoint_reshard():
    res = _run("""
    import tempfile
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.train import checkpoint as ck

    mesh_a = make_host_mesh((2, 4), ("data", "model"))
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    sharded = jax.device_put(
        tree, {"w": NamedSharding(mesh_a, P("data", "model"))})
    with tempfile.TemporaryDirectory() as d:
        ck.save(d, 1, sharded, extra={"mesh": [2, 4]})
        # restore onto a DIFFERENT mesh shape (elastic restart)
        mesh_b = make_host_mesh((4, 2), ("data", "model"))
        got, extra = ck.restore(
            d, 1, tree,
            shardings={"w": NamedSharding(mesh_b, P("model", "data"))})
        ok = bool(jnp.all(got["w"] == tree["w"]))
        nshards = len(got["w"].sharding.device_set)
    print(json.dumps({"ok": ok, "shards": nshards,
                      "saved_mesh": extra["mesh"]}))
    """)
    assert res["ok"] and res["shards"] == 8


def test_compressed_psum_cross_pod():
    res = _run("""
    from functools import partial
    from repro.optim import compressed_psum, ErrorFeedback

    mesh = make_host_mesh((8,), ("pod",))
    x = np.random.default_rng(1).normal(size=(8, 64)).astype(np.float32)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=jax.sharding.PartitionSpec("pod"),
             out_specs=jax.sharding.PartitionSpec("pod"))
    def reduce_compressed(xs):
        return compressed_psum(xs[0], "pod")[None]

    got = reduce_compressed(x)
    want = x.mean(0)
    err = float(np.abs(np.asarray(got)[0] - want).max())
    scale = float(np.abs(x).max()) / 127.0
    print(json.dumps({"err": err, "tol": 2 * scale}))
    """)
    assert res["err"] <= res["tol"]


def test_mesh_composer_partitions_devices():
    res = _run("""
    from repro.core.composer import MeshComposer, split_axis
    mesh = make_host_mesh((2, 4), ("data", "model"))
    comp = MeshComposer(mesh, cu_axis="model")
    subs = comp.compose([2, 1, 1], names=["big", "mid", "small"])
    sizes = [s.mesh.devices.size for s in subs]
    ids = [sorted(d.id for d in s.mesh.devices.flatten()) for s in subs]
    flat = sorted(i for grp in ids for i in grp)
    unified = comp.unified()
    print(json.dumps({"sizes": sizes, "disjoint": len(flat) == len(set(flat)),
                      "total": len(flat),
                      "unified": int(unified.mesh.devices.size)}))
    """)
    assert res["sizes"] == [4, 2, 2]
    assert res["disjoint"] and res["total"] == 8
    assert res["unified"] == 8


def test_multi_tenant_two_models_on_submeshes():
    res = _run("""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_reduced
    from repro.core.composer import MeshComposer
    from repro.distribution import partitioning as part
    from repro.models import build_model

    mesh = make_host_mesh((1, 8), ("data", "model"))
    comp = MeshComposer(mesh, cu_axis="model")
    sub_a, sub_b = comp.compose([4, 4], names=["tenant-a", "tenant-b"])

    outs = {}
    for name, sub, arch in [("a", sub_a, "minitron-4b"),
                            ("b", sub_b, "qwen2.5-32b")]:
        cfg = get_reduced(arch)
        m = build_model(cfg)
        params = part.strip(m.init(jax.random.key(0)))
        toks = jnp.zeros((2, 8), jnp.int32)
        with sub.mesh:
            loss, _ = jax.jit(lambda p, t: m.loss(
                p, {"tokens": t, "labels": t}))(params, toks)
        outs[name] = float(loss)
    print(json.dumps(outs))
    """)
    assert all(v > 0 for v in res.values())
