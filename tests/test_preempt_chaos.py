"""Preemption chaos: seeded preempt–readmit–recompose interleavings over the
mixed four-class fleet must be invisible in the token streams.

The contract under test is the strongest one the paged-KV PR makes:
scheduling — page-pressure preemption, SLO preemption, parking, resume,
live recomposition — is a pure *placement* decision.  Device state is
exported exactly on preempt and re-injected on resume, and greedy decode
rows are batch-independent, so any interleaving of chaos operations yields
streams bit-identical to the undisturbed run.

Subprocess-pinned (8 host devices) like tests/test_ragged_decode.py, with
the ``use_kernels`` on/off axis: kernels are a pure performance knob and
must hold the same bit-identity under chaos.
"""
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


_CHAOS_BODY = """
from repro.launch.serve import MIXED_FLEET, _streams_digest
from repro.serve import ComposedServer, ServeConfig, TenantSpec

mesh = make_host_mesh((1, 8), ("data", "model"))
serve = ServeConfig(max_slots=2, max_len=48, eos_id=-1, kv_page_rows=8,
                    use_kernels=__UK__)
tenants = [TenantSpec(f"{w}-{arch}", arch, reduced=True, serve=serve,
                      seed=i, workload=w)
           for i, (w, arch) in enumerate(MIXED_FLEET)]

def run(chaos_seed):
    # no policy, no warm pool: chaos drives every schedule change itself
    server = ComposedServer(mesh, tenants, policy=None, warm=False)
    rng = np.random.default_rng(5)
    for t in server.engines:
        vocab = server.cfgs[t].vocab_size
        for _ in range(3):
            server.submit(t, rng.integers(1, vocab,
                                          size=int(rng.integers(4, 16))),
                          max_new_tokens=8)
    crng = (np.random.default_rng(chaos_seed)
            if chaos_seed is not None else None)
    names = sorted(server.engines)
    steps = 0
    while any(e.has_work for e in server.engines.values()):
        if crng is not None and steps % 2 == 1:
            op = int(crng.integers(0, 3))
            if op == 0:
                # preempt: park a live stream on a random tenant
                t = names[int(crng.integers(0, len(names)))]
                server.engines[t].preempt_one()
            elif op == 1:
                # recompose: move one CU between two random tenants (the
                # evacuate/adopt path must carry parked requests along)
                sizes = server.sizes()
                i, j = crng.choice(len(names), size=2, replace=False)
                a, b = names[int(i)], names[int(j)]
                if sizes.get(a, 0) > 1 and sizes.get(b, 0) > 0:
                    sizes[a] -= 1
                    sizes[b] += 1
                    server.recompose(sizes, reason="chaos")
            # op == 2: plain step (interleaving spacer)
        server.step()
        steps += 1
        assert steps < 3000, "chaos run did not drain"
    server.drain(max_steps=300)
    stats = server.stats()
    return (_streams_digest(server.results()),
            sum(stats["preemptions"].values()),
            stats["recompositions"])

ref, _, _ = run(None)
digests, preempts, recomps = [], 0, 0
for seed in (3, 11):
    d, p, r = run(seed)
    digests.append(d)
    preempts += p
    recomps += r
print(json.dumps({"match": all(d == ref for d in digests),
                  "preempts": preempts, "recomps": recomps}))
"""


@pytest.mark.slow
@pytest.mark.parametrize("use_kernels", [True, False])
def test_chaos_interleavings_keep_streams_bitexact(use_kernels):
    res = _run(_CHAOS_BODY.replace("__UK__", str(use_kernels)))
    # the chaos schedule must actually have exercised both operations
    assert res["preempts"] >= 1, res
    assert res["recomps"] >= 1, res
    assert res["match"], res
