"""The control comes out as not correct.

The control is the plain reference with int8 weights and inputs in every
matrix product, put in the program's place: at each position of the served
tokens, the token it puts first is read against the float32 reference.  At
minitron-4b's published widths with 2 layers and a 32768-row vocabulary (a
size a CPU test run holds; ``configs/minitron-4b-2l.json``), the program's
own prefill-then-decode tokens read within that size's limits and the
control's past them.  The limits of that size are set the way the cell's
are, from readings on the CPU over seeds 1-3 (three requests of 48 tokens
each).  Widest gap: program 0.0166, 0.0207, 0.0230, control 0.1045, 0.0733,
0.1625; limit 0.05.  Mean gap: program 0.00041, 0.00053, 0.00062, control
0.0032, 0.0024, 0.0034; limit 0.0015.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH
from harness import check
from repro.configs import get_config
from repro.distribution import strip
from repro.models.model import build_model

CFG = json.loads((BENCH / "tests" / "configs" / "minitron-4b-2l.json")
                 .read_text())


def _served(model, params, prompt, steps, max_len):
    """Greedy prefill-then-decode through the program's cache."""
    cache = strip(model.init_cache(1, max_len))
    logits, cache = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray(prompt[None])}, cache)
    out = [int(jnp.argmax(logits[0]))]
    step = jax.jit(model.decode_step)
    for _ in range(steps - 1):
        logits, cache = step(params, cache, jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(jnp.argmax(logits[0])))
    return np.asarray(out, np.int32)


@pytest.mark.parametrize("seed", [1, 3])
def test_control_fails_where_the_program_holds(seed):
    mc = dataclasses.replace(get_config("minitron-4b"),
                             num_layers=CFG["num_hidden_layers"],
                             vocab_size=CFG["vocab_size"])
    model = build_model(mc)
    params = strip(model.init(jax.random.key(seed)))
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(3):
        prompt = rng.integers(0, mc.vocab_size,
                              size=int(rng.choice([64, 128, 192]))
                              ).astype(np.int32)
        reqs.append(check.Served("t", prompt, _served(
            model, params, prompt, 48, CFG["serve"]["max_len"])))
    limit = CFG["check"]["max_logit_gap"]
    program = check.served_gaps(CFG, {"t": seed}, reqs)
    control = check.served_gaps(CFG, {"t": seed}, reqs, control=True)
    assert program.size >= CFG["check"]["min_tokens"]
    assert program.max() <= limit < control.max()
    mean_limit = CFG["check"]["mean_logit_gap"]
    assert program.mean() <= mean_limit < control.mean()
    # the control fails through the same decision as the served tokens
    assert check.decide(CFG, program)[0]
    assert not check.decide(CFG, control)[0]


def test_decide_compares_the_statistics_the_configuration_names():
    gaps = np.zeros(1000, np.float32)
    gaps[:5] = 0.5                      # five far-off tokens: the widest gap
    by_p99 = dict(CFG, check={"p99_logit_gap": 0.07, "min_tokens": 300})
    by_max = dict(CFG, check={"max_logit_gap": 0.07, "min_tokens": 300})
    ok, compared, lines = check.decide(by_p99, gaps)
    assert ok and set(compared) == {"p99_logit_gap", "tokens_checked"}
    assert not check.decide(by_max, gaps)[0]
    gaps[:20] = 0.5                     # two in a hundred: the p99 sees them
    assert not check.decide(by_p99, gaps)[0]
    assert not check.decide(dict(CFG, check={"min_tokens": 300}), gaps)[0]
