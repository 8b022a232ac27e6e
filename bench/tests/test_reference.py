"""The plain reference against the program, at a small size on the CPU.

The reference draws its own weights from the seed; they must be the very
values the program's ``Model.init`` serves, and its float32 forward must
agree with the program's prefill-then-decode logits to the program's
bfloat16 activations.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np

from conftest import BENCH
from reference import dense_gqa as R
from repro.configs import get_reduced
from repro.distribution import strip
from repro.models.model import build_model

CFG = json.loads((BENCH / "tests" / "configs" / "tiny.json").read_text())
SEED = 2 ** 31 + 17


def _program():
    model = build_model(get_reduced(CFG["arch"]))
    assert model.cfg.num_layers == CFG["num_hidden_layers"]
    assert model.cfg.d_model == CFG["hidden_size"]
    return model, strip(model.init(jax.random.key(SEED)))


def test_reference_weights_are_the_served_weights():
    model, params = _program()
    m = R.dims(CFG)
    key = jax.random.key(SEED)
    np.testing.assert_array_equal(R.embed_table(m, key), params["embed"])
    np.testing.assert_array_equal(R.lm_head(m, key), params["lm_head"])
    layers = params["decoder"]["scanned"]
    for i in range(m.layers):
        w = R.layer_weights(m, key, i)
        np.testing.assert_array_equal(w["wq"], layers["attn"]["wq"][i])
        np.testing.assert_array_equal(w["wk"], layers["attn"]["wk"][i])
        np.testing.assert_array_equal(w["wv"], layers["attn"]["wv"][i])
        np.testing.assert_array_equal(w["wo"], layers["attn"]["wo"][i])
        np.testing.assert_array_equal(w["w_up"], layers["ffn"]["w_up"][i])
        np.testing.assert_array_equal(w["w_down"], layers["ffn"]["w_down"][i])


def test_reference_matches_prefill_then_decode():
    """Greedy prefill-then-decode logits of the program, at every served
    position, within 3 % of max |logit| of the reference's full forward
    (the program computes in bfloat16 activations: about 2^-8 relative per
    operation over two layers)."""
    model, params = _program()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, CFG["vocab_size"], size=13).astype(np.int32)
    steps, max_len = 6, 32
    cache = strip(model.init_cache(1, max_len))
    logits, cache = model.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                                  cache)
    got, served = [logits[0]], [int(jnp.argmax(logits[0]))]
    for _ in range(steps - 1):
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([[served[-1]]], jnp.int32))
        got.append(logits[0])
        served.append(int(jnp.argmax(logits[0])))
    seq = np.concatenate([prompt, served[:-1]])[None]
    h = R.hidden(CFG, SEED, seq)[0, len(prompt) - 1:]
    head = R.lm_head(R.dims(CFG), jax.random.key(SEED))
    ref = np.asarray(jnp.einsum("nd,dv->nv", h, head, precision=R.HIGHEST))
    got = np.asarray(jnp.stack(got), np.float32)
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() <= 0.03 * scale
    gap = R.gaps(CFG, SEED, h, np.asarray(served))
    assert gap.shape == (steps,) and gap.min() >= 0.0
    assert gap.max() <= 0.03 * scale
