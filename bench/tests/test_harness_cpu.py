"""The harness's whole run at a tiny size on the CPU: the look for a chip is
skipped, everything after it runs as on the chip, and ``correct`` must come
out true on the sound program and false with the timed path broken
underneath it."""
import json
import time

import jax.numpy as jnp
import pytest

from conftest import BENCH
from harness import cell_run, spec
from repro.models.model import Model

CONFIG = json.loads((BENCH / "tests" / "configs" / "tiny.json").read_text())
MIX = json.loads((BENCH / "tests" / "configs" / "tiny_traffic.json").read_text())
BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
SEED = 2 ** 31 + 99


def tiny_cell() -> spec.Cell:
    return spec.Cell("tiny.chat", 1, CONFIG, MIX, BENCHMARK["end_to_end"],
                     BENCHMARK["per_layer"])


def run_tiny(seconds: float = 2.0):
    return cell_run.run(tiny_cell(), SEED, seconds, False, time.perf_counter(),
                        require_tpu=False, log=lambda m: None)


def test_sound_run_is_correct():
    out, lines = run_tiny()
    assert out["correct"], lines
    assert out["attempted"] > 10 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in BENCHMARK["end_to_end"]}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "compared"
    assert out["compared"]["tokens_checked"]["value"] >= 50


def _altered_token(orig):
    def decode_step(self, params, cache, tokens, **kw):
        logits, cache = orig(self, params, cache, tokens, **kw)
        return jnp.roll(logits, 1, axis=-1), cache
    return decode_step


def _state_unchanged(orig):
    def decode_step(self, params, cache, tokens, **kw):
        logits, _ = orig(self, params, cache, tokens, **kw)
        return logits, cache
    return decode_step


def _half_the_batch(orig):
    def decode_step(self, params, cache, tokens, **kw):
        logits, cache = orig(self, params, cache, tokens, **kw)
        half = jnp.arange(logits.shape[0]) < logits.shape[0] // 2
        return jnp.where(half[:, None], logits, 0.0), cache
    return decode_step


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged,
                                   _half_the_batch])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(Model, "decode_step", fault(Model.decode_step))
    out, lines = run_tiny()
    assert not out["correct"], lines
