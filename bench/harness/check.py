"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed, a sample of the requests the server finished,
drawn from the seed and always holding the longest of them, goes through the
configuration's plain reference (``bench/reference/<name>.py``): one full
float32 forward over each prompt followed by its served tokens.  At every
served position the reference's logit of the served token is read against
the reference's best logit there.  Greedy decoding serves the program's own
best, so the gap is nought where the program and the reference agree on the
first place and small where rounding swaps two near-equal logits.  The
configuration file names the numbers compared and their limits
(``check``): any of the widest gap (``max_logit_gap``), the 99th
percentile of the gaps (``p99_logit_gap``) and the mean gap
(``mean_logit_gap``), each at most its limit, over at least
``check.min_tokens`` served tokens.  A lower precision flips many near ties
by a little: that lifts the mean, which counts how often the served token
is off the reference's first place, and the 99th percentile, more than the
widest gap, which grows with the rounding of one position.  The control
(``bench/control.py``) goes through the same ``decide``.

The sample covers every layer a served token passes: the first token comes
from the prefill into a slot, the rest from decode steps through the KV
cache with the ragged kernel, all through the LM head.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import numpy as np

from harness.spec import BENCH, load_module


@dataclasses.dataclass(frozen=True)
class Served:
    tenant: str
    prompt: np.ndarray
    tokens: np.ndarray


def sample(finished: Sequence[Served], seed: int, k: int) -> List[Served]:
    """``k`` finished requests drawn from ``seed``, the longest (prompt plus
    served tokens) always among them."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i].prompt)
                                   + len(finished[i].tokens)))
    rest = np.random.default_rng(np.random.SeedSequence([int(seed), 101]))
    picks = [order[0]] + list(rest.permutation(order[1:])[:k - 1])
    return [finished[i] for i in sorted(picks)]


def reference(config: dict):
    return load_module(BENCH / "reference" / f"{config['reference']}.py")


def rows(config: dict, reqs: Sequence[Served]):
    """Token rows (prompt + served tokens but the last), padded to the
    serving capacity, and for each row the positions that predict its
    served tokens."""
    width = config["serve"]["max_len"]
    toks = np.zeros((len(reqs), width), np.int32)
    pos = []
    for b, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, r.tokens[:-1]])
        toks[b, :len(seq)] = seq
        pos.append(np.arange(len(r.prompt) - 1, len(seq)))
    return toks, pos


def served_gaps(config: dict, seeds: Dict[str, int], reqs: Sequence[Served],
                control: bool = False) -> np.ndarray:
    """Gap of every served token of ``reqs`` (tenant i's weights from
    ``seeds[tenant]``); with ``control``, the gap of the token the int8
    control puts first at each of those positions instead."""
    import jax.numpy as jnp

    ref = reference(config)
    out = []
    for tenant in sorted({r.tenant for r in reqs}):
        mine = [r for r in reqs if r.tenant == tenant]
        toks, pos = rows(config, mine)
        h = ref.hidden(config, seeds[tenant], toks)
        h_rows = jnp.concatenate([h[b, p] for b, p in enumerate(pos)])
        served = np.concatenate([r.tokens for r in mine])
        if control:
            hc = ref.hidden(config, seeds[tenant], toks, quant=True)
            hc_rows = jnp.concatenate([hc[b, p] for b, p in enumerate(pos)])
            del hc
            out.append(ref.gaps(config, seeds[tenant], h_rows, h_ctl=hc_rows))
        else:
            out.append(ref.gaps(config, seeds[tenant], h_rows, served))
        del h
    return np.concatenate(out) if out else np.zeros(0, np.float32)


# the numbers a configuration's ``check`` may compare, by name
STATISTICS = {
    "max_logit_gap": lambda g: float(g.max()),
    "p99_logit_gap": lambda g: float(np.percentile(g, 99)),
    "mean_logit_gap": lambda g: float(g.mean()),
}


def decide(config: dict, gaps: np.ndarray) -> Tuple[bool, dict, List[str]]:
    """``correct`` for the gaps of one run's sample, the numbers compared
    beside their limits, and the same as lines for standard error: each
    statistic the configuration's ``check`` gives a limit, at most that
    limit, over at least ``min_tokens`` served tokens."""
    lim = config["check"]
    n = int(gaps.size)
    values = {k: f(gaps) if n else None
              for k, f in STATISTICS.items() if k in lim}
    correct = bool(values) and n >= lim["min_tokens"] and all(
        v is not None and v <= lim[k] for k, v in values.items())
    compared = {k: {"value": v, "limit": lim[k]} for k, v in values.items()}
    compared["tokens_checked"] = {"value": n, "at_least": lim["min_tokens"]}
    lines = [f"compared: {k} {v!r} limit {lim[k]!r}" for k, v in values.items()]
    lines.append(f"compared: tokens_checked {n} at_least {lim['min_tokens']}")
    return bool(correct), compared, lines


def summary(gaps: np.ndarray) -> Dict[str, float]:
    """Readings of one sample's gaps beside the widest: the 99th
    percentile, the mean, and the share of positions whose token is not the
    reference's first."""
    if not gaps.size:
        return {}
    return {"max": float(gaps.max()), "p99": float(np.percentile(gaps, 99)),
            "mean": float(gaps.mean()), "off_top_share": float((gaps > 0).mean())}
