"""The traffic generator is a pure function of its seed, gives every seed
the same work in another order, and follows the mix's parameters."""
import json

import numpy as np

from conftest import BENCH
from harness import traffic

CHAT = json.loads((BENCH / "traffic" / "chat.json").read_text())
SEED = 2 ** 31 + 12345
WINDOW = CHAT["cycle_s"]


def _gen(seed, seconds=WINDOW, mix=CHAT):
    return traffic.generate(mix, ["t"], 256000, seed, seconds)


def _sizes(arrivals):
    return sorted((len(x.prompt), x.max_new) for x in arrivals)


def test_pure_function_of_seed():
    a, b = _gen(SEED), _gen(SEED)
    assert len(a.arrivals) == len(b.arrivals)
    for x, y in zip(a.arrivals, b.arrivals):
        assert x.due == y.due and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_same_work_in_another_order():
    a, b = _gen(SEED), _gen(SEED + 1)
    assert [x.due for x in a.arrivals] != [x.due for x in b.arrivals]
    # the window holds the same arrivals, gaps and sizes, rotated
    win = lambda s: [x for x in s.arrivals if 0.0 <= x.due < WINDOW]
    wa, wb = win(a), win(b)
    assert _sizes(wa) == _sizes(wb)
    cyc = lambda w: [(len(x.prompt), x.max_new) for x in w]
    ca, cb = cyc(wa), cyc(wb)
    assert any(ca == cb[k:] + cb[:k] for k in range(len(cb)))
    def gaps(w):    # the window's gaps and the one across its wrap
        due = [x.due for x in w]
        return np.sort(np.append(np.diff(due), due[0] + WINDOW - due[-1]))
    np.testing.assert_allclose(gaps(wa), gaps(wb), atol=1e-9)
    # no arrival sits on the window's edge, whatever the seed
    for s in (a, b, _gen(7), _gen(2 ** 32 - 1)):
        assert len(win(s)) == round(CHAT["rate_rps"] * WINDOW)
        assert min(abs(x.due) for x in s.arrivals) > 1e-3
        assert min(abs(x.due - WINDOW) for x in s.arrivals) > 1e-3


def test_follows_the_mix():
    s = _gen(SEED)
    window = [x for x in s.arrivals if 0.0 <= x.due < WINDOW]
    n = round(CHAT["rate_rps"] * WINDOW)
    assert len(window) == n
    assert s.start == -CHAT["lead_in_s"]
    assert min(x.due for x in s.arrivals) >= s.start
    assert abs(s.arrivals[-1].due - s.end) < 5.0 / CHAT["rate_rps"]
    # the window holds each stratified quantile of the mix once
    for key, spec in ((lambda x: len(x.prompt), CHAT["prompt_len"]),
                      (lambda x: x.max_new, CHAT["output_len"])):
        assert sorted(map(key, window)) == list(
            traffic.stratified_lognormal(n, spec))
    # the schedule repeats every cycle: the lead-in is the cycle's end
    by_due = {round(x.due, 6): (len(x.prompt), x.max_new) for x in s.arrivals}
    for x in s.arrivals:
        if x.due < 0.0:
            assert by_due[round(x.due + WINDOW, 6)] == (len(x.prompt),
                                                        x.max_new)
    # arrivals bunch as a Poisson stream's do: 5 s blocks hold unequal counts
    counts = np.histogram([x.due for x in window],
                          bins=np.arange(0.0, WINDOW + 1e-9, 5.0))[0]
    assert counts.max() > n * 5.0 / WINDOW + 2 and counts.min() < n * 5.0 / WINDOW
    ids = np.concatenate([x.prompt for x in s.arrivals])
    assert ids.min() >= 0 and ids.max() < 256000
    assert abs(ids.mean() / 256000 - 0.5) < 0.01


def test_stratified_quantiles_follow_the_distributions():
    for spec in (CHAT["prompt_len"], CHAT["output_len"]):
        lens = traffic.stratified_lognormal(2000, spec)
        assert lens.min() >= spec["min"] and lens.max() <= spec["max"]
        assert abs(np.median(lens) / spec["median"] - 1) < 0.01
        q = np.percentile(np.log(lens), [25, 75])
        assert abs((q[1] - q[0]) / 1.349 - spec["sigma"]) < 0.02
    gaps = traffic.stratified_gaps(2000, 1.6)
    assert abs(gaps.mean() * 1.6 - 1) < 0.01
    assert abs(np.median(gaps) * 1.6 - np.log(2)) < 0.01


def test_rate_splits_evenly_over_tenants():
    s = traffic.generate(dict(CHAT, rate_rps=4.0), ["a", "b"], 1000, SEED,
                         WINDOW)
    window = [x.tenant for x in s.arrivals if 0 <= x.due < WINDOW]
    assert window.count("a") == window.count("b") == round(2.0 * WINDOW)
