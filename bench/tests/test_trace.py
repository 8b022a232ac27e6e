"""The trace reduction against a short trace recorded on a v5e chip
(``bench/trace/fixtures/decode_v5e.xplane.pb``: 0.24 s of minitron-4b decode
steps under the chat traffic, cut from a longer recording), and against
hand-made events."""
import numpy as np
import pytest

from conftest import BENCH
from harness import spec

R = spec.load_module(BENCH / "trace" / "reduce.py")
FIXTURE = str(BENCH / "trace" / "fixtures" / "decode_v5e.xplane.pb")


@pytest.fixture(scope="module")
def fixture_trace():
    return R.reduce_file(FIXTURE, (0.0, 1.0), 1)


def _device_ops():
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(FIXTURE)
    plane = next(p for p in prof.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def test_busy_and_window_match_a_timeline(fixture_trace):
    ops = _device_ops()
    lo = min(a for a, _, _ in ops)
    hi = max(b for _, b, _ in ops)
    # a 100 ns timeline of the chip, marked busy under every op
    grid = np.zeros(int((hi - lo) / 100) + 2, bool)
    for a, b, _ in ops:
        grid[int((a - lo) / 100):int(np.ceil((b - lo) / 100))] = True
    assert fixture_trace.devices == 1
    assert fixture_trace.busy_s <= fixture_trace.window_s
    assert fixture_trace.busy_s == pytest.approx(grid.sum() * 100e-9, rel=2e-3)


def test_kernel_time_and_programs(fixture_trace):
    ops = _device_ops()
    kern = [(a, b) for a, b, n in ops if "tpu_custom_call" in n]
    assert kern and all("ragged_decode_kernel" in n for _, _, n in ops
                        if "tpu_custom_call" in n)
    assert fixture_trace.kernels == {
        "ragged_decode_kernel": pytest.approx(
            sum(b - a for a, b in kern) * 1e-9)}
    # every kernel call runs inside a decode program execution
    assert fixture_trace.program_s("ragged_decode_kernel") > \
        fixture_trace.kernel_s("ragged_decode_kernel")
    assert fixture_trace.program_s("no_such_kernel") == 0.0


def test_breakdown_counts_innermost_ops_once(fixture_trace):
    b = fixture_trace.breakdown()
    assert 0 < len(b["device_ops"]) <= R.TOP
    names = [n for n, _ in b["device_ops"]]
    assert not any(n.startswith("while") for n in names)
    # the innermost ops tile the busy time at most once
    assert sum(fixture_trace.ops.values()) <= fixture_trace.busy_s * 1.0001
    assert sum(fixture_trace.gaps.values()) == pytest.approx(
        fixture_trace.window_s - fixture_trace.busy_s, abs=1e-9)


def test_hand_made_events():
    assert R.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    ev = [(0, 10, "while"), (0, 4, "a"), (5, 9, "b"), (12, 13, "c")]
    assert R.innermost(ev) == [(0, 4, "a"), (5, 9, "b"), (12, 13, "c")]
    spans = R._HostSpans([(0, 10, "bench.step"), (2, 4, "np.asarray"),
                          (10, 20, "bench.wait")])
    assert spans.at(3) == "bench.step > np.asarray"
    assert spans.at(8) == "bench.step"
    assert spans.at(15) == "bench.wait"
    assert spans.at(25) == "outside client spans"
    assert R.short_name("%copy.100 = bf16[32,12]{1,0:T(8,128)} copy(%x)") \
        == "copy.100 bf16[32,12]"
    assert R.kernel_name("%ragged_decode_kernel.6 = bf16[12] custom-call()") \
        == "ragged_decode_kernel"
