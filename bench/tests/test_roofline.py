"""Operation and byte counts against a hand count at one small shape."""
import json

from conftest import BENCH
from harness import spec

CFG = json.loads((BENCH / "configs" / "minitron-4b.json").read_text())


def test_ragged_decode_work_by_hand():
    # two live rows of 10 and 30 cached rows, 4 query heads over 2 KV heads
    # of 8 lanes, 3 layers, bf16
    flops, nbytes = spec.roofline("ragged_decode").work(
        [10, 30], heads=4, kv_heads=2, head_dim=8, layers=3)
    # q.K and p.V: 2 FLOPs per lane each, per query head and cached row
    assert flops == 3 * 4 * 4 * 8 * (10 + 30)
    kv = (10 + 30) * 2 * 8 * 2 * 2          # K and V rows, bf16
    qo = 2 * 4 * 8 * 2 * 2                  # q and out per live row, bf16
    assert nbytes == 3 * (kv + qo)


def test_decode_step_flops_by_hand():
    m = spec.roofline("decode_step")
    d, ff, v, L = 3072, 9216, 256000, 32
    per_layer = (d * 24 * 128 + 2 * d * 8 * 128      # Q, K, V
                 + 24 * 128 * d                      # output projection
                 + 2 * d * ff)                       # MLP up and down
    assert m.matmul_weights(CFG) == L * per_layer + d * v
    one = m.flops(CFG, [100])
    assert one == 2 * (L * per_layer + d * v) + 4 * 24 * 128 * L * 100
    assert m.flops(CFG, [100, 200]) == 2 * 2 * (L * per_layer + d * v) \
        + 4 * 24 * 128 * L * 300
