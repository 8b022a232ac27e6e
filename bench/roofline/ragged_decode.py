"""Work the ragged decode-attention kernel needs, from shapes and true
lengths (``kernels/ragged_decode``).

One call attends every live slot's single query token to that slot's first
``len`` cached rows, for one layer.  The least work the algorithm needs:

* bytes: K and V at each live row's true length, len x kv_heads x head_dim
  x 2 (K and V) x itemsize, plus the query and the output, heads x head_dim
  x itemsize each;
* FLOPs: 4 x heads x head_dim x len (q.K and p.V, a multiply and an add
  each).

This is per-head work: a kernel that widens q over its KV head's lanes does
kv_heads times more MXU work than this, and that extra is not counted, so
the share reads the same work whatever implements it.
"""


def work(lengths, heads: int, kv_heads: int, head_dim: int, layers: int,
         itemsize: int = 2):
    """(FLOPs, bytes) of ``layers`` calls over live rows of true lengths
    ``lengths`` (an iterable of ints, one per live row and call)."""
    total = rows = 0
    for n in lengths:
        total += n
        rows += 1
    kv = total * kv_heads * head_dim * 2 * itemsize
    qo = rows * heads * head_dim * 2 * itemsize
    flops = 4 * heads * head_dim * total
    return flops * layers, (kv + qo) * layers
