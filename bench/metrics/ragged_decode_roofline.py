"""Kernels: the ragged decode-attention kernel's share of its roofline over
the traced window.  The least time for the work the algorithm needs (bytes
and FLOPs from ``bench/roofline/ragged_decode.py`` at every decoded token's
true KV length) divided by the summed device time of the kernel's events;
bound by bandwidth at these lengths, %."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    secs = t.kernel_s("ragged_decode_kernel")
    lens = ctx.decode_kv_lengths(t.host_interval)
    if not secs or not lens:
        return None
    c = ctx.config
    flops, nbytes = ctx.roofline("ragged_decode").work(
        lens, c["num_attention_heads"], c["num_key_value_heads"],
        c["head_dim"], c["num_hidden_layers"])
    least = max(flops / ctx.peaks["flops_bf16"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
