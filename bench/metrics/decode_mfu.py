"""Model step: model FLOPs the decode steps in the traced window need
(``bench/roofline/decode_step.py`` at every decoded token's true KV length)
over the decode programs' summed device time times the chip's peak bf16
FLOP/s, %."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    secs = t.program_s("ragged_decode_kernel")
    lens = ctx.decode_kv_lengths(t.host_interval)
    if not secs or not lens:
        return None
    flops = ctx.roofline("decode_step").flops(ctx.config, lens)
    return 100.0 * flops / (secs * ctx.peaks["flops_bf16"])
