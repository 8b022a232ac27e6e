"""Engine prefill: seconds the engines spent prefilling in the window (the
``prefill_s`` histogram's sum, first-token sync included) per 1,000 prompt
tokens admitted in the window, ms."""


def read(ctx):
    n, s = ctx.host["prefill_s"]
    toks = sum(r.prompt_len for r in ctx.records
               if r.times and ctx.in_window(r.times[0]))
    return s / toks * 1e6 if n and toks else None
