"""Engine: device time of one ``prefill_chunk_at`` program call, in a
cell whose tails are judged."""


def read(ctx):
    return ctx.module_ms("prefill_chunk_at")
