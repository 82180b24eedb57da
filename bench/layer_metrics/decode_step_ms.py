"""Engine: device time of one ``decode_step`` program call."""


def read(ctx):
    return ctx.module_ms("decode")
