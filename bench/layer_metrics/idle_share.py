"""Device: the share of the traced window in which no operation ran."""
import tracereduce as tr


def read(ctx):
    lo, hi = ctx.win
    return 100.0 * (1.0 - tr.busy_ns(ctx.trace, ctx.win) / (hi - lo))
