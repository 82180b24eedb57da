"""Model step: device time per ``decode_step`` call of the ops that move
each layer's KV pool out of the stacked cache and back (scopes
``kv_slice`` and ``kv_update``), inside the call's program.  A note
gives the program's device time per scope and the share no scope
names."""
import programtrace

MOVES = ("kv_slice", "kv_update")


def read(ctx):
    by_scope, calls = programtrace.scope_ns(ctx, "decode")
    if not calls or set(by_scope) <= {"unscoped"}:
        return None
    per_call = {k: v / calls / 1e6 for k, v in by_scope.items()}
    ctx.notes.append(
        "decode_step device ms per call by scope: " + ", ".join(
            f"{k} {v!r}" for k, v in sorted(per_call.items(),
                                            key=lambda kv: -kv[1]))
        + f"; unscoped share "
        f"{100.0 * by_scope.get('unscoped', 0) / sum(by_scope.values())!r}"
        f"% of {calls} calls")
    return sum(per_call.get(s, 0.0) for s in MOVES)
