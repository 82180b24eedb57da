"""Model step: device time per ``decode_step`` call of the ops scoped
``moe_experts`` (the held routed experts' part of each MoE layer).  A
note gives the call's device time of ``moe_route``, ``moe_shared`` and
``latent_attn``, and the share no scope names."""
import programtrace

NOTED = ("moe_route", "moe_shared", "latent_attn")


def read(ctx):
    by_scope, calls = programtrace.scope_ns(ctx, "decode")
    if not calls or "moe_experts" not in by_scope:
        return None
    per_call = {k: v / calls / 1e6 for k, v in by_scope.items()}
    ctx.notes.append(
        "decode_step device ms per call: " + ", ".join(
            f"{k} {per_call.get(k, 0.0)!r}" for k in NOTED)
        + f"; unscoped share "
        f"{100.0 * by_scope.get('unscoped', 0) / sum(by_scope.values())!r}"
        f"% of {calls} calls")
    return per_call["moe_experts"]
