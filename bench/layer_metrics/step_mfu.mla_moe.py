"""Model step: model operations of an MLA + MoE decoder's tokens in the
traced window (``kernel_costs/mla_moe_flops.py``: prompt tokens without
padding, decode tokens of live rows) over the summed device time of the
engine's programs, as a share of the chip's bf16 peak."""


def read(ctx):
    mf = ctx.cost("mla_moe_flops")
    w = ctx.widths
    if "kv_lora_rank" not in w:
        return None
    flops = sum(mf.decode(w, c.live) for c in ctx.calls_of("decode"))
    flops += sum(mf.prefill(w, c.start, c.real)
                 for c in ctx.calls_of("prefill_chunk_at"))
    ns = sum(sum(m.end - m.start for m in ctx.modules(fn))
             for fn in ("decode", "prefill_chunk_at"))
    if not ns or not flops:
        return None
    return 100.0 * flops / (ns * 1e-9 * ctx.peak["bf16_flops"])
