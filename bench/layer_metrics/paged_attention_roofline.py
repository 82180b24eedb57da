"""Kernels: the least time of every ``paged_attention`` call in the
traced window, bytes and operations counted for live tokens only (the
fill levels logged at each decode call), over the kernel's summed device
time."""


def read(ctx):
    k = ctx.cost("paged_attention")
    w = ctx.widths
    bits = ctx.conf["engine"]["kv_quant_bits"]
    least, bound = 0.0, {"compute": 0, "memory": 0}
    for c in ctx.calls_of("decode"):
        flops, nbytes = k.cost(c.live, w["n_heads"], w["n_kv_heads"],
                               w["d_head"], bits)
        tf = flops / ctx.peak["bf16_flops"]
        tb = nbytes / ctx.peak["hbm_bytes_per_s"]
        bound["compute" if tf >= tb else "memory"] += 1
        least += max(tf, tb) * w["n_layers"]
    ns = ctx.kernel_ns("paged_attention")
    if not ns or not least:
        return None
    ctx.notes.append(f"paged_attention bound by call: {bound}")
    return 100.0 * least / (ns * 1e-9)
