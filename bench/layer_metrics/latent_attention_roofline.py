"""Kernels: the least time of every ``latent_attention`` call in the
traced window, bytes and operations counted for live tokens only (the
fill levels logged at each decode call), over the kernel's summed device
time."""


def read(ctx):
    k = ctx.cost("latent_attention")
    w = ctx.widths
    if "kv_lora_rank" not in w:
        return None
    least, bound = 0.0, {"compute": 0, "memory": 0}
    for c in ctx.calls_of("decode"):
        flops, nbytes = k.cost(c.live, w["n_heads"], w["kv_lora_rank"],
                               w["qk_rope_dim"])
        tf = flops / ctx.peak["bf16_flops"]
        tb = nbytes / ctx.peak["hbm_bytes_per_s"]
        bound["compute" if tf >= tb else "memory"] += 1
        least += max(tf, tb) * w["n_layers"]
    ns = ctx.kernel_ns("latent_attention")
    if not ns or not least:
        return None
    ctx.notes.append(f"latent_attention bound by call: {bound}")
    return 100.0 * least / (ns * 1e-9)
