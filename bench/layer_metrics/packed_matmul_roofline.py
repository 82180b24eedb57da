"""Kernels: the least time of every ``packed_matmul`` call in the traced
window (the larger of operations over the bf16 peak and bytes over HBM
bandwidth, from the call shapes) over the kernel's summed device time."""


def read(ctx):
    k = ctx.cost("packed_matmul")
    w = ctx.widths
    bits = ctx.conf["deploy"]["bits"]
    least = {"compute": 0.0, "memory": 0.0}
    for c in ctx.calls:
        for m, kk, n in k.calls(w, c.rows):
            flops, nbytes = k.cost(m, kk, n, bits=bits)
            tf = flops / ctx.peak["bf16_flops"]
            tb = nbytes / ctx.peak["hbm_bytes_per_s"]
            least["compute" if tf >= tb else "memory"] += max(tf, tb)
    ns = ctx.kernel_ns("packed_matmul")
    total = sum(least.values())
    if not ns or not total:
        return None
    ctx.notes.append(f"packed_matmul bound: memory {least['memory']!r} s, "
                     f"compute {least['compute']!r} s of least time")
    return 100.0 * total / (ns * 1e-9)
