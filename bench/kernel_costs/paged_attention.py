"""``kernels/paged_attention.py``: operations and bytes of one call.

Counted for the live tokens only: each live row reads its own filled
positions of K and V (int8 payload plus an f32 scale per token and KV
head) once, and its query and output once.  Rows the scheduler parked
and the unfilled blocks the grid still walks are not useful work.
"""

# the trace names each of its Pallas calls "paged_attention.<n>"
NAMES = ("paged_attention",)


def cost(fills, n_heads, n_kv_heads, d_head, kv_bits=8):
    """(operations, bytes) of one call over rows filled to ``fills``."""
    toks = int(sum(fills))
    flops = 4 * toks * n_heads * d_head
    per_tok = n_kv_heads * (2 * d_head * kv_bits // 8 + 2 * 4)
    nbytes = toks * per_tok + len(fills) * n_heads * d_head * 4 * 2
    return flops, nbytes
