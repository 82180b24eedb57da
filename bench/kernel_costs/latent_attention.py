"""``kernels/latent_attention.py``: operations and bytes of one call.

Counted for the live tokens only: each live row reads its own filled
positions of the latent cache once, ``kv_lora_rank`` + ``qk_rope_dim``
int8 values and their two f32 scales per token, and its queries
(``q_lat``, ``q_pe``) and output (``o_lat``) once, in f32.  Per token
and head, the scores take 2 * (rank + rope) operations and the weighted
sum 2 * rank.
"""

# the trace names each of its Pallas calls "latent_attention.<n>"
NAMES = ("latent_attention",)


def cost(fills, n_heads, rank, rope):
    """(operations, bytes) of one call over rows filled to ``fills``."""
    toks = int(sum(fills))
    flops = 2 * toks * n_heads * (2 * rank + rope)
    nbytes = toks * (rank + rope + 2 * 4) \
        + len(fills) * n_heads * (2 * rank + rope) * 4
    return flops, nbytes
