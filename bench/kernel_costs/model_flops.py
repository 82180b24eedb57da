"""Model operations per token, from the configuration's widths.

Two operations per multiply-add of every weight a token passes through,
plus attention's
4 * context * heads * head-size per layer, plus the output head.
Padding, parked rows and recomputation do not count.
"""


def weights_per_token(w):
    d, dh = w["d_model"], w["d_head"]
    attn = d * (w["n_heads"] + 2 * w["n_kv_heads"]) * dh \
        + w["n_heads"] * dh * d
    mlp = 3 * d * w["d_ff"]
    return w["n_layers"] * (attn + mlp) + d * w["vocab"]


def token(w, context):
    """Operations of one token that attends over ``context`` positions
    (itself included)."""
    return 2 * weights_per_token(w) \
        + 4 * context * w["n_heads"] * w["d_head"] * w["n_layers"]


def prefill(w, start, real):
    """A chunk's ``real`` prompt tokens written from position ``start``."""
    return sum(token(w, start + i + 1) for i in range(real))


def decode(w, fills):
    """One decode step over live rows filled to ``fills`` (the new token
    included)."""
    return sum(token(w, int(f)) for f in fills)
