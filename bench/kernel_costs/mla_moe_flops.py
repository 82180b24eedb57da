"""Model operations per token of an MLA + MoE decoder (DeepSeek-V2),
from the configuration's widths, counted in the published (expanded)
form whatever the program computes.

Two operations per multiply-add of every weight a token passes through:
the MLA projections (``W_q``, ``W_kva``, ``W_kvb``, ``W_o``) of every
layer, the leading dense layers' MLP, each MoE layer's shared experts
and its routed experts at the deployment's mean share of
``top_k * held / routed`` expert MLPs a token (so the count does not
depend on how routing fell), the router, and the output head.
Attention over the context adds, per layer and head, 2 * (nope + rope)
operations a position for the scores and 2 * v for the sum.  Padding,
parked rows and recomputation do not count.
"""


def weights_per_token(w):
    d, h = w["d_model"], w["n_heads"]
    r, dn, dr, dv = (w["kv_lora_rank"], w["qk_nope_dim"], w["qk_rope_dim"],
                     w["v_head_dim"])
    attn = d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d
    dense = w["first_dense_layers"]
    moe = w["n_layers"] - dense
    expert = 3 * d * w["d_ff"]
    routed = w["top_k"] * w["n_held_experts"] / w["n_experts"] * expert
    shared = w["n_shared_experts"] * expert
    return (w["n_layers"] * attn + dense * 3 * d * w["dense_d_ff"]
            + moe * (routed + shared + d * w["n_experts"])
            + d * w["vocab"])


def token(w, context):
    """Operations of one token that attends over ``context`` positions
    (itself included)."""
    per_pos = 2 * w["n_heads"] * (w["qk_nope_dim"] + w["qk_rope_dim"]
                                  + w["v_head_dim"])
    return 2 * weights_per_token(w) + per_pos * context * w["n_layers"]


def prefill(w, start, real):
    """A chunk's ``real`` prompt tokens written from position ``start``."""
    return sum(token(w, start + i + 1) for i in range(real))


def decode(w, fills):
    """One decode step over live rows filled to ``fills`` (the new token
    included)."""
    return sum(token(w, int(f)) for f in fills)
