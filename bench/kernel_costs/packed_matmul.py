"""``kernels/packed_matmul.py``: operations and bytes of one call.

The least bytes a call must move are each operand once: the packed
weight, its per-weight-block scale table, the activations and the f32
result.  The least operations are the product's 2*M*K*N.
"""

# the trace names each of its Pallas calls "packed_matmul.<n>"
NAMES = ("packed_matmul",)


def cost(m, k, n, bits=8, wbr=8, wbc=128, x_bytes=2, out_bytes=4):
    """(operations, bytes) of ``packed_matmul`` on x (m, k) and a packed
    (k, n) weight."""
    flops = 2 * m * k * n
    nbytes = (k * n * bits // 8 + (k // wbr) * (n // wbc) * 4
              + m * k * x_bytes + m * n * out_bytes)
    return flops, nbytes


def per_layer(w):
    """(k, n) of each projection that runs through ``packed_matmul`` in
    one layer of a dense model, from the configuration's widths."""
    d, dh = w["d_model"], w["d_head"]
    return [(d, w["n_heads"] * dh), (d, w["n_kv_heads"] * dh),
            (d, w["n_kv_heads"] * dh), (w["n_heads"] * dh, d),
            (d, w["d_ff"]), (d, w["d_ff"]), (w["d_ff"], d)]


def calls(w, rows):
    """(m, k, n) of every ``packed_matmul`` call one engine call makes
    with ``rows`` activation rows."""
    return [(rows, k, n) for k, n in per_layer(w)] * w["n_layers"]
