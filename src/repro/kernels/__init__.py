"""Pallas TPU kernels for BWQ inference.

Each is checked against its jnp oracle (``ref``) in interpret mode, and
compiles for a TPU v5e at phi3-mini-3.8b widths (tests/test_tpu_compile.py;
``chip_smoke.py`` runs them compiled on the chip).

bitplane_matmul — bit-plane-sliced mixed-precision matmul (paper layout)
packed_matmul   — int8/int4 per-WB-scale dequant matmul (deployment)
pact_quant      — fused symmetric PACT clip + quantize
paged_attention — fused paged decode attention with in-kernel KV dequant
"""
from .bitplane_matmul import bitplane_matmul
from .packed_matmul import packed_matmul
from .pact_kernel import pact_quant_pallas
from .paged_attention import paged_attention
from .pallas_utils import default_interpret, resolve_interpret
from .ops import (BitplaneLayout, PackedLayout, bwq_dense_bitplane,
                  bwq_dense_packed, to_bitplane_layout, to_packed_layout)
from . import ref
