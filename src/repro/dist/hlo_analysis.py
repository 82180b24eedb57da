"""HLO-text collective analysis + roofline terms.

``collective_stats`` scans compiled HLO (``compiled.as_text()``) for
cross-device collectives and totals their payload bytes per op, dtype-aware.
Async pairs are counted once at completion: ``*-start`` lines are skipped
and ``*-done`` lines are folded into their base op (the done instruction
carries the output shape).

``roofline_terms`` turns per-device FLOP / HBM-byte / collective-byte
totals into seconds against the chip constants below; ``dominant_term``
names the binding one.  Consumed by launch/dryrun.py and
benchmarks/roofline.py.

``hlo_op_scopes`` names the ``jax.named_scope`` each instruction of a
compiled program ran under; a profile names its device ops by these
instruction names.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable

# Per-chip constants (TPU-v4-class: bf16 matmul peak, HBM2e, per-chip ICI).
PEAK_FLOPS = 275e12      # FLOP/s
HBM_BW = 1.2e12          # bytes/s
ICI_BW = 0.3e12          # bytes/s (all links combined)

_COLLECTIVES = frozenset({
    "all-reduce", "all-gather", "all-to-all", "ragged-all-to-all",
    "reduce-scatter", "collective-permute", "collective-broadcast",
})

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "c64": 8,
    "s64": 8, "u64": 8, "f64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "f8e4m3fnuz": 1, "f8e5m2fnuz": 1,
}

# "%name = <shapes> opcode(operands...)" — minimal match pulls the first
# call-looking token after '=' as the opcode, everything before it as the
# result shape (possibly a tuple for async ops).
_INSTR = re.compile(
    r"=\s*(?P<shape>.*?)\s(?P<op>[a-z][a-z0-9-]*)\(")
_ARRAY = re.compile(r"([a-z][a-z0-9]*)\[([\d,\s]*)\]")


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for dtype, dims in _ARRAY.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            d = d.strip()
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    bytes_by_op: Dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())


def collective_stats(hlo_text: str) -> CollectiveStats:
    """Count collectives and total their result-shape bytes per op."""
    counts: Dict[str, int] = {}
    bytes_by_op: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.search(line)
        if not m:
            continue
        op = m.group("op")
        if op.endswith("-start"):
            continue                      # counted at the matching -done
        if op.endswith("-done"):
            op = op[:-len("-done")]
        if op not in _COLLECTIVES:
            continue
        counts[op] = counts.get(op, 0) + 1
        bytes_by_op[op] = bytes_by_op.get(op, 0) \
            + _shape_bytes(m.group("shape"))
    return CollectiveStats(counts=counts, bytes_by_op=bytes_by_op)


# "{0}: (2, {1}, may-alias)" entries inside the module header's
# input_output_alias={...} block: output index -> donated parameter.
_ALIAS_ENTRY = re.compile(
    r"\{(?P<out>[\d,\s]*)\}:\s*\((?P<param>\d+),\s*\{(?P<path>[^}]*)\}")


def input_output_aliases(hlo_text: str):
    """Parse the module-level ``input_output_alias`` map from HLO text.

    Returns ``[(output_index_tuple, param_number, param_index_tuple)]`` —
    the compiled record of buffer donation.  Empty when the module
    donates nothing (the deep-check signal behind the graph lint's
    ``missing-donation`` rule; ``Lowered.args_info`` is the cheap
    lowering-level view of the same fact)."""
    key = "input_output_alias={"
    i = hlo_text.find(key)
    if i < 0:
        return []
    # the block nests braces ({out}: (p, {path}, ...)) — scan balanced
    j = i + len(key)
    depth, k = 1, j
    while k < len(hlo_text) and depth:
        if hlo_text[k] == "{":
            depth += 1
        elif hlo_text[k] == "}":
            depth -= 1
        k += 1
    out = []
    for e in _ALIAS_ENTRY.finditer(hlo_text[j:k - 1]):
        oidx = tuple(int(t) for t in e.group("out").split(",") if t.strip())
        pidx = tuple(int(t) for t in e.group("path").split(",")
                     if t.strip().isdigit())
        out.append((oidx, int(e.group("param")), pidx))
    return out


def shape_census(hlo_text: str, min_bytes: int = 0) -> Dict[str, int]:
    """Instruction-result footprint by dtype: ``{dtype: total_bytes}``.

    A compiled-HLO-level census of what the program holds: a packed
    serving program should be s8/u8-dominated — an f32 total on the order
    of the weight bytes is the compiled symptom of a dequant leak."""
    census: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.search(line)
        if not m:
            continue
        for dtype, dims in _ARRAY.findall(m.group("shape")):
            if dtype not in _DTYPE_BYTES:
                continue
            n = _DTYPE_BYTES[dtype]
            for d in dims.split(","):
                d = d.strip()
                if d:
                    n *= int(d)
            if n >= min_bytes:
                census[dtype] = census.get(dtype, 0) + n
    return census


_OP_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")


def hlo_op_scopes(hlo_text: str, scopes: Iterable[str]) -> Dict[str, str]:
    """``{instruction name: scope}`` from optimized HLO text printed with
    its metadata.

    An instruction's scope is the innermost of the names ``scopes`` in
    the ``op_name`` of its metadata (the ``jax.named_scope`` path), and
    ``unscoped`` where that path has none.  An instruction with no
    metadata (a copy that layout assignment put in) takes the scope of
    its first operand that has one, else ``unscoped``."""
    scopes = frozenset(scopes)
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _OP_INSTR.match(line)
        if m is None:
            continue
        name, rest = m.groups()
        meta = _OP_NAME.search(rest)
        if meta is not None:
            path = [p for p in meta.group(1).split("/") if p in scopes]
            out[name] = path[-1] if path else "unscoped"
            continue
        head = rest.split(", metadata=", 1)[0]
        inherited = (out[o] for o in _OPERAND.findall(head)
                     if out.get(o, "unscoped") != "unscoped")
        out[name] = next(inherited, "unscoped")
    return out


def roofline_terms(flops: float, bytes_accessed: float,
                   collective_bytes: float) -> Dict[str, float]:
    """Per-device totals -> time lower bounds per roofline resource."""
    return {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": bytes_accessed / HBM_BW,
        "collective_s": collective_bytes / ICI_BW,
    }


def dominant_term(terms: Dict[str, float]) -> str:
    return max(terms, key=lambda k: terms[k])
