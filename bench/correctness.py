"""How ``correct`` is decided: served tokens against the plain reference.

After the window, a sample of the requests the window finished, drawn
from the seed and always holding the one with the most served tokens,
is run once through the reference over each prompt and its served
tokens.  At every served position the gap is how far the served token's
reference logit lies below the reference's best logit, in units of the
standard deviation of that position's reference logits (so the number
reads the same at any width).  The run is correct when the mean gap
stays under the cell's limit and every sampled request served all the
tokens it asked for.  The mean, not the widest gap: on the chip the
widest gap of sound phi3 runs reached 0.21 and the fp8 control's fell
to 0.58, less than three times apart, while the mean separates them.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent


def reference(conf: dict):
    name = conf["reference"]
    path = HERE / "references" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"references.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, conf["reference_class"])(conf)


def sample(served: Dict[int, Tuple[np.ndarray, List[int]]], seed: int,
           want_tokens: int, max_seqs: int) -> List[int]:
    """uids to compare: the longest served request, then others in an
    order drawn from the seed, until ``want_tokens`` served tokens or
    ``max_seqs`` requests."""
    uids = sorted(served)
    if not uids:
        return []
    longest = max(uids, key=lambda u: (len(served[u][1]), -u))
    rest = [u for u in uids if u != longest]
    rng = np.random.default_rng([seed, 0xC0FFEE])
    picked, n = [longest], len(served[longest][1])
    for i in rng.permutation(len(rest)):
        if n >= want_tokens or len(picked) >= max_seqs:
            break
        picked.append(rest[i])
        n += len(served[rest[i]][1])
    return picked


def batch(seqs: Sequence[Tuple[np.ndarray, List[int]]], rows: int,
          width: int):
    """(rows, width) tokens (each prompt followed by its served tokens
    but the last) and the flat positions whose logits predict each served
    token."""
    toks = np.zeros((rows, width), np.int32)
    pos, want = [], []
    for b, (prompt, served) in enumerate(seqs):
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        toks[b, :len(seq)] = seq
        p = len(prompt)
        pos += [b * width + p - 1 + j for j in range(len(served))]
        want += list(served)
    return toks, np.asarray(pos, np.int32), np.asarray(want, np.int64)


def gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> Dict[str, float]:
    """(best - chosen) reference logit at each position, in units of the
    position's logit standard deviation: their mean (the number
    compared) and their largest."""
    lg = ref_logits.astype(np.float64)
    best = lg.max(-1)
    got = lg[np.arange(len(chosen)), chosen]
    g = (best - got) / lg.std(-1)
    return {"mean": float(g.mean()), "widest": float(g.max())}


BUCKET = 512       # reference widths: the sample's longest, rounded up


def served_gap(ref, key, seqs, rows: int, max_len: int,
               control: bool = False) -> Dict[str, Dict[str, float]]:
    """The gaps of the served tokens; with ``control``, also those of the
    tokens the fp8 reference ranks first at the same positions."""
    longest = max(len(p) + len(s) for p, s in seqs)
    width = min(max_len, -(-longest // BUCKET) * BUCKET)
    toks, pos, want = batch(seqs, rows, width)
    lg = ref.logits(key, toks, pos)
    out = {"served": gaps(lg, want)}
    if control:
        lc = ref.logits(key, toks, pos, fp8=True)
        out["control"] = gaps(lg, lc.argmax(-1))
    return out
