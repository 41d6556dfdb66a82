"""The comparisons that decide `correct`.

Training: norms are taken per leaf, a layer-stacked leaf giving one
per layer.  A leaf whose reference gradient is under
`NEGLIGIBLE_GRAD` of the median leaf's is nought to rounding (a key
bias under softmax): it moves under the update by round-off alone and
is left out of every comparison.  The gap of a leaf is the gap between
the program's norm and the reference's, over the larger of that leaf's
reference norm and the median leaf's.

The token each ring slot holds after the checked steps is compared the
same way, so that the credit the update computes (eq. 12b) and the
exchange between slots are covered.

Serving: the widest gap by which a served token's reference logit lies
below the reference's best logit at that position.
"""
from __future__ import annotations

import statistics

import numpy as np

NEGLIGIBLE_GRAD = 1e-3


def host_leaf_norms(flat):
    """{leaf path: array} on the host -> {leaf name: norm}, one norm
    per layer for layer-stacked leaves (named as the reference names
    them)."""
    out = {}
    for name, v in flat.items():
        v = np.asarray(v, np.float64)
        if "segments" in name:
            n = np.sqrt(np.sum(np.square(v).reshape(v.shape[0], -1),
                               axis=1))
            for i, x in enumerate(n):
                out[f"{name}[{i}]"] = float(x)
        else:
            out[name] = float(np.sqrt(np.sum(np.square(v))))
    return out


def kept_leaves(ref_grad_norms):
    """Leaves whose reference gradient is not nought to rounding in
    any of the given agents' first gradients."""
    keep = None
    for norms in ref_grad_norms:
        med = statistics.median(norms.values())
        mine = {k for k, v in norms.items() if v >= NEGLIGIBLE_GRAD * med}
        keep = mine if keep is None else keep & mine
    return keep or set()


def leaf_gap(prog, ref, keep):
    """(worst relative gap of norms, its leaf) over the kept leaves."""
    names = sorted(keep)
    missing = [n for n in names if n not in prog]
    if missing:
        return float("inf"), f"missing {missing[:3]}"
    med = statistics.median(ref[n] for n in names)
    worst, leaf = 0.0, ""
    for n in names:
        g = abs(prog[n] - ref[n]) / max(ref[n], med)
        if not np.isfinite(g):
            return float("inf"), n
        if g > worst:
            worst, leaf = g, n
    return worst, leaf


def token_slots_gap(prog, ref, keep):
    """(worst gap, "slot leaf") of the token each ring slot holds: the
    gap of norms over the larger of the reference leaf's norm and the
    median over every slot's kept leaves; a slot whose reference token
    is still all zeros is measured against that median alone."""
    scale = statistics.median(
        [v for r in ref.values() if r for n, v in r.items() if n in keep]
        or [0.0])
    worst, where = 0.0, ""
    for slot in sorted(ref):
        for n in sorted(keep):
            r = ref[slot][n] if ref[slot] else 0.0
            p = prog[slot].get(n, float("inf"))
            den = max(r, scale)
            g = abs(p - r) / den if den > 0 else (0.0 if p == 0 else
                                                  float("inf"))
            if not np.isfinite(g):
                return float("inf"), f"{slot} {n}"
            if g > worst:
                worst, where = g, f"{slot} {n}"
    return worst, where


def widest_logit_gap(ref_logits, served):
    """ref_logits [T, V] f32 at the positions that chose `served` [T]."""
    ref_logits = np.asarray(ref_logits, np.float64)
    best = ref_logits.max(axis=-1)
    got = ref_logits[np.arange(len(served)), np.asarray(served)]
    return float(np.max(best - got))
