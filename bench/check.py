"""The comparison that decides ``correct``.

Both sides hand over the same readings of the first three train steps and
the first sync, taken from the program's own state as the window's call
left it, and from the plain reference run from the same seed:

* ``loss``   [step][cluster] mean loss of each of the first three steps;
* ``grad``   {leaf: [cluster]} norm of the momentum after step 1, which is
  the first gradient (weight decay included) as the optimizer got it;
* ``sync``   {kind.leaf: [...]} norms after the first sync of the applied
  consensus update ``d.<leaf>`` (new reference minus initial weights), the
  downlink error ``e.<leaf>`` and each cluster's uplink error
  ``eps.<leaf>``;
* ``change`` {leaf: [cluster]} norm of the parameters' change from the
  initial weights after step 3.

Numbers compared, each against its limit:

* ``loss_gap``: largest |loss - loss_ref| / |loss_ref|;
* ``grad_gap``, ``sync_gap``, ``change_gap``: worst leaf of
  |norm - norm_ref| / max(norm_ref, median leaf norm_ref of that kind).

Leaves whose reference gradient is under a thousandth of the median
leaf's (placeholders that hold no parameter, and any leaf the loss does not
reach) move by rounding alone and are left out of ``grad_gap``,
``sync_gap`` and ``change_gap``.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

LIMITS_DIR = Path(__file__).resolve().parent / "limits"
NUMBERS = ("loss_gap", "grad_gap", "sync_gap", "change_gap")
STILL = 1e-3


def _kind(name: str) -> tuple:
    """(kind, leaf) of a reading key: ``eps.embed`` -> ("eps", "embed")."""
    head, _, rest = name.partition(".")
    return (head, rest) if head in ("d", "e", "eps") else ("", name)


def moving_leaves(ref: dict) -> set:
    """Leaves whose reference first gradient is not nought to rounding."""
    g = {k: max(v) for k, v in ref["grad"].items()}
    med = float(np.median(list(g.values())))
    return {k for k, v in g.items() if v >= STILL * med}


def norm_gap(prog: dict, ref: dict, keep: set) -> float:
    """Worst-leaf gap between two {key: [norms]} readings."""
    kinds = {}
    for key, vals in ref.items():
        kind, leaf = _kind(key)
        if leaf in keep:
            kinds.setdefault(kind, []).extend(vals)
    med = {k: float(np.median(v)) for k, v in kinds.items()}
    worst = 0.0
    for key, rvals in ref.items():
        kind, leaf = _kind(key)
        if leaf not in keep:
            continue
        for p, r in zip(prog[key], rvals):
            den = max(abs(r), med[kind])
            if den > 0:
                worst = max(worst, abs(p - r) / den)
            elif p != r:
                worst = float("inf")
    return worst


def compare(prog: dict, ref: dict) -> dict:
    """-> {number: value} for the four numbers above."""
    lp, lr = np.asarray(prog["loss"], float), np.asarray(ref["loss"], float)
    if lp.shape != lr.shape:
        raise ValueError(f"loss readings differ in shape: {lp.shape} vs "
                         f"{lr.shape}")
    keep = moving_leaves(ref)
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": norm_gap(prog["grad"], ref["grad"], keep),
        "sync_gap": norm_gap(prog["sync"], ref["sync"], keep),
        "change_gap": norm_gap(prog["change"], ref["change"], keep),
    }


def limits(workload: str) -> dict:
    return json.loads((LIMITS_DIR / f"{workload}.json").read_text())["limits"]


def judge(numbers: dict, lim: dict) -> bool:
    """Every number finite and within its limit."""
    return all(np.isfinite(numbers[k]) and numbers[k] <= lim[k]
               for k in NUMBERS)
