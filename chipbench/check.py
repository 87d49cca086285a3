"""The comparison that decides ``correct`` for a training cell.

The program's first calls of the timed program (made in set-up, before
the window) are set against the plain reference following the same
calls. ``compare`` reads these gaps:

* ``loss_gap.call1``: the relative gap in the first call's training
  loss (the mean over clinics of the round's last local step);
  ``loss_gap``: the widest over the calls;
* ``change_gap.call<i>``: after call i, the worst leaf's gap between
  the norms of the parameters' change since the start, program against
  reference, over the larger of that leaf's reference norm and the
  median leaf's; ``median_change_gap.call<i>``: the median leaf's;
* ``kept_change_gap.call1`` and ``median_kept_change_gap.call1``: the
  same after call 1 over the clinics kept: those whose cluster after
  the brain storm holds the same clinics in the program and in the
  reference. A near tie (in k-means, or in a val accuracy that picks a
  center) can part a sound run's clusters from the reference's; the
  clinics it moves, and their clusters, are left out, and the rest
  still read the local phase, eval, stats, k-means, brain storm and
  Eq. 2 of round 1. With no clinic kept the gap is infinite.
  Leaves whose first gradient in the reference is under a thousandth
  of the median leaf's are left out: they move by round-off alone.

The cell's ``chipbench/limits/<cell>.json`` names the gaps it compares
and their limits (PERF.md gives the readings each was set from); the
others are printed, not compared. ``diagnostics`` adds ``val_gap``, the
widest absolute gap in a call's mean val accuracy, and the number of
clinics whose cluster after the brain storm differs, in each call.
"""
from __future__ import annotations

import numpy as np

STILL_LEAF = 1e-3


def leaf_norms(params: dict, p0: dict, rows=None) -> dict:
    """Per leaf: the norm of the change since ``p0``, over the clinics
    (axis 0) in ``rows`` (a mask; all where None)."""
    out = {}
    for k in p0:
        d = np.asarray(params[k], np.float64) - np.asarray(p0[k], np.float64)
        out[k] = float(np.linalg.norm(d if rows is None else d[rows]))
    return out


def leaf_gaps(prog: dict, ref: dict, p0: dict, grad_norms: dict,
              rows=None) -> dict:
    """Per moving leaf: the gap between the norms of the change since
    ``p0`` (``leaf_norms``), over the larger of the leaf's reference
    norm and the median leaf's."""
    med_g = float(np.median(list(grad_norms.values())))
    moving = [k for k in p0 if grad_norms[k] >= STILL_LEAF * med_g]
    n_prog, n_ref = leaf_norms(prog, p0, rows), leaf_norms(ref, p0, rows)
    med = float(np.median([n_ref[k] for k in moving]))
    return {k: abs(n_prog[k] - n_ref[k]) / max(n_ref[k], med)
            for k in moving}


def same_clusters(a, b) -> np.ndarray:
    """Mask of the clinics whose cluster holds the same clinics in the
    assignments ``a`` and ``b`` (labels may differ)."""
    a, b = np.asarray(a), np.asarray(b)
    return np.array([np.array_equal(a == a[i], b == b[i])
                     for i in range(len(a))])


def _loss_gaps(prog: dict, ref: dict) -> np.ndarray:
    lp, lr = (np.asarray(x["losses"], np.float64) for x in (prog, ref))
    return np.abs(lp - lr) / lr


def compare(prog: dict, ref: dict, calls_checked: tuple) -> dict:
    """``prog`` and ``ref`` each hold ``losses`` and ``val`` (one number
    a call), ``params`` (one path->array dict a call) and ``assign``
    (each call's clusters); ``ref`` also ``p0`` and ``grad_norms``. Returns name -> gap; a non-finite reading
    gives an infinite gap."""
    call_gaps = _loss_gaps(prog, ref)
    gaps = {"loss_gap": float(np.max(call_gaps)),
            "loss_gap.call1": float(call_gaps[0])}
    for i in calls_checked:
        leaves = leaf_gaps(prog["params"][i - 1], ref["params"][i - 1],
                           ref["p0"], ref["grad_norms"])
        gaps[f"change_gap.call{i}"] = max(leaves.values())
        gaps[f"median_change_gap.call{i}"] = float(
            np.median(list(leaves.values())))
    kept = same_clusters(prog["assign"][0], ref["assign"][0])
    gaps["kept_change_gap.call1"] = gaps["median_kept_change_gap.call1"] = (
        float("inf"))
    if kept.any():
        leaves = leaf_gaps(prog["params"][0], ref["params"][0], ref["p0"],
                           ref["grad_norms"], kept)
        gaps["kept_change_gap.call1"] = max(leaves.values())
        gaps["median_kept_change_gap.call1"] = float(
            np.median(list(leaves.values())))
    return {k: v if np.isfinite(v) else float("inf") for k, v in gaps.items()}


def diagnostics(prog: dict, ref: dict) -> dict:
    vp, vr = (np.asarray(x["val"], np.float64) for x in (prog, ref))
    return {"val_gap": float(np.max(np.abs(vp - vr))),
            "clinics_moved": [int(np.sum(np.asarray(a) != np.asarray(b)))
                              for a, b in zip(prog["assign"], ref["assign"])]}


def detail(prog: dict, ref: dict) -> dict:
    """Every call's loss gap and every leaf's change gap, per call."""
    return {"loss_gaps": _loss_gaps(prog, ref).tolist(),
            "leaf_gaps": [leaf_gaps(p, r, ref["p0"], ref["grad_norms"])
                          for p, r in zip(prog["params"], ref["params"])]}


def judge(gaps: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) over the numbers that have
    a limit; a limit with no such number is a fault of the benchmark
    and raises."""
    if not limits or set(limits) - set(gaps):
        raise ValueError(f"limits {sorted(limits)} vs numbers {sorted(gaps)}")
    checks = {k: {"value": gaps[k], "limit": limits[k]}
              for k in sorted(limits)}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
