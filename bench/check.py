"""The comparison that decides `correct`: the program's checked rounds
against the plain reference, each round from the same starting state.

Every number is a gap between the two sides' readings of one round (see
`reference/*.py` for what a reading holds), the largest over the checked
rounds. A cell compares the numbers its `check.limits` names:

  loss         |F_prog - F_ref| / |F_ref| of the global model on D_g
  worker_loss  the median over workers of the same gap of F_{i,t+1}
               (the widest worker swings from seed to seed by nature)
  update       worst leaf: | |v_prog| - |v_ref| | of the workers'
               velocity (round 1: the local update itself), against the
               reference's norm of that leaf or of the median leaf,
               whichever is larger
  change       the same for the change of the global model in the round
  best         the same for the change of the workers' best models (Eq. 9)
  gbest        the same for the change of the global best model (Eq. 10)
  residual     the same for the change of the workers' uplink
               error-feedback residuals
  ps_residual  the same for the change of the downlink residual, by the
               median leaf: a residual of the int4 uplink's rounding,
               whose small leaves swing with each flipped uplink element
  decisions    selections and best-model updates that differ, other than
               near ties (`reference/rules.py`)

Leaves whose reference velocity is under a thousandth of the median
leaf's do not count: they move by round-off alone.
"""
from __future__ import annotations

import math

import numpy as np

NEGLIGIBLE = 1e-3
# numbers read as the change of a carried state: name -> key of `changes`
CHANGES = {"change": "global", "best": "best", "gbest": "gbest",
           "residual": "residual", "ps_residual": "ps_residual"}
# numbers taken at the median leaf rather than the worst
MEDIAN_LEAF = {"ps_residual"}


def _rel(p, r) -> float:
    p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
    if not (np.all(np.isfinite(p)) and np.all(np.isfinite(r))):
        return math.inf
    return float(np.max(np.abs(p - r) / np.maximum(np.abs(r), 1e-30)))


def _rel_median(p, r) -> float:
    p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
    if not (np.all(np.isfinite(p)) and np.all(np.isfinite(r))):
        return math.inf
    return float(np.median(np.abs(p - r) / np.maximum(np.abs(r), 1e-30)))


def leaf_gap(prog: list, ref: list, counted: list, reduce=max) -> float:
    """Worst (or `reduce`d) counted leaf's gap of norms, against
    max(leaf, median)."""
    p, r = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if not (np.all(np.isfinite(p)) and np.all(np.isfinite(r))):
        return math.inf
    idx = [i for i in range(len(r)) if counted[i]]
    med = float(np.median(r[idx])) if idx else 0.0
    if med == 0.0:
        return 0.0 if np.all(p[idx] == 0.0) else math.inf
    return float(reduce([abs(p[i] - r[i]) / max(r[i], med) for i in idx]))


def counted_leaves(ref_velocity: list) -> list:
    v = np.asarray(ref_velocity, np.float64)
    med = float(np.median(v))
    return [bool(x >= NEGLIGIBLE * med) for x in v]


def compare(prog_rounds: list, ref_rounds: list) -> dict:
    """Numbers of the check over the checked rounds (dicts of readings);
    a state neither side carries gives no number."""
    out = {"loss": 0.0, "worker_loss": 0.0, "update": 0.0, "decisions": 0.0}
    for p, r in zip(prog_rounds, ref_rounds):
        counted = counted_leaves(r["velocity_norms"])
        out["loss"] = max(out["loss"], _rel(p["global_loss"], r["global_loss"]))
        out["worker_loss"] = max(out["worker_loss"],
                                 _rel_median(p["losses"], r["losses"]))
        out["update"] = max(out["update"], leaf_gap(
            p["velocity_norms"], r["velocity_norms"], counted))
        for name, key in CHANGES.items():
            if key in r["changes"]:
                out[name] = max(out.get(name, 0.0), leaf_gap(
                    p["changes"].get(key, [math.nan]), r["changes"][key],
                    counted, np.median if name in MEDIAN_LEAF else max))
        out["decisions"] += float(r["disagree"])
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers `limits`
    names. A number not read, or a value that is not finite, is not
    correct."""
    checks, ok = {}, True
    for name, lim in limits.items():
        v = numbers.get(name)
        good = v is not None and math.isfinite(v) and v <= lim
        ok = ok and good
        checks[name] = {"value": v if v is None or math.isfinite(v) else
                        str(v), "limit": lim}
    return ok, checks
