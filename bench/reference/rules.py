"""M-DSL's discrete rules, shared by the plain references: Eq. 6's
selection (with the single-best fallback) and the best-model updates of
Eqs. 9-10.

Given the program's readings of the same round (`prog`), a decision that
differs from the program's is a near tie, and the program's is adopted,
only where both of these hold:
  * the program's own reading of the compared value lies on the side of
    the threshold that its decision took, so the two sides differ in the
    value, not in the rule;
  * the reference's value lies within `band` (relative) of the
    threshold. The band is the cell's `loss` limit: the gap of a
    computed loss that the check allows.
Every other difference counts in `log["disagree"]`; `log["adopted"]`
and `log["adopted_gap"]` record the near ties taken."""
from __future__ import annotations

import numpy as np


def _rel_gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    with np.errstate(invalid="ignore", divide="ignore"):
        gap = np.abs(a - b) / np.abs(b)
    return np.where(np.isfinite(b), gap, np.inf)


def new_log() -> dict:
    return {"disagree": 0, "adopted": 0, "adopted_gap": 0.0}


def _adopt(log, tie, differ, gap):
    taken = tie & differ
    log["adopted"] += int(np.sum(taken))
    if np.any(taken):
        log["adopted_gap"] = max(log["adopted_gap"],
                                 float(np.max(np.where(taken, gap, 0.0))))


def decide(value, threshold, prog_decision, prog_value, band, log):
    """value < threshold, elementwise, with near ties adopted.
    `prog_value` None: the program's value is not read (any side)."""
    value = np.asarray(value, np.float64)
    raw = value < np.asarray(threshold, np.float64)
    if prog_decision is None:
        return raw
    prog = np.asarray(prog_decision).astype(bool)
    gap = _rel_gap(value, threshold)
    tie = gap <= band
    if prog_value is not None:
        tie &= (np.asarray(prog_value, np.float64) < threshold) == prog
    _adopt(log, tie, raw != prog, gap)
    log["disagree"] += int(np.sum((raw != prog) & ~tie))
    return np.where(tie, prog, raw)


def select(theta, prev_mean, prog_mask, prog_theta, band, log, *,
           invert=False):
    """Eq. 6: every worker with theta <= the previous round's mean; if
    none, the single best. Returns the (C,) float32 mask. `invert`
    plants the inverted rule (a fault of the check)."""
    theta = np.asarray(theta, np.float64)
    C = theta.shape[0]
    raw = (theta > prev_mean) if invert else (theta <= prev_mean)
    prog = None if prog_mask is None else np.asarray(prog_mask) > 0
    if prog is not None:
        gap = _rel_gap(theta, np.full(C, prev_mean))
        tie = gap <= band
        if prog_theta is not None:
            tie &= (np.asarray(prog_theta, np.float64) <= prev_mean) == prog
        _adopt(log, tie, raw != prog, gap)
        raw = np.where(tie, prog, raw)
    if raw.sum() == 0:
        best = int(np.argmin(theta))
        if prog is not None and prog.sum() == 1:
            pick = int(np.argmax(prog))
            gap = float(_rel_gap(theta[pick], theta[best]))
            own = (prog_theta is None
                   or prog_theta[pick] <= np.min(prog_theta))
            if pick != best and gap <= band and own:
                _adopt(log, np.bool_(True), np.bool_(True), gap)
                best = pick
        raw = np.arange(C) == best
    if prog is not None:
        log["disagree"] += int(np.sum(raw != prog))
    return raw.astype(np.float32)
