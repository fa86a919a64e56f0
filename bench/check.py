"""The comparison that decides ``correct``.

The timed path's outputs (its worker states after the last ingest call
and the prequential bits of every call) against the plain reference's
(``srgrid.replay`` with ``reference/<algorithm>.py``). Four numbers,
each against a limit the configuration file states:

* ``state_mismatch``: entries of the exactly compared state leaves
  (ids, counters, clocks, rated bits, and counts held in float32) that
  differ, a leaf of another shape counting whole;
* ``factor_max_abs_diff``: the largest absolute gap over the leaves the
  reference names in ``FLOAT_LEAVES`` (float32 factors);
* ``eval_mismatch``: slots of the prequential bit rows whose "was an
  event evaluated here" differs, which checks routing, buckets and
  re-queue event for event;
* ``hit_mismatch``: hit bits that differ, over the rows drawn for the
  check, among the events whose hit the reference decides by a score
  margin above the configuration's ``tie_tolerance`` (``hits_checked``
  bits compared, a check with none fails; ``near_ties`` events left out:
  float32 rounding, which moves scores far less than the tolerance, can
  decide those either way).
"""

from __future__ import annotations

import numpy as np

__all__ = ["NUMBERS", "compare", "verdict", "program_rows"]

NUMBERS = ("state_mismatch", "factor_max_abs_diff", "eval_mismatch",
           "hit_mismatch")


def program_rows(bits: np.ndarray, width: int) -> np.ndarray:
    """The program's concatenated prequential bits as ``[rows, width]``
    (NaN: no event evaluated in the slot; else the hit bit)."""
    return np.asarray(bits, np.float64).reshape(-1, width)


def compare(states: dict, ref_states: dict, float_leaves, rows: np.ndarray,
            ref, tie_tolerance: float) -> dict:
    """``states``/``ref_states``: named host arrays; ``rows``: the
    program's bit rows; ``ref``: an ``srgrid.Replay``."""
    mismatch, gap = 0, 0.0
    for name, want in ref_states.items():
        got = states.get(name)
        if got is None or got.shape != want.shape:
            mismatch += want.size
            continue
        if name in float_leaves:
            got = np.asarray(got, np.float64)
            d = np.abs(got - np.asarray(want, np.float64))
            gap = max(gap, float(np.max(d)) if d.size else 0.0)
            if not np.all(np.isfinite(got)):
                gap = float("inf")
        else:
            # Part by part along the first axis, to bound the temporaries;
            # in float64 where the two sides hold different types.
            same = got.dtype == want.dtype
            mismatch += sum(int(np.count_nonzero(
                g != w if same else np.asarray(g, np.float64)
                != np.asarray(w, np.float64)))
                for g, w in zip(np.atleast_1d(got), np.atleast_1d(want)))
    extra = set(states) - set(ref_states)
    mismatch += sum(states[n].size for n in extra)

    ev = ref.evaluated
    k = min(rows.shape[0], ev.shape[0])
    eval_mismatch = abs(rows.size - ev.size) + int(np.count_nonzero(
        ~np.isnan(rows[:k]) != ev[:k]))
    hit_mismatch = checked = near = 0
    for r, want in ref.hits.items():
        if r >= rows.shape[0]:
            continue
        both = ev[r] & ~np.isnan(rows[r])
        firm = both & (ref.margins[r] > tie_tolerance)
        checked += int(firm.sum())
        near += int((both & ~firm).sum())
        hit_mismatch += int(np.count_nonzero((rows[r][firm] == 1)
                                             != want[firm]))
    return {"state_mismatch": mismatch, "factor_max_abs_diff": gap,
            "eval_mismatch": eval_mismatch, "hit_mismatch": hit_mismatch,
            "hits_checked": checked, "near_ties": near}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and ``{name: {"value": v, "limit": l}}`` in order."""
    out = {n: {"value": numbers[n], "limit": limits[n]} for n in NUMBERS}
    ok = all(numbers[n] <= limits[n] for n in NUMBERS)
    ok = ok and numbers["hits_checked"] > 0
    out["hits_checked"] = {"value": numbers["hits_checked"], "min": 1}
    out["near_ties"] = {"value": numbers["near_ties"]}
    return ok, out
