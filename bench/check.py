"""The comparison that decides ``correct``: what the timed window produced
against the plain reference (``bench/reference.py``) replaying the same
posted events.

Numbers compared, each with its limit:

* ``records_mismatched``: sink records of the program and of the
  reference that do not pair up as a multiset of (round, stream, ts,
  ingest stamp, value bits); exact, limit 0;
* ``state_mismatched``: stream rows whose final last value (bits) or
  timestamp differ; exact, limit 0;
* ``dropped``: the events the engine dropped, the deltas of its
  ``dropped_*`` counters; limit 0, since the configurations guarantee
  that no event is dropped.  A drop also takes its event out of the
  latency samples, so a run that drops could read faster than one that
  does not.  The reference's bounded queue counts its own drops, printed
  beside it as a diagnostic;
* ``agg_err``: the largest error of a window aggregate (sum, mean, max,
  min, count of a STATS stream's last emissions) as read in the window,
  relative to the sum of the absolute values it aggregates (count: to 1).
  The reference sums in float64, so sound float32 sums in another order
  read a few units of float32 rounding; the limit lies between those
  readings and the bfloat16 control's (``PERF.md`` gives both).
"""
from __future__ import annotations

import collections
from typing import Dict, List, Optional

import numpy as np

LIMITS = {"records_mismatched": 0, "state_mismatched": 0,
          "dropped": 0,
          "agg_err": 1e-3}


def record_rows(rec: dict) -> np.ndarray:
    """(n, 4 + C) int64 rows: round, sid, ts, its, value bits."""
    bits = np.ascontiguousarray(rec["vals"], np.float32).view(np.int32)
    return np.concatenate([
        np.stack([np.asarray(rec[k], np.int64)
                  for k in ("round", "sid", "ts", "its")], axis=1),
        bits.astype(np.int64)], axis=1)


def multiset_diff(a: np.ndarray, b: np.ndarray) -> int:
    """Rows of ``a`` and ``b`` (each a multiset) that do not pair up."""
    if a.shape == b.shape:
        sa = a[np.lexsort(a.T[::-1])]
        sb = b[np.lexsort(b.T[::-1])]
        if np.array_equal(sa, sb):
            return 0
    ca = collections.Counter(map(bytes, np.ascontiguousarray(a)))
    cb = collections.Counter(map(bytes, np.ascontiguousarray(b)))
    return sum(((ca - cb) + (cb - ca)).values())


def agg_error(prog: List[dict], ref: List[dict]) -> float:
    err = 0.0
    for p, r in zip(prog, ref, strict=True):
        scale = np.maximum(r["abs"], 1e-30)
        for k in ("sum", "mean", "max", "min"):
            den = scale if k == "sum" else \
                scale / np.maximum(r["count"], 1.0)
            err = max(err, float(np.max(
                np.abs(np.asarray(p[k], np.float64) - r[k]) / den,
                initial=0.0)))
        err = max(err, float(np.max(np.abs(
            np.asarray(p["count"], np.float64) - r["count"]), initial=0.0)))
    return err


def compare(prog_records: dict, prog_values: np.ndarray,
            prog_stamps: np.ndarray, dropped: int, ref,
            prog_aggs: Optional[List[dict]] = None,
            ref_aggs: Optional[List[dict]] = None) -> Dict[str, dict]:
    """Every number compared, each as ``{"value", "limit"}``."""
    vals = np.asarray(prog_values, np.float32)
    rows_p = record_rows(prog_records)
    rows_r = record_rows(ref.records)
    state = (np.any(vals.view(np.int32) != ref.values.view(np.int32), axis=1)
             | (np.asarray(prog_stamps, np.int64) != ref.timestamps))
    out = {
        "records_mismatched": multiset_diff(rows_p, rows_r),
        "state_mismatched": int(state.sum()),
        "dropped": int(dropped),
    }
    if prog_aggs is not None and ref_aggs is not None:
        out["agg_err"] = agg_error(prog_aggs, ref_aggs)
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in out.items()}


def passed(checks: Dict[str, dict], n_records: int) -> bool:
    return n_records > 0 and all(c["value"] <= c["limit"]
                                 for c in checks.values())
