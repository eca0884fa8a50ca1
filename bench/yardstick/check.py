"""The comparison that decides ``correct`` for a scheduling request.

Each request's answer is a circuit program: per flow, the core it was put
on and the interval ``[t_establish, t_complete)`` in which its circuit holds
the ingress and egress port of that core. Three numbers are compared, each
beside its own limit (set in the configuration's file from readings of the
program and of the control; PERF.md gives them):

- ``choices_differing``: flows whose core differs from the plain
  reference's assignment (the kernel's layer);
- ``referee_violations``: breaches found by the plain referee below (the
  per-core circuit schedule): a flow missing, repeated, resized or
  unknown, a circuit before time 0, a circuit that does not last exactly
  ``delta + size / rate``, or two circuits that hold one port of one core
  at once;
- ``wcct_rel_gap``: the largest relative gap, over the requests compared,
  between the program's weighted coflow completion time and the
  reference's (the emitted schedule as a whole).
"""
from __future__ import annotations

import numpy as np

#: Relative slack on time equalities: the program and the referee compute
#: ``t + delta + size / rate`` alike, so any real breach is far larger.
TIME_TOL = 1e-9

SEGMENT_FIELDS = ("core", "ingress", "egress", "cid", "size",
                  "t_establish", "t_complete")


def referee(demands: np.ndarray, seg: dict, rates: np.ndarray,
            delta: float) -> int:
    """Count of breaches of the request's guarantees in ``seg``."""
    n_coflows, n_ports, _ = demands.shape
    rates = np.asarray(rates, np.float64)
    core, fi, fj, cid = (np.asarray(seg[k], np.int64)
                         for k in ("core", "ingress", "egress", "cid"))
    size, t0, t1 = (np.asarray(seg[k], np.float64)
                    for k in ("size", "t_establish", "t_complete"))
    ok = ((core >= 0) & (core < len(rates)) & (fi >= 0) & (fi < n_ports)
          & (fj >= 0) & (fj < n_ports) & (cid >= 0) & (cid < n_coflows))
    bad = int((~ok).sum())
    core, fi, fj, cid, size, t0, t1 = (a[ok] for a in
                                       (core, fi, fj, cid, size, t0, t1))
    key = (cid * n_ports + fi) * n_ports + fj
    want = np.flatnonzero(demands)
    got, counts = np.unique(key, return_counts=True)
    bad += int((counts - 1).sum())                      # repeated
    bad += int(np.setdiff1d(want, got).size)            # missing
    known = np.isin(key, want)
    bad += int((~known).sum())                          # unknown
    bad += int((size[known] != demands.reshape(-1)[key[known]]).sum())
    bad += int((t0 < 0).sum())
    span = np.maximum(1.0, np.abs(t1))
    bad += int((np.abs((t1 - t0) - (delta + size / rates[core]))
                > TIME_TOL * span).sum())
    for port in (fi, fj):
        rid = core * n_ports + port
        order = np.lexsort((t0, rid))
        same = rid[order][1:] == rid[order][:-1]
        early = t0[order][1:] < t1[order][:-1] - TIME_TOL * span[order][:-1]
        bad += int((same & early).sum())
    return bad


def weighted_cct(n_coflows: int, weights: np.ndarray, cid: np.ndarray,
                 t_complete: np.ndarray) -> float:
    ccts = np.zeros(n_coflows)
    np.maximum.at(ccts, np.asarray(cid, np.int64), t_complete)
    return float(np.dot(weights, ccts))


def compare(demands: np.ndarray, weights: np.ndarray, seg: dict,
            ref: dict, rates: np.ndarray, delta: float) -> dict:
    """The three numbers of one request against the reference's answer."""
    n_coflows, n_ports, _ = demands.shape
    ref_key = (ref["coflow"] * n_ports + ref["fi"]) * n_ports + ref["fj"]
    ref_core = dict(zip(ref_key.tolist(), ref["core"].tolist()))
    key = (np.asarray(seg["cid"], np.int64) * n_ports
           + np.asarray(seg["ingress"], np.int64)) * n_ports \
        + np.asarray(seg["egress"], np.int64)
    got_core = dict(zip(key.tolist(), np.asarray(seg["core"]).tolist()))
    differing = sum(got_core.get(k, -1) != c for k, c in ref_core.items())
    w_ref = weighted_cct(n_coflows, weights, ref["coflow"], ref["t_complete"])
    valid = (np.asarray(seg["cid"]) >= 0) & (np.asarray(seg["cid"]) < n_coflows)
    w_got = weighted_cct(n_coflows, weights, np.asarray(seg["cid"])[valid],
                         np.asarray(seg["t_complete"])[valid])
    return dict(choices_differing=int(differing),
                referee_violations=referee(demands, seg, rates, delta),
                wcct_rel_gap=abs(w_got - w_ref) / w_ref)


def reduce(per_request: list[dict]) -> dict:
    """One run's numbers from its requests' comparisons."""
    if not per_request:
        return dict(choices_differing=0, referee_violations=0,
                    wcct_rel_gap=0.0)
    return dict(
        choices_differing=sum(r["choices_differing"] for r in per_request),
        referee_violations=sum(r["referee_violations"] for r in per_request),
        wcct_rel_gap=max(r["wcct_rel_gap"] for r in per_request))


def verdict(numbers: dict, limits: dict, compared: int,
            failed: int) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``: every number within its
    limit, no request failed, and at least one request compared."""
    shown = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    shown["requests_failed"] = {"value": failed, "limit": 0}
    ok = compared > 0 and failed == 0 and all(
        numbers[k] <= limits[k] for k in limits)
    return ok, shown


def reference_segments(solved: dict) -> dict:
    """A reference answer in the program's segment layout (the control)."""
    return dict(core=solved["core"], ingress=solved["fi"],
                egress=solved["fj"], cid=solved["coflow"],
                size=solved["size"], t_establish=solved["t_establish"],
                t_complete=solved["t_complete"])
