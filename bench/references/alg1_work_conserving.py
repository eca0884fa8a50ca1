"""Plain reference of the scheduler under test: the paper's Algorithm 1.

Written from the paper's description (Section IV, "Scheduling Coflows in
Multi-Core OCS Networks with Performance Guarantee"), with no code of the
program under test:

1. order the coflows by WSPT on the global lower bound,
   ``w_m / (delta + rho_m / R)``, ties to the lower index;
2. list every nonzero flow, coflow by coflow in that order, the largest
   first (ties by ingress, then egress port);
3. assign each flow, in that order, to the core whose per-core lower bound
   ``max_p(load_p / r_k + tau_p * delta)`` would be least with it (ties to
   the lowest core), counting a (i, j) pair into ``tau`` once per core;
4. on each core, a work-conserving list schedule: whenever a port frees,
   scan the core's pending flows in priority order and establish every
   flow whose ingress and egress are both idle; a circuit holds both ports
   for ``delta + size / r_k``.

Step 3 reads the sizes, rates and delay as float32, the input precision of
the scheduler's assignment stage. Each step computes in the precision it is
given: ``"float64"`` is the reference; ``"float32"`` is the control, one
precision below what the configuration states (each operation rounded to
float32, which float64 arithmetic followed by one rounding gives exactly).
"""
from __future__ import annotations

import heapq

import numpy as np

PRECISIONS = ("float64", "float32")


def rounding(precision: str):
    """A Python float rounded to ``precision``."""
    if precision == "float64":
        return float
    if precision == "float32":
        return lambda x: float(np.float32(x))
    raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")


def order(demands: np.ndarray, weights: np.ndarray, rates: np.ndarray,
          delta: float, precision: str = "float64") -> np.ndarray:
    """Step 1: coflow indices in non-increasing ``w / (delta + rho / R)``."""
    dt = np.dtype(precision).type
    total_rate = dt(np.sum(np.asarray(rates, dt)))
    scores = np.empty(len(demands))
    for m, d in enumerate(demands):
        d = np.asarray(d, dt)
        rho = max(d.sum(axis=1).max(), d.sum(axis=0).max())
        lb = dt(delta) + rho / total_rate if rho > 0 else dt(0)
        scores[m] = dt(weights[m]) / lb if lb > 0 else np.inf
    return np.argsort(-scores, kind="stable")


def flows(demands: np.ndarray, pi: np.ndarray) -> tuple[np.ndarray, ...]:
    """Step 2: ``(coflow, i, j, size)`` of every flow in priority order."""
    cols = []
    for m in pi:
        ii, jj = np.nonzero(demands[m])
        sz = demands[m][ii, jj]
        k = np.lexsort((jj, ii, -sz))
        cols.append((np.full(k.size, m), ii[k], jj[k], sz[k]))
    return tuple(np.concatenate(c) for c in zip(*cols))


def assign(fi: np.ndarray, fj: np.ndarray, sizes: np.ndarray,
           rates: np.ndarray, delta: float, n_ports: int,
           precision: str = "float64") -> np.ndarray:
    """Step 3: the tau-aware greedy; returns each flow's core."""
    rnd = rounding(precision)
    n_cores = len(rates)
    cores = range(n_cores)
    r = [rnd(x) for x in np.asarray(rates, np.float32).tolist()]
    dl = rnd(float(np.float32(delta)))
    d_in = [rnd(x) for x in np.asarray(sizes, np.float32).tolist()]
    row = [[0.0] * n_cores for _ in range(n_ports)]
    col = [[0.0] * n_cores for _ in range(n_ports)]
    row_tau = [[0] * n_cores for _ in range(n_ports)]
    col_tau = [[0] * n_cores for _ in range(n_ports)]
    seen: set[tuple[int, int, int]] = set()
    bound = [0.0] * n_cores
    out = np.empty(len(fi), np.int64)
    for t, (i, j) in enumerate(zip(fi.tolist(), fj.tolist())):
        d = d_in[t]
        ri, cj, ti, tj = row[i], col[j], row_tau[i], col_tau[j]
        best, kb, new_kb = 0.0, -1, 0
        for k in cores:
            new = 0 if (i, j, k) in seen else 1
            # tau counts are small integers: (tau + new) * delta is exact
            li = rnd(rnd((ri[k] + d)) / r[k]) + (ti[k] + new) * dl
            lj = rnd(rnd((cj[k] + d)) / r[k]) + (tj[k] + new) * dl
            cand = max(bound[k], rnd(li), rnd(lj))
            if kb < 0 or cand < best:
                best, kb, new_kb = cand, k, new
        out[t] = kb
        ri[kb] = rnd(ri[kb] + d)
        cj[kb] = rnd(cj[kb] + d)
        ti[kb] += new_kb
        tj[kb] += new_kb
        seen.add((i, j, kb))
        bound[kb] = best
    return out


def schedule_core(fi: np.ndarray, fj: np.ndarray, sizes: np.ndarray,
                  rate: float, delta: float, n_ports: int,
                  precision: str = "float64"
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Step 4 on one core; flows in priority order; returns
    ``(t_establish, t_complete)``.

    A flow can only become startable at a moment when one of its ports
    frees, so at each event only the pending flows of the freed ports are
    candidates. Scanning them in priority order and establishing each that
    can start is the same as establishing, again and again, the first in
    priority order that can start: a candidate passed over stays blocked,
    since a scan only takes ports. That first one is found from the pending
    flows of each (ingress, egress) pair whose other port is idle.
    """
    rnd = rounding(precision)
    n = len(fi)
    fi, fj = fi.tolist(), fj.tolist()
    dl, rate = rnd(delta), rnd(rate)
    srv = [rnd(rnd(s) / rate) for s in np.asarray(sizes, np.float64).tolist()]
    pair: dict[tuple[int, int], list[int]] = {}     # pending, reversed
    for f in range(n - 1, -1, -1):
        pair.setdefault((fi[f], fj[f]), []).append(f)
    # partner[side][p]: the ports that p has a pending flow with
    partner = [[set() for _ in range(n_ports)], [set() for _ in range(n_ports)]]
    for i, j in pair:
        partner[0][i].add(j)
        partner[1][j].add(i)
    idle = [set(range(n_ports)), set(range(n_ports))]
    t_est = [-1.0] * n
    t_end = [-1.0] * n
    frees: dict[float, list] = {}   # time -> ports (side, port) freed then
    events: list[float] = []

    def first_startable(ports) -> int:
        best = n
        for side, p in ports:
            if p not in idle[side]:
                continue
            mates, other = partner[side][p], idle[1 - side]
            for q in (mates & other if len(mates) < len(other)
                      else other & mates):
                key = (p, q) if side == 0 else (q, p)
                best = min(best, pair[key][-1])
        return best

    def start(f: int, t: float) -> None:
        i, j = fi[f], fj[f]
        tc = rnd(rnd(t + dl) + srv[f])
        t_est[f], t_end[f] = t, tc
        idle[0].discard(i)
        idle[1].discard(j)
        lst = pair[(i, j)]
        lst.pop()
        if not lst:
            partner[0][i].discard(j)
            partner[1][j].discard(i)
        if tc not in frees:
            frees[tc] = []
            heapq.heappush(events, tc)
        frees[tc] += ((0, i), (1, j))

    for f in range(n):          # time 0: every flow is a candidate
        if fi[f] in idle[0] and fj[f] in idle[1]:
            start(f, 0.0)
    while events:
        t = heapq.heappop(events)
        ports = frees.pop(t)
        for side, p in ports:
            idle[side].add(p)
        while (f := first_startable(ports)) < n:
            start(f, t)
    return np.asarray(t_est), np.asarray(t_end)


def schedule(core: np.ndarray, fi: np.ndarray, fj: np.ndarray,
             sizes: np.ndarray, rates: np.ndarray, delta: float,
             n_ports: int, precision: str = "float64"
             ) -> tuple[np.ndarray, np.ndarray]:
    """Step 4 on every core: ``(t_establish, t_complete)`` per flow."""
    t_est, t_end = np.empty(len(fi)), np.empty(len(fi))
    for k in range(len(rates)):
        idx = np.nonzero(core == k)[0]
        t_est[idx], t_end[idx] = schedule_core(
            fi[idx], fj[idx], sizes[idx], float(rates[k]), delta, n_ports,
            precision)
    return t_est, t_end


def solve(demands: np.ndarray, weights: np.ndarray, rates: np.ndarray,
          delta: float, assign_precision: str = "float64",
          schedule_precision: str = "float64") -> dict[str, np.ndarray]:
    """The whole of Algorithm 1 on one backlog, flow by flow."""
    rates = np.asarray(rates, np.float64)
    n_ports = demands.shape[1]
    pi = order(demands, weights, rates, delta, schedule_precision)
    cf, fi, fj, sz = flows(demands, pi)
    core = assign(fi, fj, sz, rates, delta, n_ports, assign_precision)
    t_est, t_end = schedule(core, fi, fj, sz, rates, delta, n_ports,
                            schedule_precision)
    return dict(coflow=cf, fi=fi, fj=fj, size=sz, core=core,
                t_establish=t_est, t_complete=t_end)
