"""One-shot plane, closed loop: one client asks for a circuit program for a
whole offline backlog, waits for it, and asks again.

This is an operator's controller that batches the coflows of an epoch and
calls ``FabricManager.schedule_instance(inst, backend="pallas")`` once per
epoch; every coflow of a backlog is released at time 0.

The mix names ``shapes`` backlog shapes: those that the paper's sampling
(``fbtrace.sample_backlog``) picks with pool seeds ``pool_seed`` onwards,
each ``coflows`` coflows on some racks, so each with its own flow count.
Every seed serves the same shapes: the window takes them in cycles, each
cycle in an order drawn from ``--seed``, and request ``r`` draws its own
numbers (sender shares, weights, which port each rack becomes) from
``--seed`` and ``r``. No backlog repeats, so every request misses the
program cache. Set-up serves each shape once, with numbers of its own, so
the program has compiled everything for those flow counts before the
window: the window measures requests of known shapes, warm, and not the
compile that a backlog of a new flow count costs. The window serves
requests until the first one that completes at or after ``--seconds``.
Afterwards every completed request's program is compared with the plain
reference (``yardstick.check``).
"""
from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from yardstick import check, fbtrace

#: Streams of random numbers drawn from the seed.
WINDOW, WARMUP, ORDER = 0, 1, 2


def _rng(seed: int, stream: int, r: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([seed % 2**64, stream, r]))


class Requests:
    """The backlogs of one cell, built on demand."""

    def __init__(self, cfg: dict, mix: dict) -> None:
        tr = cfg["trace"]
        self.trace = fbtrace.synth_fb_trace(tr["coflows"], seed=tr["seed"])
        self.cfg = cfg
        self.weight_range = tuple(cfg["weight_range"])
        self.shapes = []            # (selected racks, picked coflows)
        for k in range(mix["shapes"]):
            _, selected, pick = fbtrace.sample_backlog(
                self.trace, n_ports=cfg["ports"], n_coflows=mix["coflows"],
                seed=mix["pool_seed"] + k, weight_range=self.weight_range)
            self.shapes.append((selected, pick))

    def backlog(self, shape: int, rng: np.random.Generator):
        selected, pick = self.shapes[shape]
        return fbtrace.redraw_backlog(self.trace, selected, pick, rng,
                                      self.weight_range)

    def warmup(self, k: int):
        """The set-up's request of shape ``k``."""
        return self.backlog(k, _rng(0, WARMUP, k))

    def window(self, seed: int, r: int):
        """The window's request ``r`` under ``seed``."""
        n = len(self.shapes)
        cycle = _rng(seed, ORDER, r // n).permutation(n)
        return self.backlog(int(cycle[r % n]), _rng(seed, WINDOW, r))

    def instance(self, backlog):
        from repro.core.coflow import Coflow, Instance

        coflows = tuple(Coflow(cid=m, demand=d, weight=float(w))
                        for m, (d, w) in enumerate(zip(backlog.demands,
                                                       backlog.weights)))
        return Instance(coflows=coflows,
                        rates=np.asarray(self.cfg["rates"], np.float64),
                        delta=float(self.cfg["delta"]))


def compare(cfg: dict, ref, backlog, seg: dict) -> dict:
    """One request's answer ``seg`` against the plain reference's."""
    rates = np.asarray(cfg["rates"], np.float64)
    delta = float(cfg["delta"])
    solved = ref.solve(backlog.demands, backlog.weights, rates, delta)
    return check.compare(backlog.demands, backlog.weights, seg, solved,
                         rates, delta)


def run(ctx) -> dict:
    from repro.service import FabricConfig, FabricManager

    cfg = ctx.cfg
    reqs = Requests(cfg, ctx.mix)
    ctx.log(f"traffic built at {ctx.since_start():.3f} s")
    mgr = FabricManager(FabricConfig(
        rates=tuple(cfg["rates"]), delta=float(cfg["delta"]),
        N=cfg["ports"], algorithm=cfg["algorithm"],
        scheduling=cfg["scheduling"]))
    for k in range(len(reqs.shapes)):
        mgr.schedule_instance(reqs.instance(reqs.warmup(k)), backend="pallas")
    gc.collect()
    ctx.log(f"{len(reqs.shapes)} warm-up requests served at "
            f"{ctx.since_start():.3f} s")
    ctx.setup_done()

    served: list[tuple] = []    # (backlog, program)
    latency: list[float] = []
    cpu: list[float] = []       # this process's CPU seconds per request
    flows: list[int] = []
    failed = 0
    span = ctx.spans.span if ctx.spans else (lambda _l: contextlib.nullcontext())
    with ctx.window():
        t_open = time.perf_counter()
        r = 0
        while True:
            b = reqs.window(ctx.seed, r)
            inst = reqs.instance(b)
            c0, t0 = time.process_time(), time.perf_counter()
            try:
                with span("request"):
                    program, _hit = mgr.schedule_instance(inst,
                                                          backend="pallas")
            except Exception as e:  # a failed request is counted, not fatal
                ctx.log(f"request {r} failed: {type(e).__name__}: {e}")
                program = None
            t1, c1 = time.perf_counter(), time.process_time()
            r += 1
            if program is None:
                failed += 1
            else:
                served.append((b, program))
                latency.append(t1 - t0)
                cpu.append(c1 - c0)
                flows.append(b.n_flows)
            if t1 - t_open >= ctx.seconds:
                break
        window_s = t1 - t_open
    ctx.window_closed()
    slow = sorted(range(len(latency)), key=latency.__getitem__)[-3:]
    ctx.log("slowest requests (wall s / CPU s / flows): " + ", ".join(
        f"{latency[i]:.4f} / {cpu[i]:.4f} / {flows[i]}" for i in slow)
        + f"; median wall {float(np.median(latency)):.4f} s"
        if latency else "no request completed")

    per_request = [
        compare(cfg, ctx.reference, b,
                {k: getattr(program, k) for k in check.SEGMENT_FIELDS})
        for b, program in served]
    return dict(attempted=r, failed=failed, latency_s=latency, flows=flows,
                window_s=window_s, compared=len(per_request),
                numbers=check.reduce(per_request))
