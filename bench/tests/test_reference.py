"""The plain reference gives the program's answer where both compute alike.

Where every size is a float32 value, the program's numpy backend (float64
sizes) and the reference (float32 inputs) see the same numbers, so their
programs agree exactly: cores, establish and completion times.
"""
import numpy as np
import pytest

from yardstick import check, fbtrace, registry


@pytest.mark.parametrize("config,n_coflows,seed", [
    ("fb2010-n150-k4", 6, 11), ("paper-n16-k3", 40, 12)])
def test_reference_matches_program(config, n_coflows, seed):
    from repro.core.coflow import Coflow, Instance
    from repro.core.engine import run_fast
    from repro.service.program import compile_schedule

    cfg = registry.config(config)
    ref = registry.reference(cfg["reference"])
    trace = fbtrace.synth_fb_trace(526, seed=2026)
    b, _, _ = fbtrace.sample_backlog(trace, n_ports=cfg["ports"],
                                     n_coflows=n_coflows, seed=seed)
    demands = b.demands.astype(np.float32).astype(np.float64)
    rates = np.asarray(cfg["rates"])
    inst = Instance(coflows=tuple(Coflow(cid=m, demand=d, weight=float(w))
                                  for m, (d, w) in enumerate(zip(demands,
                                                                 b.weights))),
                    rates=rates, delta=cfg["delta"])
    program = compile_schedule(run_fast(inst, backend="numpy"))
    solved = ref.solve(demands, b.weights, rates, cfg["delta"])
    seg = {k: getattr(program, k) for k in check.SEGMENT_FIELDS}
    got = check.compare(demands, b.weights, seg, solved, rates, cfg["delta"])
    assert got == dict(choices_differing=0, referee_violations=0,
                       wcct_rel_gap=0.0)
    # and the referee finds the reference's own answer sound
    assert check.referee(demands, check.reference_segments(solved), rates,
                         cfg["delta"]) == 0


def test_referee_finds_each_breach():
    cfg = registry.config("paper-n16-k3")
    ref = registry.reference(cfg["reference"])
    trace = fbtrace.synth_fb_trace(526, seed=2026)
    b, _, _ = fbtrace.sample_backlog(trace, n_ports=16, n_coflows=10, seed=5)
    rates = np.asarray(cfg["rates"])
    seg = check.reference_segments(ref.solve(b.demands, b.weights, rates, 8.0))

    def breaches(**change):
        s = {k: np.array(v, copy=True) for k, v in seg.items()}
        for k, fn in change.items():
            s[k] = fn(s[k])
        return check.referee(b.demands, s, rates, 8.0)

    assert breaches() == 0
    drop = lambda a: a[1:]
    assert breaches(**{k: drop for k in seg}) == 1                  # missing
    assert breaches(size=lambda a: a * np.r_[2.0, np.ones(a.size - 1)]) >= 1
    assert breaches(t_establish=lambda a: a - np.r_[1.0, np.zeros(a.size - 1)]) >= 1
    # a circuit started early enough to overlap the one before it
    # (the earliest circuit after time 0 waited for one that began at 0)
    first = int(np.argmin(np.where(seg["t_establish"] > 0,
                                   seg["t_establish"], np.inf)))
    shift = np.zeros(seg["t_establish"].size)
    shift[first] = seg["t_establish"][first]
    assert breaches(t_establish=lambda a: a - shift,
                    t_complete=lambda a: a - shift) >= 1
