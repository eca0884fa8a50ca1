"""The control of the comparison that decides ``correct``.

The plain reference, computed one precision below what the configuration
states, is put in the program's place: for each request that a run of the
cell serves, its answer is compared with the reference's by the same
numbers and limits as the program's answer is (``yardstick.check``). It has
to come out as not correct.

The configuration states float64 arithmetic for the assignment (or two
float32 words on the chip) and for the schedule, over float32 inputs.
``--steps assignment`` computes the assignment in float32 (one float32 word
of state, the step a faster kernel would take) and keeps the rest;
``--steps all`` computes every step in float32. ``--requests`` is the
number of requests a window of the cell serves on the chip.

    python3 bench/tests/control.py --workload <cell> --requests <n> \\
        --steps assignment --seeds 11 12 13

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from yardstick import check, registry  # noqa: E402

STEPS = ("assignment", "all")


def readings(workload: str, seeds: list[int], n_requests: int, steps: str,
             mix_overrides: dict | None = None) -> list[dict]:
    """Per seed: the control's numbers over the first ``n_requests``
    requests of a run of ``workload`` with that seed, and its verdict."""
    if steps not in STEPS:
        raise ValueError(f"steps {steps!r} is not one of {STEPS}")
    cell = registry.cell(workload)
    cfg = registry.config(cell["config"])
    mix = dict(registry.traffic(cell["traffic"]), **(mix_overrides or {}))
    ref = registry.reference(cfg["reference"])
    reqs = registry.driver(mix["driver"]).Requests(cfg, mix)
    rates = np.asarray(cfg["rates"], np.float64)
    delta = float(cfg["delta"])
    lower = dict(assign_precision="float32",
                 schedule_precision="float32" if steps == "all" else "float64")
    out = []
    for seed in seeds:
        per = []
        for r in range(n_requests):
            b = reqs.window(seed, r)
            solved = ref.solve(b.demands, b.weights, rates, delta)
            control = ref.solve(b.demands, b.weights, rates, delta, **lower)
            per.append(check.compare(b.demands, b.weights,
                                     check.reference_segments(control),
                                     solved, rates, delta))
        numbers = check.reduce(per)
        ok, _ = check.verdict(numbers, cfg["limits"], len(per), 0)
        out.append(dict(seed=seed, correct=ok,
                        requests_differing=sum(p["choices_differing"] > 0
                                               for p in per), **numbers))
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--requests", type=int, required=True)
    ap.add_argument("--steps", choices=STEPS, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    for row in readings(a.workload, a.seeds, a.requests, a.steps):
        print(json.dumps(dict(row, steps=a.steps, workload=a.workload)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
