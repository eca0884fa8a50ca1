"""A run whose timed path is broken underneath comes out not correct.

Each fault is planted in the program (at the module attribute the engine
calls), the harness runs the cell at a tiny size on the CPU, and the
comparison with the plain reference has to say ``correct: false``. The
faults that a one-shot scheduling request can have: an answer altered
where it is produced (one flow's core), half of the batch left out of the
program, and circuits timed wrongly by the scheduling phase. (A state left
unchanged and an exchange between chips left out belong to training and to
several chips; these cells have neither.)
"""
import dataclasses

import numpy as np
import pytest

import run
from yardstick import registry


def _one_core_altered(fn):
    def wrapped(inst, flows):
        out = np.array(fn(inst, flows), copy=True)
        out[len(out) // 2] = (out[len(out) // 2] + 1) % inst.K
        return out
    return wrapped


def _half_left_out(fn):
    def wrapped(s, **kw):
        p = fn(s, **kw)
        keep = np.arange(p.n_segments) % 2 == 0
        return dataclasses.replace(p, **{
            k: getattr(p, k)[keep] for k in ("core", "ingress", "egress",
                                              "cid", "size", "t_establish",
                                              "t_complete")})
    return wrapped


def _no_reconfiguration_wait(fn):
    def wrapped(*args, **kw):
        t_est, srv = fn(*args, **kw)
        return np.maximum(t_est - 8.0, 0.0), srv
    return wrapped


FAULTS = {
    "answer_altered": ("repro.core.engine", "_pallas_choices",
                       _one_core_altered),
    "half_left_out": ("repro.service.manager", "compile_schedule",
                      _half_left_out),
    "schedule_wrong": ("repro.core.engine", "_times_for_table",
                       _no_reconfiguration_wait),
}


@pytest.mark.parametrize("cell", ["fb2010-n150-k4.oneshot-m60",
                                  "paper-n16-k3.oneshot-m100"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(monkeypatch, tmp_path, cell, fault):
    import importlib

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    orig = registry.traffic
    monkeypatch.setattr(registry, "traffic",
                        lambda name: dict(orig(name), coflows=3, shapes=2))
    mod_name, attr, plant = FAULTS[fault]
    mod = importlib.import_module(mod_name)
    monkeypatch.setattr(mod, attr, plant(getattr(mod, attr)))
    line = run.run_cell(cell, 99, 0.2, False, require_tpu=False)
    assert line["attempted"] >= 1
    assert line["correct"] is False, line["checks"]
