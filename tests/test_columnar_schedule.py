"""The flat engine's ``Schedule`` carries its flows as columns.

``engine._schedule_from_times`` gathers the flow table's columns in the
legacy order (core-major, priority within a core) and computes start and
completion times elementwise; ``Schedule.flows`` builds the
``ScheduledFlow`` records from them only when a caller reads it. These
tests pin that:

* the program ``compile_schedule`` builds from the columns equals, in all
  seven arrays, the program compiled from a ``Schedule`` of the lazily
  built records;
* the lazily built records equal the per-flow construction the engine used
  before (kept here as the reference), in order and in every field;
* the one-shot service builds no record (``materialized`` on its
  ``oneshot`` span, ``service.flows_materialized``), and reading ``flows``
  afterwards builds each record once.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import ScheduledFlow, engine, run_fast
from repro.core.circuit_scheduler import records_built
from repro.core.coflow import OnlineInstance
from repro.core.engine import run_fast_online
from repro.core.scheduler import FlowColumns, Schedule
from repro.obs import Tracer
from repro.service import FabricConfig, FabricManager
from repro.service.program import compile_schedule

from test_engine_differential import _random_instance

PROGRAM_FIELDS = ("core", "ingress", "egress", "cid", "size", "t_establish",
                  "t_complete")


def _per_flow_reference(inst, table, t_est, delta_f=None):
    """The records as the engine built them one flow at a time."""
    order = np.lexsort((np.arange(table.n_flows), table.core))
    flows = []
    for f in order:
        te = float(t_est[f])
        s = float(table.size[f])
        rate = float(inst.rates[table.core[f]])
        dl = inst.delta if delta_f is None else float(delta_f[f])
        flows.append(ScheduledFlow(
            coflow=int(table.pos[f]), cid=int(table.cid[f]),
            i=int(table.fi[f]), j=int(table.fj[f]),
            core=int(table.core[f]), size=s, t_establish=te,
            t_start=te + dl, t_complete=te + dl + s / rate))
    return flows


def _emission_inputs(inst, delta_k):
    """The table, times and per-flow delays ``run_fast`` emits from."""
    seen = []

    def spy(inst, pi, assignment, table, t_est, srv, delta_f=None):
        seen.append((table, t_est, delta_f))
        return emit(inst, pi, assignment, table, t_est, srv, delta_f)

    emit = engine._schedule_from_times
    engine._schedule_from_times = spy
    try:
        run_fast(inst, delta_k=delta_k)
    finally:
        engine._schedule_from_times = emit
    (args,) = seen
    return args


def _as_bits(flows):
    return [(f.coflow, f.cid, f.i, f.j, f.core, f.size.hex(),
             f.t_establish.hex(), f.t_start.hex(), f.t_complete.hex())
            for f in flows]


def _drift(inst, trial):
    """A per-core delay vector with one core off nominal."""
    dk = np.full(inst.K, float(inst.delta))
    dk[trial % inst.K] += 1.25
    return dk


@pytest.mark.parametrize("drifted", [False, True], ids=["nominal", "drifted"])
@pytest.mark.parametrize("index_labels", [False, True], ids=["cid", "index"])
@pytest.mark.parametrize("trial", [0, 1, 2, 7, 11, 13])
def test_columnar_program_and_lazy_flows_are_bit_identical(trial, index_labels,
                                                            drifted):
    inst = _random_instance(trial)
    dk = _drift(inst, trial) if drifted else None
    s = run_fast(inst, delta_k=dk)
    columnar = compile_schedule(s, index_labels=index_labels)

    # the records, built lazily, against today's per-flow construction
    # over the same table and times
    table, t_est, dl_f = _emission_inputs(inst, dk)
    assert _as_bits(s.flows) == _as_bits(
        _per_flow_reference(inst, table, t_est, dl_f))
    assert s.n_flows == len(s.flows) == table.n_flows

    # the program from the records equals the program from the columns
    from_objects = compile_schedule(
        Schedule(inst=s.inst, pi=s.pi, assignment=None, flows=s.flows,
                 ccts=s.ccts), index_labels=index_labels)
    for name in PROGRAM_FIELDS:
        a, b = getattr(columnar, name), getattr(from_objects, name)
        assert a.dtype == b.dtype, name
        assert np.array_equal(a, b), name


@pytest.mark.parametrize("trial", [3, 5])
def test_columns_read_back_from_records(trial):
    """``columns()`` of a schedule made from records, and of one made from
    columns, agree field for field."""
    inst = _random_instance(trial)
    s = run_fast(inst)
    back = Schedule(inst=s.inst, pi=s.pi, assignment=None, flows=s.flows,
                    ccts=s.ccts).columns()
    for name, col in vars(s.columns()).items():
        assert getattr(back, name).dtype == col.dtype, name
        assert np.array_equal(getattr(back, name), col), name


def test_online_schedule_is_columnar():
    inst = _random_instance(4)
    rel = np.linspace(0.0, 30.0, inst.M)
    r0 = records_built()
    s = run_fast_online(OnlineInstance(inst=inst, releases=rel))
    assert records_built() == r0
    assert len(s.flows) == s.n_flows
    assert records_built() == r0 + s.n_flows


def test_schedule_takes_one_of_flows_and_columns():
    s = run_fast(_random_instance(1))
    with pytest.raises(ValueError):
        Schedule(inst=s.inst, pi=s.pi, assignment=None, ccts=s.ccts)
    with pytest.raises(ValueError):
        Schedule(inst=s.inst, pi=s.pi, assignment=None, flows=s.flows,
                 ccts=s.ccts, columns=s.columns())
    assert isinstance(s.columns(), FlowColumns)


def test_oneshot_request_builds_no_records():
    inst = _random_instance(8)
    tr = Tracer()
    mgr = FabricManager(FabricConfig(rates=tuple(inst.rates),
                                     delta=inst.delta, N=inst.N), tracer=tr)
    program, hit = mgr.schedule_instance(inst)
    assert not hit and program.n_segments > 0
    (span,) = [r["attrs"] for r in tr.records if r["name"] == "oneshot"]
    assert span["materialized"] == 0
    assert mgr.metrics.snapshot()["service.flows_materialized"] == 0

    # the engine's schedule builds its records on first access, once
    s = run_fast(inst)
    r0 = records_built()
    first = s.flows
    assert records_built() - r0 == s.n_flows == program.n_segments
    assert s.flows is first
    assert records_built() - r0 == s.n_flows


def test_oneshot_request_validating_inline_builds_no_records():
    """``validate_every_tick`` referees the program through its columns."""
    inst = _random_instance(9)
    mgr = FabricManager(FabricConfig(rates=tuple(inst.rates),
                                     delta=inst.delta, N=inst.N,
                                     validate_every_tick=True))
    mgr.schedule_instance(inst)
    assert mgr.metrics.snapshot()["service.flows_materialized"] == 0
