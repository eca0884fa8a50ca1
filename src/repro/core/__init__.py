"""The paper's primary contribution: multi-coflow scheduling in multi-core OCS
networks under the not-all-stop reconfiguration model (Algorithm 1), with its
lower bounds, ablation baselines, feasibility validator, theory certificates,
and trace-driven workload generation.
"""
from .batch import ResultTable, SweepRow, row_from_ccts, run_batch  # noqa: F401
from .engine import (  # noqa: F401
    BACKENDS,
    FabricState,
    FlowTable,
    TickCommit,
    build_flow_table,
    cross_check,
    cross_check_incremental,
    cross_check_online,
    run_fast,
    run_fast_metrics,
    run_fast_online,
    schedule_all_cores,
)
from .online import OnlineInstance, run_online  # noqa: F401
from .assignment import (  # noqa: F401
    AssignedFlow,
    Assignment,
    FlatAssignState,
    assign_fast,
    assign_random,
    assign_rho_only,
    assign_tau_aware,
    assignment_from_choices,
)
from .circuit_scheduler import (  # noqa: F401
    ScheduledFlow,
    schedule_core_list,
    schedule_core_sunflow,
)
from .coflow import (  # noqa: F401
    Coflow,
    Flow,
    Instance,
    col_loads,
    extract_flows,
    rho,
    row_loads,
    tau,
)
from .fault import (  # noqa: F401
    AbortedCircuit,
    CoreDown,
    CoreUp,
    DeltaDrift,
    FaultApplication,
    FaultInjector,
    PortFlap,
)
from .lower_bounds import CoreState, global_lb, per_core_lb  # noqa: F401
from .ordering import order_coflows, priority_scores  # noqa: F401
from .scheduler import ALGORITHMS, Schedule, run, tail_cct, weighted_cct  # noqa: F401
from .simulator import validate  # noqa: F401
from .theory import (  # noqa: F401
    check_lemma1,
    check_lemma2,
    check_lemma3,
    check_theorem1,
    check_theorem2,
    gamma_w,
)
from .trace import (  # noqa: F401
    arrival_stream,
    load_fb_trace,
    sample_instance,
    sample_online_instance,
    synth_fb_trace,
)
