"""Run one cell of the benchmark once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``, ``workloads``) names a configuration
(``bench/configs``) and a traffic mix (``bench/traffic``); the mix names the
driver (``bench/drivers``) that serves it. Set-up builds the traffic and
warms up; the window then runs for ``--seconds``; afterwards the driver
compares what the window produced with the configuration's plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics, each read by
``bench/metrics/<name>.py``), ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number compared with its limit, which also end
standard error. Without an accelerator with enough chips for the cell it
exits non-zero and prints no result.

JAX's persistent compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``,
else in ``.jax_cache`` at the root of the checkout. Set-up compiles what the
window will run, reading that cache; the window reads no cache, so anything
that still compiles there costs the same in every run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from yardstick import check, devtrace, instrument, registry, stats  # noqa: E402
from yardstick.peaks import peaks  # noqa: E402

#: Program functions timed as host spans in a traced run, by label.
SPAN_TARGETS = {
    "extract": "repro.core.engine:extract_flows",
    "assign": "repro.core.engine:_pallas_choices",
    "event_loop": "repro.core.engine:_times_for_table",
    "schedule": "repro.core.engine:_schedule_from_times",
    "emit": "repro.service.manager:compile_schedule",
    "compile": "jax._src.compiler:backend_compile_and_load",
}


def _log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class Context:
    """What a driver is given: the cell's pieces and the harness's hooks."""

    def __init__(self, cell: dict, seed: int, seconds: float,
                 traced: bool) -> None:
        self.cfg = registry.config(cell["config"])
        self.mix = registry.traffic(cell["traffic"])
        self.reference = registry.reference(self.cfg["reference"])
        self.seed, self.seconds, self.traced = seed, seconds, traced
        self.log = _log
        self.spans = instrument.Spans() if traced else None
        self.compiles = instrument.Compiles() if traced else None
        self.setup_s = None
        self.trace = None
        self.window_s = None
        self.memory_peak_bytes = 0
        if self.spans:
            for label, target in SPAN_TARGETS.items():
                self.spans.wrap(target, label)

    @staticmethod
    def since_start() -> float:
        return time.perf_counter() - T_START

    def setup_done(self) -> None:
        """Set-up ends: its time is taken, and the persistent compilation
        cache is turned off, so that whatever still compiles in the window
        costs the same in every run."""
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        self.setup_s = self.since_start()
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()

    @contextlib.contextmanager
    def window(self):
        cap = devtrace.capture() if self.traced else contextlib.nullcontext()
        with cap:
            if self.spans:
                self.spans.armed = self.compiles.armed = True
            span = self.spans.span("window") if self.spans \
                else contextlib.nullcontext()
            try:
                with span:
                    yield
            finally:
                if self.spans:
                    self.spans.armed = self.compiles.armed = False
        if self.traced:
            self.trace, self.window_s = cap.trace, cap.window_s

    def window_closed(self) -> None:
        import jax

        st = jax.devices()[0].memory_stats() or {}
        self.memory_peak_bytes = int(st.get("peak_bytes_in_use", 0))


def device_info(require_chips: int | None) -> dict | None:
    """The device JAX finds, or ``None`` where it is not enough."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chips is not None and (info["platform"] != "tpu"
                                      or info["count"] < require_chips):
        _log(f"needs {require_chips} TPU chip(s), found {info['count']} "
             f"{info['platform']} device(s) ({info['kind']}); no result")
        return None
    return info


def end_to_end(cell_name: str, res: dict, setup_s: float) -> dict:
    lat = res["latency_s"]
    values = {"setup_s": setup_s}
    if lat:
        values.update(decision_p50_s=stats.percentile(lat, 50),
                      decision_p95_s=stats.percentile(lat, 95),
                      flows_per_s=sum(res["flows"]) / res["window_s"])
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in registry.metrics_of(cell_name, "end_to_end")
            if m["name"] in values}


def per_layer(cell_name: str, ctx: Context, res: dict, info: dict) -> dict:
    view = types.SimpleNamespace(
        result=res, spans=ctx.spans, compiles=ctx.compiles, trace=ctx.trace,
        window_s=ctx.window_s, cfg=ctx.cfg,
        peaks=peaks(info["kind"]) if info["platform"] == "tpu" else None)
    out = {}
    for m in registry.metrics_of(cell_name, "per_layer"):
        value = registry.metric_reader(m["name"]).read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             require_tpu: bool = True) -> dict | None:
    """One run of ``workload``; the result line, or ``None`` when the
    device is not what the cell needs."""
    cell = registry.cell(workload)
    info = device_info(cell["chips"] if require_tpu else None)
    if info is None:
        return None
    import jax

    _log(f"device found at {time.perf_counter() - T_START:.3f} s")
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(registry.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    ctx = Context(cell, seed, seconds, traced)
    drv = registry.driver(ctx.mix["driver"])
    try:
        res = drv.run(ctx)
    finally:
        if ctx.spans:
            ctx.spans.restore()
    ok, shown = check.verdict(res["numbers"], ctx.cfg["limits"],
                              res["compared"], res["failed"])
    line = {"correct": ok, "attempted": res["attempted"],
            "failed": res["failed"]}
    device = dict(info, memory_peak_bytes=ctx.memory_peak_bytes)
    if traced:
        line["metrics"] = per_layer(workload, ctx, res, info)
        device.update(busy_s=devtrace.busy_ns(ctx.trace) / 1e9,
                      window_s=ctx.window_s)
        line["device"] = device
        line["breakdown"] = {
            "device_ops": devtrace.top_ops(ctx.trace),
            "idle_gaps": devtrace.idle_by_host_span(ctx.trace)}
    else:
        line["metrics"] = end_to_end(workload, res, ctx.setup_s)
        line["device"] = device
    line["checks"] = shown
    for name, c in shown.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    line = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    if line is None:
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
