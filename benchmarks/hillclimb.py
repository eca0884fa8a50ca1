"""Perf hillclimbing driver: lower one (arch x shape) cell with config
variants, report the three roofline terms + a top-contributor breakdown so
each hypothesis -> change -> measure cycle is grounded in the lowered IR.

  PYTHONPATH=src python -m benchmarks.hillclimb --arch tinyllama-1.1b \
      --shape prefill_32k --variant baseline --variant chunked_attn
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # compile-only: never take the chip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import argparse
import json
import re
from collections import Counter

from repro.analysis.hlo import _parse_computations, type_bytes  # noqa: E402

VARIANTS = {
    "baseline": {},
    "chunked_attn": {"attention_impl": "chunked"},
    "remat_dots": {"remat_policy": "dots"},
    "chunked_dots": {"attention_impl": "chunked", "remat_policy": "dots"},
}


def breakdown(compiled_text: str, top: int = 12):
    """Top HBM-traffic contributors by (computation, opcode, shape)."""
    comps = _parse_computations(compiled_text)
    types = {}
    for ops in comps.values():
        for op in ops:
            types[op.name] = op.result_type
    by = Counter()
    for cname, ops in comps.items():
        for op in ops:
            if op.opcode in ("fusion", "dot", "all-reduce", "all-gather",
                             "reduce-scatter", "all-to-all", "copy",
                             "transpose", "broadcast", "convert"):
                b = type_bytes(op.result_type)
                by[(op.opcode, op.result_type[:46], cname[:34])] += b
    return by.most_common(top)


def run_cell(arch, shape, variant_name, extra, mesh, dump=False):
    from repro.launch.dryrun import lower_cell

    r = lower_cell(arch, shape, mesh, "single", extra_cfg=extra or None,
                   return_text=dump)
    rf = r["roofline"]
    print(f"\n== {arch} x {shape} [{variant_name}] ==")
    print(f"  peak {r['memory']['peak_estimate_bytes']/2**30:.2f} GiB/dev  "
          f"compile {r['compile_s']}s")
    print(f"  terms: compute={rf['compute_s']:.4f}s memory={rf['memory_s']:.4f}s "
          f"collective={rf['collective_s']:.4f}s  dom={rf['dominant']}")
    print(f"  roofline_fraction={100*rf['roofline_fraction']:.2f}%  "
          f"useful={rf['useful_fraction']:.3f}  colls={rf['collective_counts']}")
    if dump:
        for (opc, typ, cname), b in breakdown(r.pop("hlo_text")):
            print(f"    {b/2**30:8.2f} GiB  {opc:12s} {typ:46s} in {cname}")
    return r


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", action="append", default=None)
    ap.add_argument("--extra", default=None, help="json dict of config overrides")
    ap.add_argument("--dump-breakdown", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=False)
    results = []
    variants = args.variant or ["baseline"]
    for vn in variants:
        extra = dict(VARIANTS.get(vn, {}))
        if args.extra:
            extra.update(json.loads(args.extra))
        r = run_cell(args.arch, args.shape, vn, extra, mesh,
                     dump=args.dump_breakdown)
        results.append({"variant": vn, **r})
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=1, default=str)


if __name__ == "__main__":
    main()
