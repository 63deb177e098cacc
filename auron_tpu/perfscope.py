"""Perfscope CLI: roofline reports, baseline checks and overhead A/B.

    python -m auron_tpu.perfscope report --query q01 --sf 0.002
    python -m auron_tpu.perfscope check --baseline tests/golden_plans/perf_baseline.json
    python -m auron_tpu.perfscope ab --query q01 --reps 5

`report` executes one TPC-DS corpus query with `auron.perf.enable` armed
and renders the per-site roofline table (calls, bytes, seconds, achieved
GB/s vs the measured machine peak).  `check` compares achieved per-site
bandwidth against committed floors with tolerance bands
(tools/perf_check.sh's teeth; `--regen-golden` rewrites the baseline).  `ab` interleaves warm
disarmed/armed runs of the same query and gates that results stay
bit-identical and the overhead ratio stays small — the evidence that
the always-installed site shim is free when off.  This is the
command-line face of runtime/perfscope.py.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional


def _run_query(args: argparse.Namespace, extra_scope=None):
    """One corpus query under the standard CLI scope; returns the
    session result (the caller reads perfscope's ledgers after)."""
    import tempfile

    from auron_tpu.config import conf
    from auron_tpu.frontend.session import AuronSession
    from auron_tpu.it import queries
    from auron_tpu.it.datagen import generate
    from auron_tpu.it.oracle import PyArrowEngine

    data_dir = getattr(args, "_data_dir", None)
    if data_dir is None:
        data_dir = args.data_dir or tempfile.mkdtemp(prefix="auron_perf_")
        catalog = generate(data_dir, sf=args.sf)
        args._data_dir = data_dir
        args._catalog = catalog
    catalog = args._catalog
    plan = queries.build(args.query, catalog)
    scope = {}
    if getattr(args, "serial", False):
        scope["auron.spmd.singleDevice.enable"] = False
    if extra_scope:
        scope.update(extra_scope)
    with conf.scoped(scope):
        session = AuronSession(foreign_engine=PyArrowEngine())
        return session.execute(plan)


def _cmd_report(args: argparse.Namespace) -> int:
    import jax
    jax.config.update("jax_platforms", args.platform)
    from auron_tpu.runtime import perfscope

    perfscope.reset_state()
    perfscope.configure(True)
    try:
        res = _run_query(args)
        doc = perfscope.rooflines()
        if not doc["sites"]:
            print("no kernel executions were recorded "
                  "(auron.perf.enable did not take?)", file=sys.stderr)
            return 2
        print(f"{args.query}: {res.table.num_rows} rows, "
              f"{len(doc['sites'])} jit sites measured")
        print(perfscope.render_report(doc))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(doc, f, indent=2, sort_keys=True)
            print(f"rooflines -> {args.json}")
    finally:
        perfscope.configure(False)
    return 0


def _cmd_check(args: argparse.Namespace) -> int:
    import jax
    jax.config.update("jax_platforms", args.platform)
    from auron_tpu.runtime import perfscope

    perfscope.reset_state()
    perfscope.configure(True)
    try:
        # warm-up run absorbs compiles; the measured run prices steady
        # state, which is what a bandwidth floor is about
        _run_query(args)
        perfscope.reset_state()
        _run_query(args)
        doc = perfscope.rooflines()
    finally:
        perfscope.configure(False)
    sites = doc["sites"]
    if not sites:
        print("perf_check: no kernel executions recorded",
              file=sys.stderr)
        return 2
    if args.regen_golden:
        baseline = {
            "perfscope_baseline": 1,
            "platform": doc["platform"],
            "machine_peak_gbps": doc["peak_gbps"],
            "query": args.query,
            "sf": args.sf,
            # floor = half the achieved bandwidth at regen time: wide
            # enough to absorb machine noise, tight enough that an
            # accidental sync/copy regression (integer-factor slowdowns)
            # still trips it
            "tolerance": args.tolerance,
            "floors_gbps": {
                site: round(s["achieved_gbps"] * 0.5, 4)
                for site, s in sorted(sites.items())
                if s["calls"] >= args.min_calls},
        }
        with open(args.baseline, "w") as f:
            json.dump(baseline, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"perf baseline regenerated -> {args.baseline} "
              f"({len(baseline['floors_gbps'])} site floors)")
        return 0
    with open(args.baseline) as f:
        baseline = json.load(f)
    tol = float(baseline.get("tolerance", args.tolerance))
    failures = []
    for site, floor in sorted(baseline.get("floors_gbps", {}).items()):
        s = sites.get(site)
        if s is None or s["calls"] < args.min_calls:
            # a site may legitimately disappear when a plan rewrite
            # stops using its kernel family — report, don't fail
            print(f"perf_check: site {site} absent from this run "
                  f"(floor {floor} GB/s unchecked)")
            continue
        lo = floor * (1.0 - tol)
        status = "ok" if s["achieved_gbps"] >= lo else "FAIL"
        print(f"perf_check: {site:<28} achieved {s['achieved_gbps']:8.3f}"
              f" GB/s  floor {lo:8.3f}  {status}")
        if status == "FAIL":
            failures.append(site)
    print(perfscope.render_report(doc))
    if failures:
        print(f"perf_check: {len(failures)} site(s) below floor: "
              f"{', '.join(failures)}", file=sys.stderr)
        return 1
    print(f"perf_check: all {len(baseline.get('floors_gbps', {}))} "
          f"floors hold (tolerance {tol:.0%})")
    return 0


def _cmd_ab(args: argparse.Namespace) -> int:
    import time

    import jax
    jax.config.update("jax_platforms", args.platform)
    from auron_tpu.runtime import perfscope

    perfscope.configure(False)
    # warm BOTH paths first so compiles never land in a measured rep
    base = _run_query(args)
    perfscope.configure(True)
    try:
        armed0 = _run_query(args)
    finally:
        perfscope.configure(False)
    if not base.table.equals(armed0.table):
        print("perf ab: armed run is NOT bit-identical to disarmed",
              file=sys.stderr)
        return 1
    t_off, t_on = [], []
    for _ in range(args.reps):
        t0 = time.perf_counter()
        _run_query(args)
        t_off.append(time.perf_counter() - t0)
        perfscope.configure(True)
        try:
            t0 = time.perf_counter()
            _run_query(args)
            t_on.append(time.perf_counter() - t0)
        finally:
            perfscope.configure(False)
    med_off = sorted(t_off)[len(t_off) // 2]
    med_on = sorted(t_on)[len(t_on) // 2]
    ratio = med_on / med_off if med_off > 0 else 1.0
    print(f"perf ab: {args.query} x{args.reps} interleaved warm — "
          f"disarmed {med_off * 1e3:.1f}ms, armed {med_on * 1e3:.1f}ms, "
          f"overhead ratio {ratio:.4f} (results identical)")
    if ratio > 1.0 + args.max_overhead:
        print(f"perf ab: armed overhead {ratio - 1.0:.2%} exceeds "
              f"{args.max_overhead:.0%}", file=sys.stderr)
        return 1
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="auron_tpu.perfscope")
    sub = ap.add_subparsers(dest="cmd", required=True)

    def corpus_args(p):
        p.add_argument("--query", default="q01")
        p.add_argument("--sf", type=float, default=0.002)
        p.add_argument("--data-dir", default=None)
        p.add_argument("--platform", default="cpu")
        p.add_argument("--serial", action="store_true",
                       help="force the serial per-partition path")

    rep = sub.add_parser("report",
                         help="run one corpus query armed and render "
                              "the per-site roofline table")
    corpus_args(rep)
    rep.add_argument("--json", default=None,
                     help="also write the rooflines doc as JSON")
    rep.set_defaults(fn=_cmd_report)

    chk = sub.add_parser("check",
                         help="gate achieved per-site bandwidth against "
                              "committed floors")
    corpus_args(chk)
    chk.add_argument("--baseline",
                     default="tests/golden_plans/perf_baseline.json")
    chk.add_argument("--regen-golden", action="store_true")
    chk.add_argument("--tolerance", type=float, default=0.5,
                     help="fractional band under each floor that still "
                          "passes (default 0.5)")
    chk.add_argument("--min-calls", type=int, default=1,
                     help="sites with fewer calls are not gated")
    chk.set_defaults(fn=_cmd_check)

    ab = sub.add_parser("ab",
                        help="interleaved warm disarmed/armed A/B: "
                             "bit-identical results + overhead gate")
    corpus_args(ab)
    ab.add_argument("--reps", type=int, default=5)
    ab.add_argument("--max-overhead", type=float, default=0.02,
                    help="fail if armed median exceeds disarmed by "
                         "more than this fraction (default 2%%)")
    ab.set_defaults(fn=_cmd_ab)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
