"""CLI: `python -m auron_tpu.analysis [plan.json ...]`.

With no paths, lints every golden plan document under the IT reference
set (tests/golden_plans, or $AURON_GOLDEN_PLANS).  A path may be a
directory, a golden document ({"query": ..., "plans": {...}}), or a bare
serialized node ({"@kind": ...} — the wire form ir/serde.py emits).

    python -m auron_tpu.analysis                      # lint the golden set
    python -m auron_tpu.analysis plan.json --strict   # warnings fail too
    python -m auron_tpu.analysis --regen-golden       # rebuild the set
    python -m auron_tpu.analysis --concurrency        # static lock lint
    python -m auron_tpu.analysis --concurrency --regen-golden
                                      # rebuild the lock-order golden
    python -m auron_tpu.analysis --compilation        # compile-hygiene lint
    python -m auron_tpu.analysis --compilation --regen-golden
                                      # rerun q01+q03, rebuild the
                                      # compile manifest
    python -m auron_tpu.analysis --protocol           # wire-protocol lint
    python -m auron_tpu.analysis --protocol --regen-golden
                                      # rebuild the wire manifest

--regen-golden re-derives the documents from the IT corpus: every
query in auron_tpu.it.queries is converted exactly as the runner
converts it, and the native root plus each exchange/broadcast producer
subtree (wrapped in its ShuffleWriter so partitioning contracts stay
checkable) is serialized into one JSON document per query.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, Iterator, List, Tuple

from auron_tpu.analysis import analyze
from auron_tpu.ir.node import Node


def default_golden_dir() -> str:
    env = os.environ.get("AURON_GOLDEN_PLANS")
    if env:
        return env
    # repo-relative (…/auron_tpu/analysis/__main__.py -> repo root)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo, "tests", "golden_plans")


def iter_documents(paths: List[str]) -> Iterator[Tuple[str, dict]]:
    def load(f: str) -> dict:
        with open(f) as fh:
            return json.load(fh)

    for p in paths:
        if os.path.isdir(p):
            for f in sorted(glob.glob(os.path.join(p, "*.json"))):
                yield f, load(f)
        else:
            yield p, load(p)


def plans_of(doc: dict) -> Iterator[Tuple[str, Node]]:
    """(label, decoded plan) pairs of one document."""
    if "@kind" in doc:
        yield "plan", Node.from_dict(doc)
        return
    for label, d in doc.get("plans", {}).items():
        yield label, Node.from_dict(d)


def lint_paths(paths: List[str], strict: bool = False,
               quiet: bool = False) -> int:
    n_plans = n_err = n_warn = 0
    failed: List[str] = []
    for path, doc in iter_documents(paths):
        name = doc.get("query") or os.path.basename(path)
        for label, plan in plans_of(doc):
            n_plans += 1
            res = analyze(plan)
            n_err += len(res.errors)
            n_warn += len(res.warnings)
            bad = bool(res.errors) or (strict and res.warnings)
            if bad:
                failed.append(f"{name}:{label}")
            for d in res.diagnostics:
                if d.severity == "info" and quiet:
                    continue
                if d.is_error or not quiet or strict:
                    print(f"{name}:{label}: {d}")
    status = "FAIL" if failed else "ok"
    print(f"{status}: {n_plans} plans linted, {n_err} errors, "
          f"{n_warn} warnings"
          + (f"; failing: {', '.join(failed[:20])}" if failed else ""))
    if failed:
        return 2
    return 0


# ---------------------------------------------------------------------------
# golden regeneration (the IT reference set, serialized)
# ---------------------------------------------------------------------------

def regen_golden(out_dir: str, sf: float, data_dir: str) -> int:
    from auron_tpu.frontend import converters, strategy
    from auron_tpu.frontend.converters import ConvertContext, ForeignWrap
    from auron_tpu.ir import plan as P
    from auron_tpu.it import queries
    from auron_tpu.it.datagen import generate
    # goldens record what the runtime EXECUTES: the fusion rewrite
    # (runtime/fusion.py) is applied to every section, so fragment
    # boundaries are part of the committed plan shape and the verifier's
    # FusionContractPass lints them on every CI run
    from auron_tpu.runtime.fusion import fuse_plan

    cat = generate(data_dir, sf=sf)
    os.makedirs(out_dir, exist_ok=True)
    n = 0
    for name in queries.names():
        plan = queries.build(name, cat)
        tags = strategy.apply(plan)
        ctx = ConvertContext()
        ctx._uid = "golden00"   # deterministic resource ids for goldens
        converted = converters.convert_recursively(plan, tags, ctx)

        plans: Dict[str, dict] = {}

        def native_roots(c) -> Iterator[P.PlanNode]:
            if isinstance(c, P.PlanNode):
                yield c
            elif isinstance(c, ForeignWrap):
                for ch in c.children:
                    yield from native_roots(ch)

        for i, root in enumerate(native_roots(converted)):
            plans["root" if i == 0 and isinstance(converted, P.PlanNode)
                  else f"native[{i}]"] = fuse_plan(root).to_dict()
        for i, job in enumerate(ctx.exchanges.values()):
            if isinstance(job.child, P.PlanNode):
                w = P.ShuffleWriter(child=job.child,
                                    partitioning=job.partitioning)
                plans[f"exchange[{i}]"] = fuse_plan(w).to_dict()
        for i, job in enumerate(ctx.broadcasts.values()):
            if isinstance(job.child, P.PlanNode):
                plans[f"broadcast[{i}]"] = fuse_plan(job.child).to_dict()
        for i, src in enumerate(ctx.sources.values()):
            for j, root in enumerate(native_roots(src.node)):
                plans[f"source[{i}][{j}]"] = fuse_plan(root).to_dict()

        doc = {"query": name, "sf": sf, "plans": plans}
        with open(os.path.join(out_dir, f"{name}.json"), "w") as fh:
            json.dump(doc, fh, sort_keys=True, separators=(",", ":"))
            fh.write("\n")
        n += 1
        print(f"{name}: {len(plans)} plan sections", flush=True)
    print(f"regenerated {n} golden plan documents in {out_dir}")
    return 0


def run_concurrency(regen: bool, golden_dir: str) -> int:
    """The static concurrency pass (`--concurrency`): raw-lock lint,
    static lock-order graph + cycle check, blocking-under-lock lint,
    golden comparison."""
    from auron_tpu.analysis import concurrency as conc

    report = conc.analyze_concurrency()
    golden = os.path.join(golden_dir, "lock_order.txt")
    if regen:
        text = conc.render_golden(report)
        os.makedirs(golden_dir, exist_ok=True)
        with open(golden, "w") as fh:
            fh.write(text)
        print(f"wrote {golden}: {len(report.locks)} locks, "
              f"{len(report.edge_set())} edges, "
              f"{len(set(report.waivers))} waivers")
    problems = [] if regen else conc.check_against_golden(report, golden)
    for d in report.result.diagnostics:
        print(d)
    for p in problems:
        print(f"error[concurrency-golden] {p}")
    n_err = len(report.result.errors) + len(problems)
    status = "FAIL" if n_err else "ok"
    print(f"{status}: {len(report.locks)} locks, "
          f"{len(report.edge_set())} static edges, "
          f"{len(set(report.waivers))} waivers, "
          f"{n_err} unwaived errors")
    return 2 if n_err else 0


def run_protocol(regen: bool, golden_dir: str) -> int:
    """The static wire-protocol pass (`--protocol`): server-ladder vs
    registry exhaustiveness (both directions), client request literals
    inside the contract, transport fault-point + retry-policy riding,
    idempotency-vs-replay consistency, raw struct framing lint, golden
    wire-manifest comparison."""
    from auron_tpu.analysis import protocol as proto

    report = proto.analyze_protocol()
    golden = os.path.join(golden_dir, "wire_manifest.txt")
    if regen:
        text = proto.render_golden()
        os.makedirs(golden_dir, exist_ok=True)
        with open(golden, "w") as fh:
            fh.write(text)
        print(f"wrote {golden}: {report.command_count()} commands on "
              f"{len(report.ladders) + 1} wires")
    problems = [] if regen else proto.check_against_golden(golden)
    for d in report.result.diagnostics:
        print(d)
    for p in problems:
        print(f"error[protocol-golden] {p}")
    n_err = len(report.result.errors) + len(problems)
    status = "FAIL" if n_err else "ok"
    print(f"{status}: {report.command_count()} commands, "
          f"{sum(len(c) for c in report.ladders.values())} ladder arms, "
          f"{len(report.framing_sites)} framing sites, "
          f"{n_err} unwaived errors")
    return 2 if n_err else 0


def run_compilation(regen: bool, golden_dir: str) -> int:
    """The static compilation pass (`--compilation`): raw-jit lint,
    host-materialization inside jitted bodies, mutable-capture lint,
    the config-knob lint, and
    (with --regen-golden) the canonical-run compile manifest."""
    from auron_tpu.analysis import compilation as comp

    report = comp.analyze_compilation()
    for d in report.result.diagnostics:
        print(d)
    n_err = len(report.result.errors)
    manifest_note = ""
    if regen:
        # the canonical run needs the CPU backend and jitcheck armed
        # (sites wrapped while checking is off stay raw): force both
        # BEFORE any kernel module imports
        import jax

        from auron_tpu.runtime import jitcheck
        try:
            jax.config.update("jax_platforms", "cpu")
        except RuntimeError:
            pass   # backend already initialized (e.g. under pytest)
        jitcheck.configure(True, True)
        snapshot = comp.collect_compile_manifest()
        path = os.path.join(golden_dir, "compile_manifest.txt")
        os.makedirs(golden_dir, exist_ok=True)
        with open(path, "w") as fh:
            fh.write(comp.render_manifest(snapshot))
        total = sum(c for _s, c in snapshot.values())
        print(f"wrote {path}: {len(snapshot)} sites, {total} compiles")
        manifest_note = f", manifest {len(snapshot)} sites"
    status = "FAIL" if n_err else "ok"
    print(f"{status}: {len(report.jit_sites)} jit bodies resolved, "
          f"{report.conf_keys_checked} conf-key sites checked"
          f"{manifest_note}, {n_err} unwaived errors")
    return 2 if n_err else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="auron_tpu.analysis")
    ap.add_argument("paths", nargs="*",
                    help="plan JSON files/dirs (default: the golden set)")
    ap.add_argument("--strict", action="store_true",
                    help="treat warnings as failures")
    ap.add_argument("--quiet", action="store_true",
                    help="print errors only")
    ap.add_argument("--concurrency", action="store_true",
                    help="run the static concurrency pass instead of the "
                         "plan lint (raw-lock registry bypass, static "
                         "lock-order graph vs the committed golden, "
                         "blocking-under-lock)")
    ap.add_argument("--compilation", action="store_true",
                    help="run the static compilation-hygiene pass "
                         "instead of the plan lint (raw-jit registry "
                         "bypass, host materialization inside jitted "
                         "bodies, mutable-capture, config-knob lint)")
    ap.add_argument("--protocol", action="store_true",
                    help="run the static wire-protocol pass instead of "
                         "the plan lint (server dispatch ladders vs the "
                         "wirecheck command registry both ways, client "
                         "sites on named fault points + the shared "
                         "retry policy, idempotency-vs-replay audit, "
                         "raw struct framing lint, wire-manifest "
                         "golden)")
    ap.add_argument("--regen-golden", action="store_true",
                    help="rebuild the golden plan documents from the IT "
                         "corpus (with --concurrency: rebuild the "
                         "lock-order graph golden; with --compilation: "
                         "rerun the canonical q01+q03 and rebuild the "
                         "compile manifest; with --protocol: rebuild "
                         "the wire manifest)")
    ap.add_argument("--golden-dir", default=None)
    ap.add_argument("--sf", type=float, default=0.001)
    ap.add_argument("--data-dir", default="/tmp/auron_tpcds_lint")
    args = ap.parse_args(argv)

    golden = args.golden_dir or default_golden_dir()
    if args.concurrency:
        return run_concurrency(args.regen_golden, golden)
    if args.compilation:
        return run_compilation(args.regen_golden, golden)
    if args.protocol:
        return run_protocol(args.regen_golden, golden)
    if args.regen_golden:
        return regen_golden(golden, args.sf, args.data_dir)
    paths = args.paths or [golden]
    for p in paths:
        if not os.path.exists(p):
            print(f"error: no such file or directory: {p}",
                  file=sys.stderr)
            return 1
    return lint_paths(paths, strict=args.strict, quiet=args.quiet)


if __name__ == "__main__":
    sys.exit(main())
