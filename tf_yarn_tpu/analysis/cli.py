"""`python -m tf_yarn_tpu.analysis` — run all four engines, report, gate.

One invocation covers the whole stack: AST lints (TYA0xx), jaxpr-level
entry-point verification (TYA1xx), compiled-HLO artifact audits
(TYA2xx), and host-concurrency audits (TYA3xx: lock-discipline lint +
dynamic lockset race scenarios) — `--hlo` / `--concurrency` narrow to
one engine, `--no-*` flags drop individual engines, `--no-race` keeps
the concurrency lint but skips the dynamic scenario drivers. Per-engine
wall time is printed (and included in `--json`) so the tier-1 log shows
where analysis time goes.

Exit codes: 0 clean, 2 findings, 1 engine/usage error — distinct so CI
can tell "the code has defects" from "the checker itself broke"
(tests/test_analysis.py gates on this over `tf_yarn_tpu/` in tier-1).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List, Optional

from tf_yarn_tpu.analysis.findings import Finding
from tf_yarn_tpu.analysis.rules import RULES

# Bumped whenever the --json document shape changes; consumers pin it.
# v3: added the "race_report" section + the "concurrency" engine.
JSON_SCHEMA_VERSION = 3

EXIT_CLEAN = 0
EXIT_ERROR = 1
EXIT_FINDINGS = 2


def _parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="python -m tf_yarn_tpu.analysis",
        description="JAX/TPU-aware static checker: AST lints (TYA0xx) + "
        "jaxpr entry-point verification (TYA1xx) + compiled-HLO artifact "
        "audits (TYA2xx) + host-concurrency audits (TYA3xx). Rule "
        "catalog: docs/StaticAnalysis.md.",
    )
    parser.add_argument(
        "paths", nargs="*", default=["tf_yarn_tpu"],
        help="files/directories to lint (default: tf_yarn_tpu)",
    )
    parser.add_argument(
        "--json", action="store_true", dest="as_json",
        help="machine-readable output (findings + counts + census; "
        f"json_schema_version {JSON_SCHEMA_VERSION})",
    )
    parser.add_argument(
        "--hlo", action="store_true", dest="hlo_only",
        help="run ONLY the compiled-HLO engine (skip the others)",
    )
    parser.add_argument(
        "--concurrency", action="store_true", dest="concurrency_only",
        help="run ONLY the concurrency engine (lock-discipline lint + "
        "lockset race scenarios)",
    )
    parser.add_argument(
        "--no-ast", action="store_true", help="skip the AST lint engine"
    )
    parser.add_argument(
        "--no-jaxpr", action="store_true",
        help="skip the jaxpr engine (entry-point tracing)",
    )
    parser.add_argument(
        "--no-hlo", action="store_true",
        help="skip the HLO engine (lower-and-compile audits)",
    )
    parser.add_argument(
        "--no-concurrency", action="store_true",
        help="skip the concurrency engine entirely",
    )
    parser.add_argument(
        "--no-race", action="store_true",
        help="keep the concurrency lint but skip the dynamic lockset "
        "scenario drivers (fast lint-only mode)",
    )
    parser.add_argument(
        "--update-hlo-budgets", action="store_true",
        help="rewrite analysis/hlo_budgets.json from this run's census "
        "instead of diffing against it (review + commit the diff)",
    )
    parser.add_argument(
        "--counts", action="store_true",
        help="print per-entry-point primitive counts (text mode; always "
        "present in --json)",
    )
    parser.add_argument(
        "--axes", default="",
        help="comma-separated extra declared axis names for TYA006 "
        "(beyond what the analyzed tree itself declares)",
    )
    parser.add_argument(
        "--list-rules", action="store_true", help="print the rule catalog"
    )
    return parser


def _force_cpu() -> None:
    """The checker is a host-side tool: tracing and compiling its tiny
    entries needs no accelerator, and it must not take a chip a task may
    need — narrow jax to the CPU platform exactly like tests/conftest.py."""
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
    except Exception:  # noqa: TYA011 — jax absent/locked: CPU narrowing is best-effort
        pass


def main(argv: Optional[List[str]] = None) -> int:
    try:
        args = _parser().parse_args(argv)
    except SystemExit as exc:
        # argparse exits 2 on usage errors and 0 for --help; our exit 2
        # means "findings", so usage errors become the engine-error code.
        return EXIT_CLEAN if exc.code == 0 else EXIT_ERROR

    if args.list_rules:
        for rule in RULES.values():
            print(f"{rule.code}  [{rule.engine:>5}]  {rule.name}: "
                  f"{rule.summary}")
        return EXIT_CLEAN

    only = args.hlo_only or args.concurrency_only
    run_ast = not args.no_ast and not only
    run_jaxpr = not args.no_jaxpr and not only
    run_hlo = not args.no_hlo and not args.concurrency_only
    run_conc = (
        args.concurrency_only
        or (not args.no_concurrency and not args.hlo_only)
    )

    findings: List[Finding] = []
    suppressed: List[Finding] = []
    skipped: List[str] = []
    counts: Dict[str, Dict[str, int]] = {}
    hlo_census: Dict[str, Dict] = {}
    race_report: Dict[str, Dict] = {}
    engine_seconds: Dict[str, float] = {}
    extra_axes = [a.strip() for a in args.axes.split(",") if a.strip()]

    if run_ast:
        from tf_yarn_tpu.analysis.ast_engine import analyze_paths

        started = time.monotonic()
        try:
            findings.extend(analyze_paths(args.paths, extra_axes=extra_axes))
        except FileNotFoundError as exc:
            print(f"error: no such path: {exc}", file=sys.stderr)
            return EXIT_ERROR
        except Exception as exc:
            print(f"error: ast engine failed: {exc}", file=sys.stderr)
            return EXIT_ERROR
        engine_seconds["ast"] = round(time.monotonic() - started, 2)

    if run_jaxpr:
        _force_cpu()
        from tf_yarn_tpu.analysis.jaxpr_engine import run as run_jaxpr_engine

        started = time.monotonic()
        try:
            jaxpr_findings, counts, jaxpr_skipped, jaxpr_suppressed = (
                run_jaxpr_engine()
            )
        except Exception as exc:
            print(f"error: jaxpr engine failed: {exc}", file=sys.stderr)
            return EXIT_ERROR
        findings.extend(jaxpr_findings)
        suppressed.extend(jaxpr_suppressed)
        skipped.extend(jaxpr_skipped)
        engine_seconds["jaxpr"] = round(time.monotonic() - started, 2)

    if run_hlo:
        _force_cpu()
        from tf_yarn_tpu.analysis.hlo_engine import run as run_hlo_engine

        started = time.monotonic()
        try:
            report = run_hlo_engine(
                update_budgets=args.update_hlo_budgets
            )
        except Exception as exc:
            print(f"error: hlo engine failed: {exc}", file=sys.stderr)
            return EXIT_ERROR
        findings.extend(report.findings)
        suppressed.extend(report.suppressed)
        skipped.extend(report.skipped)
        hlo_census = report.census
        engine_seconds["hlo"] = round(time.monotonic() - started, 2)
        if args.update_hlo_budgets:
            print(
                "hlo budgets updated from this run's census "
                f"({len(hlo_census)} entries)", file=sys.stderr,
            )

    if run_conc:
        from tf_yarn_tpu.analysis.concurrency import (
            analyze_paths as analyze_concurrency,
        )

        started = time.monotonic()
        try:
            findings.extend(analyze_concurrency(args.paths))
        except FileNotFoundError as exc:
            print(f"error: no such path: {exc}", file=sys.stderr)
            return EXIT_ERROR
        except Exception as exc:
            print(f"error: concurrency engine failed: {exc}",
                  file=sys.stderr)
            return EXIT_ERROR
        if not args.no_race:
            from tf_yarn_tpu.analysis.racecheck import run as run_racecheck

            try:
                race = run_racecheck()
            except Exception as exc:
                print(f"error: racecheck scenarios failed: {exc}",
                      file=sys.stderr)
                return EXIT_ERROR
            findings.extend(race.findings)
            suppressed.extend(race.suppressed)
            race_report = race.report
        engine_seconds["concurrency"] = round(time.monotonic() - started, 2)

    engines = "+".join(engine_seconds) or "no"
    if args.as_json:
        print(json.dumps({
            "json_schema_version": JSON_SCHEMA_VERSION,
            "findings": [f.to_json() for f in findings],
            "suppressed_findings": [f.to_json() for f in suppressed],
            "primitive_counts": counts,
            "hlo_census": hlo_census,
            "race_report": race_report,
            "skipped_entries": skipped,
            "engine_seconds": engine_seconds,
            "n_findings": len(findings),
        }, indent=1, sort_keys=True))
    else:
        for notice in skipped:
            print(f"skipped (environment): {notice}", file=sys.stderr)
        for finding in suppressed:
            print(
                f"suppressed (entry allow=): {finding.format()}",
                file=sys.stderr,
            )
        for finding in findings:
            print(finding.format())
        if args.counts and counts:
            print("\nper-entry primitive counts:")
            for name in sorted(counts):
                total = sum(counts[name].values())
                top = sorted(
                    counts[name].items(), key=lambda kv: -kv[1]
                )[:8]
                summary = ", ".join(f"{k}={v}" for k, v in top)
                print(f"  {name}: {total} eqns ({summary})")
        timing = " ".join(
            f"{name}={secs}s" for name, secs in engine_seconds.items()
        )
        print(
            f"{'no findings' if not findings else f'{len(findings)} finding(s)'}"
            f" ({engines} engines; {timing})"
        )
    return EXIT_FINDINGS if findings else EXIT_CLEAN
