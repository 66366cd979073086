"""``python -m tpurx_lint`` / ``tpurx-lint`` command line.

Exit codes: 0 clean (baselined findings allowed), 1 findings (or baseline
hygiene failures: unjustified or stale entries), 2 bad invocation.
"""

from __future__ import annotations

import argparse
import json
import sys

from .baseline import Baseline, DEFAULT_BASELINE
from .engine import run_lint
from .registry import all_rules


def _print(*parts):
    # stdout IS the interface of this CLI
    sys.stdout.write(" ".join(str(p) for p in parts) + "\n")


def _jobs_arg(val: str):
    if val == "auto":
        return "auto"
    try:
        return int(val)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--jobs expects an integer or 'auto', got {val!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="tpurx-lint",
        description="Resiliency static analysis for the tpu-resiliency repo.",
    )
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: tpu_resiliency tests "
                         "tpurx_lint)")
    ap.add_argument("--root", default=None,
                    help="repo root for relative paths (default: cwd)")
    ap.add_argument("--format", choices=("text", "json", "sarif"),
                    default="text")
    ap.add_argument("--jobs", type=_jobs_arg, default="auto", metavar="N",
                    help="parallel per-file lint processes ('auto' = cpu "
                         "count, 1 = serial; whole-program tier always runs "
                         "once in the parent)")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline file (default: {DEFAULT_BASELINE})")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding, ignoring the baseline")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write current findings as the new baseline "
                         "(justifications must then be filled in by hand)")
    ap.add_argument("--witness", action="append", metavar="FILE",
                    help="runtime lock-order sanitizer witness JSONL "
                         "(TPURX_SANITIZE=1 output; repeatable) — promotes "
                         "static TPURX011 cycles to CONFIRMED or prunes "
                         "false positives")
    ap.add_argument("--rule", action="append", dest="rules", metavar="TPURXnnn",
                    help="run only the given rule (repeatable)")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--show-baselined", action="store_true",
                    help="also list findings matched by the baseline")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rule in all_rules():
            _print(f"{rule.rule_id}  {rule.name}")
            _print(f"    scope: {', '.join(rule.scope)}"
                   + (f"  (exempt: {', '.join(rule.exclude)})" if rule.exclude else ""))
            _print(f"    {rule.rationale.strip()}")
        return 0

    result = run_lint(
        paths=args.paths or None,
        root=args.root,
        baseline_path=args.baseline,
        use_baseline=not args.no_baseline,
        rule_ids=args.rules,
        jobs=args.jobs,
        witness_path=args.witness,
    )

    if args.write_baseline:
        path = args.baseline or DEFAULT_BASELINE
        old = Baseline.load(path)
        carried = {e.key(): e.justification for e in old.entries}
        bl = Baseline.from_findings(result.findings + result.baselined, path)
        for e in bl.entries:
            e.justification = carried.get(e.key(), "")
        bl.save(path)
        _print(f"wrote {len(bl.entries)} entries to {path} "
               f"(fill in any empty justifications before committing)")
        return 0

    if args.format == "sarif":
        from .sarif import render
        import os
        root = os.path.abspath(args.root or os.getcwd())
        _print(json.dumps(render(result, all_rules(), root), indent=2))
    elif args.format == "json":
        _print(json.dumps({
            "findings": [f.to_dict() for f in result.findings],
            "baselined": [f.to_dict() for f in result.baselined],
            "parse_errors": [f.to_dict() for f in result.parse_errors],
            "witness_pruned": [f.to_dict() for f in result.witness_pruned],
            "stale_baseline": [
                {"rule": e.rule, "path": e.path, "symbol": e.symbol}
                for e in result.stale_baseline
            ],
            "unjustified_baseline": [
                {"rule": e.rule, "path": e.path, "symbol": e.symbol}
                for e in result.unjustified_baseline
            ],
            "ok": result.ok and not result.stale_baseline
                  and not result.unjustified_baseline,
        }, indent=2))
    else:
        for f in result.parse_errors:
            _print(f"{f.location()}: {f.rule} {f.message}")
        for f in result.findings:
            _print(f"{f.location()}: {f.rule} {f.message}")
        if args.show_baselined:
            for f in result.baselined:
                _print(f"{f.location()}: {f.rule} [baselined] {f.message}")
        for f in result.witness_pruned:
            _print(f"{f.location()}: {f.rule} [pruned by witness] {f.message}")
        for e in result.unjustified_baseline:
            _print(f"{e.path}: baseline entry for {e.rule} has no "
                   f"justification ({e.symbol!r})")
        for e in result.stale_baseline:
            _print(f"{e.path}: stale baseline entry for {e.rule} "
                   f"({e.symbol!r}) — offending line is gone; remove it")
        n = len(result.findings)
        b = len(result.baselined)
        _print(f"{n} finding(s), {b} baselined, "
               f"{len(result.parse_errors)} parse error(s)"
               + (f", {len(result.witness_pruned)} pruned by witness"
                  if result.witness_pruned else ""))

    failed = (not result.ok or result.stale_baseline
              or result.unjustified_baseline)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
