"""tpulint CLI: prove the train/eval steps are TPU-clean.

Runs both static-analysis layers (mx_rcnn_tpu/analysis/) and writes
``artifacts/tpulint_report.json``:

* layer 1 — AST lint over the jit-traced package source, diffed against
  the committed baseline (``tpulint_baseline.json``); only NEW findings
  fail.
* layer 2 — jaxpr/HLO invariants on the real jitted train/eval/proposal
  steps (f64-free, transfer-guard-clean, trace-deterministic,
  donation-applied, >=99% FLOP attribution, and TPU006: no bf16->f32
  upcast outside the accumulation allowlist in the bf16-mixed train
  step).  No suppressions.

Usage:
  python tools/tpulint.py --check                 # CI gate: exit 1 on any
                                                  # new finding / failed
                                                  # invariant
  python tools/tpulint.py                         # report only, exit 0
  python tools/tpulint.py --ast-only [paths...]   # fast source-only pass
  python tools/tpulint.py --jaxpr-only            # invariants only
  python tools/tpulint.py --write-baseline        # refreeze layer 1
                                                  # (review the diff!)

Runs entirely under JAX_PLATFORMS=cpu — no accelerator needed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# The jaxpr layer traces and jits the tiny train step.  What it checks are
# properties of the traced program, not of a backend, so the lint runs on
# the CPU unless told otherwise and never takes a chip from another
# process.
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero on new findings / failed invariants")
    ap.add_argument("--ast-only", action="store_true")
    ap.add_argument("--jaxpr-only", action="store_true")
    ap.add_argument("--write-baseline", action="store_true",
                    help="freeze current layer-1 findings as the baseline")
    ap.add_argument("--baseline",
                    default=os.path.join(REPO_ROOT, "tpulint_baseline.json"))
    ap.add_argument("--config", default="tiny_synthetic",
                    help="config preset traced by the jaxpr layer")
    ap.add_argument("--out",
                    default=os.path.join(REPO_ROOT, "artifacts",
                                         "tpulint_report.json"))
    ap.add_argument("paths", nargs="*",
                    help="repo-relative files for the AST layer "
                         "(default: all traced modules)")
    args = ap.parse_args(argv)

    from mx_rcnn_tpu.analysis import (
        RULES,
        collect_counts,
        lint_paths,
        load_baseline,
        new_findings,
        run_jaxpr_checks,
        traced_files,
        write_baseline,
    )

    report: dict = {"rules": RULES, "config": args.config}
    failed = False

    if not args.jaxpr_only:
        findings = lint_paths(REPO_ROOT, args.paths or None)
        if args.write_baseline:
            write_baseline(args.baseline, findings)
            print(f"baseline frozen: {len(findings)} findings -> "
                  f"{args.baseline}", file=sys.stderr)
        baseline = load_baseline(args.baseline)
        new = new_findings(findings, baseline)
        report["ast"] = {
            "files_scanned": len(args.paths or traced_files(REPO_ROOT)),
            "total_findings": len(findings),
            "baselined": len(findings) - len(new),
            "new": [
                {"rule": f.rule, "path": f.path, "line": f.line,
                 "snippet": f.snippet, "fingerprint": f.fingerprint()}
                for f in new
            ],
            "per_rule": {
                rule: sum(1 for f in findings if f.rule == rule)
                for rule in sorted(RULES)
            },
            "fingerprints": collect_counts(findings),
        }
        for f in new:
            print(f"NEW {f.format()}", file=sys.stderr)
        if new:
            failed = True
            print(f"tpulint: {len(new)} new AST finding(s) beyond baseline",
                  file=sys.stderr)
        else:
            print(f"tpulint: AST layer clean "
                  f"({len(findings)} baselined finding(s))", file=sys.stderr)

    if not args.ast_only:
        results = run_jaxpr_checks(args.config)
        report["jaxpr"] = [r.as_dict() for r in results]
        for r in results:
            mark = "PASS" if r.ok else "FAIL"
            print(f"{mark} {r.name}: {r.detail}", file=sys.stderr)
        if not all(r.ok for r in results):
            failed = True

    report["ok"] = not failed
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {args.out}", file=sys.stderr)
    print(json.dumps({"metric": "tpulint_ok", "value": bool(report["ok"])}))
    if args.check and failed:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
