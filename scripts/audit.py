#!/usr/bin/env python
"""The audit gate: lint the repo, optionally audit the pinned programs.

Usage::

    python scripts/audit.py [paths...]            # lint (default: dtdl_tpu/)
    python scripts/audit.py --list-rules          # the rule catalog
    python scripts/audit.py --programs            # + jaxpr/HLO contract audits
    python scripts/audit.py --programs --rebase   # regenerate baselines.json
    python scripts/audit.py --json                # machine-readable findings

Exit status: 0 when every finding is suppressed (``# audit: ok[rule-id]
reason`` on the offending or preceding line) and — under ``--programs``
— the census matches dtdl_tpu/analysis/baselines.json; 1 otherwise.
The lint half is pure AST (sub-second) and is what
tests/test_analysis_gate.py runs inside tier-1; ``--programs`` builds
and compiles the real train/megatron/decode/verify programs (tens of
seconds on CPU) — the same check the slow-marked
tests/test_analysis_contracts.py runs.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

_REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("paths", nargs="*", default=None,
                   help="files/dirs to lint (default: dtdl_tpu/)")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule catalog and exit")
    p.add_argument("--rules", default=None,
                   help="comma-separated rule-id (prefix) filter")
    p.add_argument("--programs", action="store_true",
                   help="also audit the pinned programs (compiles; see "
                        "dtdl_tpu/analysis/contracts.py)")
    p.add_argument("--rebase", action="store_true",
                   help="with --programs: write the observed census as "
                        "the new baselines.json")
    p.add_argument("--json", action="store_true",
                   help="emit findings as JSON instead of the report")
    args = p.parse_args(argv)

    from dtdl_tpu.analysis import lint_paths, render_report, rule_docs

    if args.list_rules:
        for rid, doc in rule_docs().items():
            print(f"{rid:24s} {doc}")
        return 0

    paths = args.paths or [str(_REPO / "dtdl_tpu")]
    only = args.rules.split(",") if args.rules else None
    findings = lint_paths(paths, root=str(_REPO), only_rules=only)

    reports = {}
    if args.programs:
        from dtdl_tpu.analysis import contracts
        runnable, skipped = contracts.runnable_programs()
        for name in skipped:
            print(f"{name}: SKIPPED (needs "
                  f"{contracts.MIN_DEVICES[name]} devices; run under "
                  f"XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                  f"to audit it on CPU)", file=sys.stderr)
        reports = contracts.audit_programs(runnable)
        for rep in reports.values():
            findings.extend(rep.pop("_findings"))
        if args.rebase:
            path = contracts.save_baseline(reports)
            print(f"baseline written: {path}", file=sys.stderr)
        else:
            findings.extend(contracts.compare_to_baseline(
                reports, contracts.load_baseline()))

    if args.json:
        out = {"findings": [vars(f) | {"detail": f.detail}
                            for f in findings]}
        if reports:
            out["programs"] = {k: {kk: vv for kk, vv in v.items()
                                   if kk != "_findings"}
                               for k, v in reports.items()}
        print(json.dumps(out, indent=2, default=str))
    else:
        if reports:
            for name, rep in sorted(reports.items()):
                cc = {**rep["jaxpr_collectives"],
                      **rep["hlo_collectives"]}
                cstr = ", ".join(f"{k} x{v['count']}"
                                 for k, v in cc.items()) or "none"
                print(f"{name}: collectives [{cstr}], "
                      f"host_transfers={rep['host_transfers']}, "
                      f"donated {rep['n_donated_args']}/"
                      f"{rep['n_expected_donated']} args "
                      f"({rep['donated_bytes']} B)")
        if findings:
            print(render_report(
                findings,
                header=f"{len(findings)} unsuppressed finding(s):"))
        else:
            print("audit clean: no unsuppressed findings")
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
