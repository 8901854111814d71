"""The tier-1 lint gate (ISSUE 15): dtdl_tpu/ must audit clean.

AST-only — no compilation, seconds — so the invariants the repo's
performance story rests on (no hot-path host syncs, one-spelling
shard_map, donation on step jits, catalog consistency) fail HERE, by
rule id, instead of surfacing as a mystery MFU drop three PRs later.
"""

import pathlib

import pytest

import dtdl_tpu
from dtdl_tpu.analysis import lint_paths, render_report, rule_docs
from dtdl_tpu.analysis.findings import scan_suppressions

PKG = pathlib.Path(dtdl_tpu.__file__).parent
REPO = PKG.parent


def test_package_audits_clean():
    """Zero unsuppressed findings over the whole package — the same
    check ``scripts/audit.py dtdl_tpu/`` gates on."""
    findings = lint_paths([str(PKG)], root=str(REPO))
    assert not findings, "\n" + render_report(
        findings, header="lint gate: unsuppressed findings —")


def test_every_suppression_carries_a_reason():
    """The suppression contract: ``# audit: ok[rule] reason`` — a bare
    ok is itself a finding, so this is belt-and-braces over the gate,
    and it pins the count so suppressions cannot quietly multiply."""
    sups = []
    for f in sorted(PKG.rglob("*.py")):
        if "__pycache__" in f.parts:
            continue
        rel = f.relative_to(REPO).as_posix()
        sups.extend(scan_suppressions(rel, f.read_text()))
    assert sups, "expected the documented deliberate-sync suppressions"
    for s in sups:
        assert s.reason, f"{s.path}:{s.line}: suppression without reason"
    # deliberate host-boundary suppressions, each reviewed in ISSUE 15;
    # growing this number needs the same review — keep it current
    assert len(sups) <= 40, (
        f"{len(sups)} suppressions — review the new ones and raise "
        f"this bound deliberately, not by drift")


def test_rule_catalog_is_stable():
    """Every rule id is kebab-case with a one-line doc, and the core
    rule families the README documents exist."""
    docs = rule_docs()
    for rid, doc in docs.items():
        assert rid == rid.lower() and " " not in rid, rid
        assert doc.strip()
    for family in ("host-sync-get", "host-sync-item", "compat-shard-map",
                   "jit-donate", "trace-host-time", "trace-host-rng",
                   "obs-event-uncataloged", "metrics-window-counter"):
        assert family in docs, f"rule {family} vanished from the registry"


def test_cli_gate_entrypoint():
    """scripts/audit.py main(): clean lint exits 0; --list-rules prints
    the catalog (in-process — the CLI is the same lint_paths call)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "audit_cli", REPO / "scripts" / "audit.py")
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    assert cli.main([str(PKG)]) == 0
    assert cli.main(["--list-rules"]) == 0


def test_baseline_checked_in():
    """The collective-census baseline the contract tests pin against
    must be committed (regenerate: scripts/audit.py --programs
    --rebase)."""
    from dtdl_tpu.analysis import contracts
    base = contracts.load_baseline()
    assert set(base) == set(contracts.PROGRAMS), (
        f"baselines.json programs {sorted(base)} != "
        f"{sorted(contracts.PROGRAMS)}")
    for name, fields in base.items():
        assert set(fields) == set(contracts.BASELINE_FIELDS), name
        assert fields["donation_ok"] is True, (
            f"{name}: checked-in baseline records a donation failure")
        assert fields["host_transfers"] == 0 and fields["callbacks"] == 0


@pytest.mark.parametrize("path", ["scripts", "examples"])
def test_satellite_trees_have_no_stale_suppressions(path):
    """scripts/ and examples/ are linted too (they drive the hot paths);
    today they need zero suppressions — keep it that way."""
    findings = lint_paths([str(REPO / path)], root=str(REPO))
    assert not findings, "\n" + render_report(findings)
