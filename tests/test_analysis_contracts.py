"""Pinned program contracts (ISSUE 15): the REAL hot-path programs,
audited against the checked-in census baseline.

The whole module is slow-marked (it compiles the train step, the 4D
megatron step, and the serve decode/verify pair — ~40s on CPU); the
same audit runs by hand through ``scripts/audit.py --programs`` where
tier-1 skips the compile cost.

Contracts pinned here (the acceptance criteria of ISSUE 15):

* train-step state fully donated (every state leaf aliased in the
  optimized module);
* the serve decode/verify programs contain ZERO host
  transfers/callbacks and donate the whole KV arena;
* each program's collective census (jaxpr AND compiled HLO, counts and
  bytes) matches dtdl_tpu/analysis/baselines.json exactly — a GSPMD
  resharding that sneaks in an all-gather is a named diff, not a
  mystery MFU drop.
"""

import pytest

from dtdl_tpu.analysis import contracts

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def reports(devices):
    assert len(devices) == 8
    return contracts.audit_programs()


def test_census_matches_checked_in_baseline(reports):
    drift = contracts.compare_to_baseline(reports,
                                          contracts.load_baseline())
    assert not drift, "\n".join(f.render() for f in drift)


def test_train_steps_fully_donated(reports):
    for name in ("train_step", "megatron_step"):
        rep = reports[name]
        assert rep["donation_ok"], rep["findings"]
        assert rep["n_donated_args"] == rep["n_expected_donated"] > 0
        assert rep["donated_bytes"] > 0


def test_serve_programs_zero_host_traffic_and_arena_donated(reports):
    for name in ("serve_decode", "serve_verify"):
        rep = reports[name]
        assert rep["callbacks"] == 0, name
        assert rep["host_transfers"] == 0, name
        assert rep["donation_ok"], rep["findings"]
        # the donated KV arena IS the receipt that decode updates the
        # largest serving buffer in place
        assert rep["donated_bytes"] > 0
        # single-chip engine: no collectives of any kind
        assert rep["jaxpr_collectives"] == {}
        assert rep["hlo_collectives"] == {}


def test_no_program_findings_at_all(reports):
    for name, rep in reports.items():
        assert rep["findings"] == [], (name, rep["findings"])


def test_megatron_census_has_the_handwritten_collectives(reports):
    """The 4D step's manual-SPMD shape: psums (grad/loss reductions) and
    ppermutes (pipeline edges) present at jaxpr level, surviving into
    the compiled module as all-reduce/collective-permute."""
    j = reports["megatron_step"]["jaxpr_collectives"]
    h = reports["megatron_step"]["hlo_collectives"]
    assert j["psum"]["count"] > 0 and j["ppermute"]["count"] > 0
    assert h["all-reduce"]["count"] > 0
    assert h["collective-permute"]["count"] > 0
