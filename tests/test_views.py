"""Renderer smoke tests: every ``tools/*_view.py`` script drives its
real snapshot fixture end-to-end (ISSUE 14 satellite).

The fixtures under ``tests/data/`` are genuine payloads dumped from
deterministic sim runs — ``chain_status.json`` /
``fleet_status.json`` / ``incident_dump.json`` came out of one
``equivocating_validator`` run (seed ``b"fixtures"``, 20 nodes) and
``profile_dump.json`` out of ``gateway_hotspot_pool`` — so a renderer
that drifts from its plane's snapshot shape fails here, not in an
operator's terminal. Each viewer must exit 0, print its section
anchors, and refuse a payload belonging to a different RPC.
"""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "tests", "data")


def _viewer(name):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


def _fixture(name):
    return os.path.join(DATA, name)


class TestViewerSmoke:
    def test_chain_view_renders_the_chain_status_fixture(self, capsys):
        mod = _viewer("chain_view")
        assert mod.main([_fixture("chain_status.json")]) == 0
        out = capsys.readouterr().out
        assert "chain plane:" in out
        assert "consensus:" in out
        assert "equivocation evidence" in out
        assert "block-equivocation" in out
        assert "market:" in out
        assert "anomalies:" in out
        assert "transition log" in out

    def test_chain_view_node_table_is_capped(self, capsys):
        mod = _viewer("chain_view")
        assert mod.main([_fixture("chain_status.json"),
                         "--nodes", "3"]) == 0
        out = capsys.readouterr().out
        assert "top 3 of" in out

    def test_fleet_view_renders_the_fleet_status_fixture(self, capsys):
        mod = _viewer("fleet_view")
        assert mod.main([_fixture("fleet_status.json")]) == 0
        out = capsys.readouterr().out
        assert "fleet plane @" in out
        # the chain-plane fold is visible at fleet level: the board
        # carries the finality_lag SLO class next to head
        assert "finality_lag" in out

    def test_profile_view_renders_the_profile_dump_fixture(self,
                                                           capsys):
        mod = _viewer("profile_view")
        assert mod.main([_fixture("profile_dump.json")]) == 0
        out = capsys.readouterr().out
        assert "profile plane:" in out
        assert "pad ledger:" in out
        assert "compile ledger:" in out

    def test_incident_view_renders_the_incident_dump_fixture(self,
                                                             capsys):
        mod = _viewer("incident_view")
        assert mod.main([_fixture("incident_dump.json")]) == 0
        out = capsys.readouterr().out
        assert "incident #" in out
        assert "equivocation" in out
        assert "finality-stall" in out

    def test_remediation_view_renders_the_remediation_status_fixture(
            self, capsys):
        # fixture dumped from one perf_regression_autopilot run
        # (seed b"fixtures", 20 nodes): two perf-pin fire/release
        # episodes live in its journal tail
        mod = _viewer("remediation_view")
        assert mod.main([_fixture("remediation_status.json")]) == 0
        out = capsys.readouterr().out
        assert "remediation plane" in out
        assert "policy table (" in out
        assert "engagements (" in out
        assert "detector evidence (" in out
        assert "action journal (" in out
        assert "perf-pin" in out
        assert "pin-reference" in out

    def test_custody_view_renders_the_custody_status_fixture(
            self, capsys):
        # fixture dumped from one miner_attrition run (seed
        # b"fixtures", 20 nodes): two silent-death -> proactive-repair
        # episodes live in its timelines and transition log
        mod = _viewer("custody_view")
        assert mod.main([_fixture("custody_status.json")]) == 0
        out = capsys.readouterr().out
        assert "custody plane @" in out
        assert "margin histogram (" in out
        assert "at-risk (" in out
        assert "segments (worst" in out
        assert "fragment timelines (" in out
        assert "anomaly transition log (" in out
        # the drill's lineage is visible end-to-end: the silent death
        # surfaced as a restoral, the proactive rebuild as a repair,
        # and the at_risk edge both fired and released
        assert "restoral" in out and "repair(" in out
        assert "at_risk" in out and "ok -> bad" in out \
            and "bad -> ok" in out

    def test_custody_view_segment_table_is_capped(self, capsys):
        mod = _viewer("custody_view")
        assert mod.main([_fixture("custody_status.json"),
                         "--segments", "1", "--timelines", "2"]) == 0
        out = capsys.readouterr().out
        assert "segments (worst 1 of" in out
        assert "fragment timelines (first 2 of" in out

    def test_viewers_reject_foreign_payloads(self):
        # each _load names its RPC in the rejection so an operator
        # who mixes up dump files learns which file they actually got
        for viewer, wrong in (("chain_view", "fleet_status.json"),
                              ("fleet_view", "chain_status.json"),
                              ("profile_view", "chain_status.json"),
                              ("incident_view", "profile_dump.json"),
                              ("remediation_view",
                               "chain_status.json"),
                              ("custody_view",
                               "remediation_status.json")):
            mod = _viewer(viewer)
            with pytest.raises(SystemExit):
                mod.main([_fixture(wrong)])
