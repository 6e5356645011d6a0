"""Chain-plane observability (ISSUE 14): consensus health, the
storage-market ledger, byzantine anomaly detection — and the two
contracts everything in ``cess_tpu/obs`` lives by:

- zero-cost-when-off: a node that never armed ``--chainwatch`` has
  ``chainwatch`` unset/None, exports no ``cess_chain_*`` gauges, and
  a scenario without ``chainwatch=True`` leaves the chain slot of the
  sim witness empty — the disarmed paths are byte-identical;
- count-sequenced determinism: two same-seed ``equivocating_validator``
  runs replay every chain-plane witness byte-for-byte.

Plus the detector units (reorg-depth inference, BABE-shaped
block-equivocation evidence, the audit-failure-spike window, the
fake-capacity heuristic, edge-triggered anomaly transitions) and
hostile-input hardening for the gossip-frame ingest path.
"""
import json

import pytest

from cess_tpu import obs
from cess_tpu.obs import flight as _obs_flight
from cess_tpu.obs.chainwatch import (ChainAnomalyDetector, ChainWatch,
                                     ConsensusWatch, MarketWatch,
                                     lag_state)
from cess_tpu.sim.scenarios import SCENARIOS, run_scenario


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    obs.disarm()
    _obs_flight.disarm()


def _state(head, finalized, *, tail=None, blocks=(), locks=(),
           votes=(), slot=0, era=0, forks=0):
    return {
        "head": head, "finalized": finalized, "slot": slot,
        "era": era, "forks": forks,
        "tail": tail if tail is not None
        else {str(n): f"h{n}" for n in range(head + 1)},
        "blocks": list(blocks), "locks": list(locks),
        "vote_equivocations": list(votes),
    }


# -- consensus units ---------------------------------------------------------
class TestConsensusWatch:
    def test_lag_state_grading(self):
        assert lag_state(0) == "ok"
        assert lag_state(3) == "ok"
        assert lag_state(4) == "warn"
        assert lag_state(9) == "warn"
        assert lag_state(10) == "burning"

    def test_reorg_depth_is_inferred_from_the_tail_diff(self):
        w = ConsensusWatch()
        w.observe("n0", _state(5, 3))
        # pure extension: same hashes below, new head on top
        ext = {str(n): f"h{n}" for n in range(6)}
        ext["6"] = "h6"
        w.observe("n0", _state(6, 4, tail=ext))
        assert w.views()["n0"]["reorg_depth"] == 0
        # blocks 5..6 replaced by a side branch: depth = old head (6)
        # minus the deepest common height (4)
        reorg = {str(n): f"h{n}" for n in range(5)}
        reorg["5"] = "h5'"
        reorg["6"] = "h6'"
        w.observe("n0", _state(6, 4, tail=reorg))
        assert w.views()["n0"]["reorg_depth"] == 2
        snap = w.snapshot()
        assert snap["reorgs"] == 1 and snap["max_reorg_depth"] == 2

    def test_block_equivocation_needs_two_hashes_one_slot(self):
        w = ConsensusWatch()
        w.observe("n0", _state(3, 2, blocks=[["v1", 7, "aa"]]))
        assert w.evidence() == ()
        # a second DISTINCT hash for the same (author, slot) — seen
        # via a different node's view — is the BABE equivocation shape
        w.observe("n1", _state(3, 2, blocks=[["v1", 7, "bb"]]))
        ev = w.evidence()
        assert len(ev) == 1
        assert ev[0] == {"kind": "block-equivocation", "offender": "v1",
                         "round": 7, "hashes": ["aa", "bb"]}
        # the same pair reported again does not duplicate evidence
        w.observe("n2", _state(3, 2, blocks=[["v1", 7, "aa"],
                                             ["v1", 7, "bb"]]))
        assert len(w.evidence()) == 1

    def test_vote_equivocation_and_lock_ages(self):
        w = ConsensusWatch()
        w.observe("n0", _state(10, 8, locks=[["acct", 4]],
                               votes=[["v2", 5, "cc", "dd"]]))
        v = w.views()["n0"]
        assert v["locks"] == 1 and v["max_lock_age"] == 6
        ev = w.evidence()
        assert ev[0]["kind"] == "vote-equivocation"
        assert ev[0]["offender"] == "v2"
        assert ev[0]["hashes"] == ["cc", "dd"]

    def test_malformed_state_is_dropped_whole(self):
        w = ConsensusWatch()
        w.observe("n0", _state(3, 2))
        for garbage in (None, 42, {}, {"head": "x"},
                        {"head": 1, "finalized": 0, "tail": 7},
                        {"head": 1, "finalized": 0, "tail": {},
                         "blocks": [["only-two", 1]]}):
            w.observe("n0", garbage)
        # the good view survives untouched; nothing partially applied
        assert w.views()["n0"]["head"] == 3
        assert w.snapshot()["scans"] == 1


# -- market units ------------------------------------------------------------
def _market(verdicts, *, service=0, audited=0):
    return {
        "miners": {"m0": {"idle": 100, "service": service, "lock": 0,
                          "state": "positive", "audited": audited}},
        "verdicts": {"m0": verdicts},
        "restoral": {"open": 1, "claimed": 1, "generated": 2,
                     "claims": 3, "completed": 1},
    }


class TestMarketWatch:
    def test_audit_failure_spike_window(self):
        w = MarketWatch(spike_window=4, spike_fails=3)
        # 3 fails, but only 2 inside the last-4 window: no spike
        w.observe(_market([0, 1, 1, 0, 1, 0, 1, 1]))
        assert w.spikes() == ()
        # 3 fails inside the window: spike
        w.observe(_market([1, 1, 0, 0, 1, 0]))
        assert w.spikes() == ("m0",)
        m = w.snapshot()["miners"]["m0"]
        assert m["passes"] == 3 and m["fails"] == 3 and m["spike"]

    def test_fake_capacity_is_declared_vs_audited_drift(self):
        w = MarketWatch()
        w.observe(_market([1], service=100, audited=49))
        m = w.snapshot()["miners"]["m0"]
        assert m["drift"] == 51 and m["fake_capacity"]
        # recompute-and-replace is idempotent: audits catching up
        # clears the flag on the next scan, no cursor state left over
        w.observe(_market([1], service=100, audited=80))
        m = w.snapshot()["miners"]["m0"]
        assert m["drift"] == 20 and not m["fake_capacity"]
        assert w.snapshot()["space"]["drift"] == 20

    def test_malformed_market_is_dropped_whole(self):
        w = MarketWatch()
        w.observe(_market([1], service=8, audited=8))
        for garbage in (None, [], {"miners": {"m1": {}}},
                        {"miners": {"m1": {"idle": "x", "service": 0}}}):
            w.observe(garbage)
        snap = w.snapshot()
        assert list(snap["miners"]) == ["m0"] and snap["scans"] == 1


# -- anomaly detector units --------------------------------------------------
class TestChainAnomalyDetector:
    def test_transitions_are_edge_triggered(self):
        det = ChainAnomalyDetector()
        det.update("finality-stall", "n0", True, lag=5)
        det.update("finality-stall", "n0", True, lag=6)   # no new edge
        det.update("finality-stall", "n0", False, lag=0)
        det.update("finality-stall", "n0", False, lag=0)  # no new edge
        assert det.transition_log() == (
            (1, "finality-stall", "n0", "ok", "bad"),
            (2, "finality-stall", "n0", "bad", "ok"))
        snap = det.snapshot()
        assert snap["seq"] == 2 and snap["anomalies"] == 1
        assert snap["active"]["finality-stall"] == []

    def test_each_bad_edge_announces_one_flight_note(self):
        from cess_tpu.obs import flight
        rec = flight.arm(flight.FlightRecorder(b"t"))
        det = ChainAnomalyDetector()
        det.update("deep-reorg", "n3", True, depth=4)
        det.update("deep-reorg", "n3", True, depth=5)
        notes = [e for e in rec.journal_tail("chain")
                 if e["kind"] == "anomaly"]
        assert len(notes) == 1
        d = notes[0]["detail"]
        assert d["cls"] == "deep-reorg" and d["key"] == "n3"
        assert d["frm"] == "ok" and d["to"] == "bad" and d["depth"] == 4

    def test_witness_is_canonical_bytes(self):
        a, b = ChainAnomalyDetector(), ChainAnomalyDetector()
        for det in (a, b):
            det.update("equivocation", "v1@7", True)
            det.update("finality-stall", "n0", True)
            det.update("finality-stall", "n0", False)
        assert a.witness() == b.witness()
        canon = json.loads(a.witness())
        assert canon["active"] == [["equivocation", "v1@7"]]
        assert len(canon["transitions"]) == 3


# -- the composed plane ------------------------------------------------------
class TestChainWatch:
    def test_seal_round_runs_every_detector(self):
        w = ChainWatch("probe", stall_lag=4)
        w.ingest_state("n0", _state(9, 3))           # lag 6: stall
        w.ingest_state("n1", _state(9, 8))           # lag 1: fine
        w.ingest_state("n0", _state(9, 3, blocks=[["v1", 7, "aa"]]))
        w.ingest_state("n1", _state(9, 8, blocks=[["v1", 7, "bb"]]))
        w.ingest_market(_market([0, 0, 0]))
        w.seal_round()
        active = w.anomalies.active()
        assert active["finality-stall"] == ["n0"]
        assert active["equivocation"] == ["v1@7"]
        assert active["audit-failure-spike"] == ["m0"]
        m = w.metrics()
        assert m["cess_chain_rounds"] == 1.0
        assert m["cess_chain_nodes"] == 2.0
        assert m["cess_chain_equivocations_total"] == 1.0
        assert m["cess_chain_stalled_nodes"] == 1.0
        assert m["cess_chain_audit_fail_spikes"] == 1.0
        # recovery clears the stall edge on the next seal
        w.ingest_state("n0", _state(9, 9, blocks=[["v1", 7, "aa"]]))
        w.seal_round()
        assert w.anomalies.active().get("finality-stall", []) == []

    def test_hundred_node_scan_finds_the_one_double_signer(self):
        """Five sealed rounds over 100 synthesized node states and an
        eight-miner market: node 7 lags and claims a twin block at
        every head, so the scan must come back with equivocation
        evidence and active anomalies — a silently empty scan at fleet
        scale cannot pass."""
        from cess_tpu.obs.chainwatch import TAIL

        def state(i, rnd):
            h = (i * 2654435761 + rnd * 40503) & 0xFFFF
            head = rnd * 3 + (h % 2)
            finalized = max(0, head - (6 if i == 7 else h % 3))
            blocks = [[f"v{i % 4}", head, f"b{i % 5}-{head}"]]
            if i == 7:
                blocks.append([f"v{i % 4}", head, f"b-twin-{head}"])
            return _state(
                head, finalized, slot=head + 1, era=head // 10,
                forks=h % 3, blocks=blocks,
                locks=[["acct", max(0, head - 2)]],
                tail={str(n): f"{i % 5}-{n}"
                      for n in range(max(0, head - TAIL), head + 1)})

        def market(rnd):
            return {
                "miners": {f"m{j}": {"idle": 1 << 28,
                                     "service": j << 23, "lock": 0,
                                     "state": "positive",
                                     "audited": j << 23}
                           for j in range(8)},
                "verdicts": {f"m{j}": [int((j + k + rnd) % 4 != 0)
                                       for k in range(8)]
                             for j in range(8)},
                "restoral": {"open": rnd % 2, "claimed": 0,
                             "generated": rnd, "claims": rnd,
                             "completed": rnd}}

        w = ChainWatch("fleet")
        for rnd in range(6):
            for i in range(100):
                w.ingest_state(f"n{i:03d}", state(i, rnd))
            w.ingest_market(market(rnd))
            w.seal_round()
        snap = w.snapshot()
        assert w.metrics()["cess_chain_nodes"] == 100.0
        assert len(snap["consensus"]["equivocations"]) >= 1
        assert snap["anomalies"]["anomalies"] >= 1
        assert len(snap["market"]["miners"]) == 8
        assert any(k.startswith("v3@")
                   for k in w.anomalies.active()["equivocation"])

    def test_ingest_frame_survives_hostile_peers(self):
        w = ChainWatch("probe")
        for frame in (None, 42, ("inst",), ("inst", None, "not-json"),
                      ("inst", None, json.dumps(["not", "a", "dict"])),
                      ("inst", None, json.dumps({"chain": "bogus"})),
                      ("inst", None, json.dumps({"targets": {}}))):
            w.ingest_frame(frame)
        assert w.consensus.views() == {}
        good = ("n9", None, json.dumps({"chain": _state(4, 2)}))
        w.ingest_frame(good)
        assert w.consensus.views()["n9"]["lag"] == 2

    def test_snapshot_is_json_safe(self):
        w = ChainWatch("probe")
        w.ingest_state("n0", _state(3, 2))
        w.ingest_market(_market([1]))
        w.seal_round()
        snap = w.snapshot()
        json.dumps(snap)
        assert snap["instance"] == "probe" and snap["rounds"] == 1
        assert set(snap) == {"instance", "rounds", "consensus",
                             "market", "anomalies"}


# -- zero-cost-when-off pins -------------------------------------------------
class TestDisarmedIsFree:
    def test_node_has_no_chain_gauges_when_disarmed(self):
        from cess_tpu.node.chain_spec import dev_spec
        from cess_tpu.node.metrics import collect, render_metrics
        from cess_tpu.node.network import Node

        node = Node(dev_spec(), "cold-node", {})
        assert getattr(node, "chainwatch", None) is None
        m = collect(node)
        assert not any(k.startswith("cess_chain_") for k in m)
        # ...and the build-info gauge is there regardless (ISSUE 14
        # satellite): value 1, instance + version labels
        expo = render_metrics(node)
        lines = [l for l in expo.splitlines()
                 if l.startswith("cess_build_info")]
        assert len(lines) == 1
        assert 'instance="cold-node"' in lines[0]
        assert 'version=' in lines[0]
        assert lines[0].endswith(" 1")

    def test_rpc_returns_none_when_disarmed(self):
        from cess_tpu.node.chain_spec import dev_spec
        from cess_tpu.node.network import Node
        from cess_tpu.node.rpc import RpcServer

        node = Node(dev_spec(), "rpc-node", {})
        rpc = RpcServer(node, port=0).start()
        try:
            assert rpc.handle("cess_chainStatus", []) is None
            node.chainwatch = ChainWatch("rpc-node")
            node.chainwatch.ingest_state("rpc-node", _state(2, 1))
            dump = rpc.handle("cess_chainStatus", [])
            assert dump["consensus"]["nodes"]["rpc-node"]["lag"] == 1
            json.dumps(dump)
        finally:
            rpc.stop()

    def test_armed_node_exports_chain_gauges(self):
        from cess_tpu.node.chain_spec import dev_spec
        from cess_tpu.node.metrics import collect
        from cess_tpu.node.network import Node

        node = Node(dev_spec(), "hot-node", {})
        node.chainwatch = ChainWatch("hot-node")
        node.chainwatch.ingest_state("hot-node", _state(5, 2))
        node.chainwatch.seal_round()
        m = collect(node)
        assert m["cess_chain_head"] == 5.0
        assert m["cess_chain_finality_lag"] == 3.0

    def test_build_info_is_relabeled_by_the_federator(self):
        from cess_tpu.node.chain_spec import dev_spec
        from cess_tpu.node.metrics import render_metrics
        from cess_tpu.node.network import Node
        from cess_tpu.obs.fleet import MetricFederator

        node = Node(dev_spec(), "build-node", {})
        fed = MetricFederator()
        fed.scrape_round({"fleet-inst": render_metrics(node)})
        gauges = fed.snapshot()["gauges"]
        keys = [k for k in gauges if k.startswith("cess_build_info")]
        assert len(keys) == 1
        # the scrape instance label WINS over the node's own — one
        # series per fleet member even when nodes share a name
        assert 'instance="fleet-inst"' in keys[0]
        assert 'version=' in keys[0]
        assert gauges[keys[0]] == 1.0

    def test_unarmed_scenario_has_an_empty_chain_witness_slot(self):
        sc = SCENARIOS["partition_heal"]
        report = run_scenario(sc, b"cold", n_nodes=8)
        assert report.chainwatch is None
        w = report.witness()
        # 8-tuple since the custody plane joined the witness; every
        # optional plane is empty-bytes when unarmed
        assert len(w) == 8 and w[5] == b"" and w[6] == b"" \
            and w[7] == b""


# -- the replay drill --------------------------------------------------------
class TestSameSeedReplay:
    def test_equivocating_validator_chain_witnesses_replay(self):
        sc = SCENARIOS["equivocating_validator"]
        a = run_scenario(sc, b"drill", n_nodes=12)
        b = run_scenario(sc, b"drill", n_nodes=12)
        wa, wb = a.chainwatch.witness(), b.chainwatch.witness()
        assert isinstance(wa, bytes) and wa == wb
        assert a.chainwatch.anomalies.witness() \
            == b.chainwatch.anomalies.witness()
        assert a.witness() == b.witness()
        assert a.witness()[5] == wa
        # the witness really carries all three parts, and the run
        # really produced evidence + anomalies to replay
        canon = json.loads(wa)
        assert set(canon) == {"consensus", "market", "transitions"}
        assert canon["consensus"]["equivocations"]
        assert canon["transitions"]
        # ...and a different seed is a different chain-plane history
        c = run_scenario(sc, b"other", n_nodes=12)
        assert c.chainwatch.witness() != wa
