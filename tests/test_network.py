"""Full-network integration: consensus + chain + off-chain agents + TPU
data plane, multi-replica determinism, audit liveness, data-loss repair.

This is the multi-node behavior the reference never tests in-repo
(SURVEY.md §4: "Multi-node behavior is NOT tested... exercised only on
live dev/testnets").
"""
import numpy as np
import pytest

from cess_tpu import constants
from cess_tpu.chain.file_bank import UserBrief
from cess_tpu.crypto.hashing import fragment_hash
from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
from cess_tpu.node.chain_spec import ChainSpec, ValidatorGenesis, dev_spec, local_spec
from cess_tpu.node.network import Network, Node
from cess_tpu.node.offchain import MinerAgent, OssGateway, TeeAgent, ValidatorOcw
from cess_tpu.ops import podr2

D = constants.DOLLARS


def make_net(n_validators=3):
    spec = ChainSpec(
        name="t", chain_id="test-net",
        endowed=(("alice", 1_000_000_000 * D), ("gw", 1_000_000 * D),
                 ("stash1", 10_000_000 * D), ("tee1", 1_000 * D),
                 ("m1", 10_000 * D), ("m2", 10_000 * D), ("m3", 10_000 * D),
                 ("m4", 10_000 * D)),
        validators=tuple(ValidatorGenesis(f"v{i}", 4_000_000 * D)
                         for i in range(n_validators)),
        era_blocks=40, epoch_blocks=10,
        audit_challenge_life=6, audit_verify_life=8, sudo="alice")
    nodes = [Node(spec, f"node{i}", {f"v{i}": spec.session_key(f"v{i}")})
             for i in range(n_validators)]
    return spec, nodes


def test_block_production_and_replica_determinism():
    spec, nodes = make_net()
    net = Network(nodes)
    nodes[0].submit_extrinsic("alice", "balances.transfer", "bob", 5 * D)
    net.run_slots(12)
    heads = [n.chain[-1] for n in nodes]
    assert all(h.hash() == heads[0].hash() for h in heads)
    assert all(n.runtime.state.state_root()
               == nodes[0].runtime.state.state_root() for n in nodes)
    assert nodes[1].runtime.balances.free("bob") == 5 * D
    assert nodes[0].finalized == heads[0].number
    authors = {h.author for n in nodes for h in n.chain[1:]}
    assert authors  # someone authored


def test_forged_origin_rejected():
    """VERDICT #1 done-criterion: a forged-origin transfer must be
    rejected — at pool admission AND at block execution."""
    import dataclasses

    from cess_tpu.chain.extrinsic import sign_extrinsic
    from cess_tpu.chain.state import DispatchError
    from cess_tpu.crypto import ed25519

    spec, nodes = make_net(2)
    net = Network(nodes)
    net.run_slots(2)
    node = nodes[0]
    g = node.runtime.genesis_hash()
    mallory = ed25519.SigningKey.generate(b"mallory-key")
    # sign "alice pays mallory" with a key that is NOT alice's
    forged = sign_extrinsic(mallory, g, "alice",
                            node.runtime.system.nonce("alice"),
                            "balances.transfer", ("mallory", 10 * D))
    with pytest.raises(DispatchError, match="AccountKeyMismatch"):
        node.submit_signed(forged)
    # a tampered-signature tx injected straight into the pool (bypassing
    # admission) is skipped deterministically at execution
    good = sign_extrinsic(spec.account_key("alice"), g, "alice",
                          node.runtime.system.nonce("alice"),
                          "balances.transfer", ("mallory", 10 * D))
    tampered = dataclasses.replace(good, args=("mallory", 1_000_000 * D))
    node.tx_pool.append(tampered)
    net.run_slots(2)
    assert node.runtime.balances.free("mallory") == 0
    failed = node.runtime.state.events_of("system", "ExtrinsicFailed")
    assert any(dict(e.data)["error"] == "system.BadSignature"
               for e in failed)
    # a forged AUDIT proposal (non-sudo signer, bad session sig) can't
    # install a challenge either
    evil_net, evil_miners = node.runtime.audit.generation_challenge()
    node.submit_extrinsic("v0", "audit.save_challenge_info", evil_net,
                          evil_miners, b"\x00" * 64)
    net.run_slots(2)
    assert node.runtime.audit.challenge() is None
    # replicas stayed in lockstep through all the rejections
    assert nodes[0].runtime.state.state_root() \
        == nodes[1].runtime.state.state_root()


def test_internal_pallet_methods_not_dispatchable():
    """Only #[pallet::call]-style extrinsics dispatch; internal pallet
    methods (mint, set_sudo, lock_space...) are unreachable from a tx."""
    spec, nodes = make_net(2)
    net = Network(nodes)
    node = nodes[0]
    for call, args in (("balances.mint", (10**30,)),
                       ("system.set_sudo", ()),
                       ("sminer.lock_space", ("m1", 1)),
                       ("balances.slash_reserved", ("m1", 1))):
        with pytest.raises(Exception, match="UnknownCall"):
            node.submit_extrinsic("m1", call, *args)
    # malformed field shapes are skipped deterministically, not crashes
    import dataclasses

    from cess_tpu.chain.extrinsic import sign_extrinsic

    g = node.runtime.genesis_hash()
    xt = sign_extrinsic(spec.account_key("alice"), g, "alice", 0,
                        "balances.transfer", ("bob", 1))
    node.tx_pool.append(dataclasses.replace(xt, args="notatuple"))
    net.run_slots(2)
    assert nodes[0].runtime.state.state_root() \
        == nodes[1].runtime.state.state_root()


def test_nonce_replay_rejected():
    spec, nodes = make_net(2)
    net = Network(nodes)
    node = nodes[0]
    from cess_tpu.chain.extrinsic import sign_extrinsic
    from cess_tpu.chain.state import DispatchError

    g = node.runtime.genesis_hash()
    xt = sign_extrinsic(spec.account_key("alice"), g, "alice", 0,
                        "balances.transfer", ("bob", 1 * D))
    node.submit_signed(xt)
    net.run_slots(2)
    assert node.runtime.balances.free("bob") == 1 * D
    with pytest.raises(DispatchError, match="BadNonce"):
        node.submit_signed(xt)       # replay: nonce already consumed
    node.tx_pool.append(xt)          # force it into a block anyway
    net.run_slots(2)
    assert node.runtime.balances.free("bob") == 1 * D  # not re-applied


def test_import_rejects_tampered_state_root():
    spec, nodes = make_net(2)
    net = Network(nodes)
    net.run_slots(2)
    blk = None
    slot = 100
    while blk is None:
        blk = nodes[0].try_author(slot)
        slot += 1
    nodes[0].commit_proposal()
    import dataclasses

    bad = dataclasses.replace(blk.header, state_root=b"\0" * 32)
    with pytest.raises(ValueError, match="state root|claim"):
        nodes[1].import_block(dataclasses.replace(blk, header=bad))


@pytest.fixture(scope="module")
def storage_net():
    """A full storage network: 3 validators, gateway, 4 miners, 1 TEE,
    with the TPU pipeline on tiny segments."""
    spec, nodes = make_net(3)
    net = Network(nodes)
    node = nodes[0]
    cfg = PipelineConfig(k=2, m=1, segment_size=64 * 1024)
    key = podr2.Podr2Key.generate(7)
    pipe = StoragePipeline(cfg, podr2_key=key)

    # genesis-ish setup extrinsics
    from cess_tpu.chain.attestation import issue_cert, issue_report
    from cess_tpu.crypto.rsa import generate_rsa_keypair

    kp = generate_rsa_keypair(1024, seed=5)
    signer_kp = generate_rsa_keypair(1024, seed=6)
    mr = b"\x02" * 32
    for n in nodes:
        n.runtime.apply_extrinsic("root", "tee_worker.update_whitelist", mr)
        n.runtime.apply_extrinsic("root", "tee_worker.pin_ias_signer", kp.public)
    cert = issue_cert(kp, "ias-signer", signer_kp.public)
    # the TEE registers a BLS master key, so every verify verdict in
    # this network is sealed + publicly re-verifiable (tests the full
    # sign -> gossip -> on-chain pairing check path under replay)
    from cess_tpu.crypto import bls12381
    tee_bls_sk, tee_bls_pk = bls12381.keygen(b"net-tee-master")
    report, rsig = issue_report(signer_kp, mr, b"tee-pk", "tee1",
                                bls_pk=tee_bls_pk)
    node.submit_extrinsic("tee1", "tee_worker.register", "stash1", b"tp",
                          b"tee-pk", report, rsig, (cert,), tee_bls_pk,
                          bls12381.prove_possession(tee_bls_sk, tee_bls_pk))
    for w in ("m1", "m2", "m3", "m4"):
        node.submit_extrinsic(w, "sminer.regnstk", w, b"p" + w.encode(),
                              2000 * D)
    net.run_slots(2)

    gw = OssGateway(node, "gw", pipe)
    miners = [MinerAgent(node, w, [gw], pipe)
              for w in ("m1", "m2", "m3", "m4")]
    tee = TeeAgent(node, "tee1", key, cfg.blocks_per_fragment,
                   bls_seed=b"net-tee-master")
    # TEE-certified fillers: 400 x 8 MiB protocol units = 12.5 GiB idle
    for m in miners:
        m.setup_fillers(tee, 400)
    net.run_slots(2)
    node.submit_extrinsic("alice", "storage_handler.buy_space", 10)
    node.submit_extrinsic("alice", "oss.authorize", "gw")
    net.run_slots(2)
    node.submit_extrinsic("gw", "file_bank.create_bucket", "alice", "photos")
    net.run_slots(2)
    # two validators' offchain workers: 2/3 matching proposals activate
    ocws = [ValidatorOcw("v0", spec.session_key("v0")),
            ValidatorOcw("v1", spec.session_key("v1"))]
    node.offchain_agents.extend([*miners, tee, *ocws])
    # fund the reward pool so audits pay out
    for n in nodes:
        n.runtime.fund("sminer_reward_pool", 10_000 * D)
    return spec, net, node, gw, miners, tee, cfg


def test_file_upload_through_network(storage_net):
    spec, net, node, gw, miners, tee, cfg = storage_net
    data = np.random.default_rng(0).integers(0, 256, 150_000,
                                             dtype=np.uint8).tobytes()
    fh = gw.upload("alice", "photos", "cat.jpg", data)
    net.run_slots(1)   # declaration lands; deal created
    assert node.runtime.file_bank.deal(fh) is not None
    net.run_slots(2)   # miners fetch + report
    f = node.runtime.file_bank.file(fh)
    assert f is not None and f.state == "calculate"
    # the scheduler would fire calculate_end after the 600-block tag
    # window; drive it now via a root extrinsic through a block
    node.submit_extrinsic("root", "file_bank.calculate_end", fh)
    net.run_slots(1)
    f = node.runtime.file_bank.file(fh)
    assert f.state == "active"
    # every assigned miner holds real bytes matching the on-chain hashes
    for seg in f.segments:
        for row, h in enumerate(seg.fragment_hashes):
            holder = next(m for m in miners if m.account == f.miners[row])
            assert fragment_hash(holder.store[h]) == h


def test_audit_round_over_network(storage_net):
    spec, net, node, gw, miners, tee, cfg = storage_net
    rt = node.runtime
    # run until a challenge starts, proofs submitted, verified, ended
    for _ in range(60):
        net.run_slots(1)
        if rt.state.events_of("audit", "VerifyResult"):
            break
    results = rt.state.events_of("audit", "VerifyResult")
    assert results, "audit round never produced verify results"
    assert all(dict(e.data)["idle"] and dict(e.data)["service"]
               for e in results), "honest miners must pass"
    assert rt.state.events_of("sminer", "RewardPaid")
    # every verdict was BLS-sealed on chain and re-verifies publicly
    # on a DIFFERENT replica from on-chain data alone
    from cess_tpu.chain.audit import reverify_verdict
    other = net.nodes[1].runtime
    recs = other.audit.verdicts()
    assert len(recs) >= len(results)
    bls_pk = other.tee_worker.worker("tee1").bls_pk
    assert reverify_verdict(recs[0], bls_pk)
    # replicas still in lockstep after the full audit machinery
    assert all(n.runtime.state.state_root()
               == net.nodes[0].runtime.state.state_root()
               for n in net.nodes)


def test_data_loss_detected_and_repaired(storage_net):
    spec, net, node, gw, miners, tee, cfg = storage_net
    rt = node.runtime
    # find an active file + a victim fragment
    fh, f = next(((k[0], v) for k, v in
                  rt.state.iter_prefix("file_bank", "file")
                  if v.state == "active"))
    victim_row = 0
    victim = next(m for m in miners if m.account == f.miners[victim_row])
    frag = f.segments[0].fragment_hashes[victim_row]
    del victim.store[frag]          # simulate disk loss
    del victim.tags[frag]
    # victim reports the break; a healthy peer repairs via RS decode
    node.submit_extrinsic(victim.account, "file_bank.generate_restoral_order",
                          fh, frag)
    net.run_slots(1)
    assert rt.file_bank.restoral_order(frag) is not None
    rescuer = next(m for m in miners if m.account not in f.miners)
    assert rescuer.try_repair(frag, miners, [gw])
    net.run_slots(1)
    assert rt.file_bank.restoral_order(frag) is None
    assert fragment_hash(rescuer.store[frag]) == frag
    ev = rt.state.events_of("file_bank", "RestoralComplete")
    assert ev and dict(ev[-1].data)["miner"] == rescuer.account
    # replicas agree after the whole repair market dance
    assert all(n.runtime.state.state_root()
               == net.nodes[0].runtime.state.state_root()
               for n in net.nodes)


def test_dropped_filler_fails_idle_audit_and_punishes(storage_net):
    """VERDICT #2 done-criterion: a miner that drops a filler fails
    the IDLE audit (service side still passes) and gets idle_punish
    after the fault tolerance is exceeded."""
    spec, net, node, gw, miners, tee, cfg = storage_net
    rt = node.runtime
    victim = miners[1]
    h = sorted(victim.filler_store)[0]
    del victim.filler_store[h]        # disk loss of one idle file
    del victim.filler_tags[h]
    collateral0 = rt.sminer.miner(victim.account).collateral
    idle_fails = 0
    for _ in range(200):
        net.run_slots(1)
        results = [dict(e.data) for e in
                   rt.state.events_of("audit", "VerifyResult")
                   if dict(e.data)["miner"] == victim.account
                   and not dict(e.data)["idle"]]
        idle_fails = len(results)
        if rt.sminer.miner(victim.account).collateral < collateral0:
            break
    assert idle_fails >= constants.AUDIT_FAULT_TOLERANCE
    assert rt.sminer.miner(victim.account).collateral < collateral0, \
        "idle punish must slash collateral"
    # the failures are idle-specific: service proofs kept passing
    last = [dict(e.data) for e in
            rt.state.events_of("audit", "VerifyResult")
            if dict(e.data)["miner"] == victim.account][-1]
    assert last["service"] is True and last["idle"] is False
    ev = rt.state.events_of("sminer", "Punished")
    assert any(dict(e.data).get("who") == victim.account for e in ev)
    # replicas in lockstep through the punish machinery
    assert all(n.runtime.state.state_root()
               == net.nodes[0].runtime.state.state_root()
               for n in net.nodes)


def test_pois_filler_setup_and_audit(storage_net):
    """PoIS-direction fillers (round-2 VERDICT #10): secret-seeded,
    sequentially-slow filler content behind the SAME cert flow —
    committed seed checked by the TEE, content not publicly derivable,
    and the registered fillers pass the idle audit."""
    from cess_tpu.chain.state import DispatchError
    from cess_tpu.node.offchain import (MinerAgent, filler_bytes,
                                        filler_seed_commitment,
                                        slow_filler_bytes)

    spec, net, node, gw, miners, tee, cfg = storage_net
    secret = b"m5-plot-secret"
    node.submit_extrinsic("alice", "balances.transfer", "m5", 10_000 * D)
    net.run_slots(1)
    node.submit_extrinsic("m5", "sminer.regnstk", "m5", b"pm5", 2000 * D)
    net.run_slots(1)
    m5 = MinerAgent(node, "m5", [gw], miners[0].pipeline)
    # TEE refuses before the commitment is on chain
    with pytest.raises(ValueError, match="commitment"):
        tee.certify_pois_fillers("m5", secret, [0], work=4)
    m5.commit_filler_seed(secret)
    net.run_slots(1)
    # TEE refuses a WRONG secret against the commitment
    with pytest.raises(ValueError, match="commitment"):
        tee.certify_pois_fillers("m5", b"not-the-secret", [0], work=4)
    idle0 = node.runtime.sminer.get_miner_idle_space("m5")
    m5.setup_fillers_pois(tee, 3, secret, work=4)
    net.run_slots(1)
    assert node.runtime.sminer.get_miner_idle_space("m5") \
        == idle0 + 3 * constants.FRAGMENT_SIZE
    # content is secret-dependent and NOT the public PRF stream
    size = cfg.fragment_size
    assert slow_filler_bytes(secret, 0, size, work=4) \
        != slow_filler_bytes(b"other", 0, size, work=4)
    assert slow_filler_bytes(secret, 0, size, work=4) \
        != filler_bytes("m5", 0, size)
    # the commitment is one-time
    with pytest.raises(DispatchError, match="SeedAlreadyCommitted"):
        node.runtime.apply_extrinsic(
            "m5", "sminer.commit_filler_seed",
            filler_seed_commitment(b"rotated"))
    # the registered pois fillers answer the next idle audit
    node.offchain_agents.append(m5)
    node.submit_extrinsic("root", "audit.set_keys", ("v0", "v1", "v2"))
    for v in ("v0", "v1", "v2"):
        node.submit_extrinsic(v, "system.set_session_key",
                              spec.session_key(v).public)
    net.run_slots(2)
    rt = node.runtime
    start = rt.state.block
    for _ in range(40):
        net.run_slots(1)
        ev = rt.state.events_of("audit", "VerifyResult")
        if any(dict(e.data)["miner"] == "m5" for e in ev):
            break
    results = [dict(e.data) for e in
               rt.state.events_of("audit", "VerifyResult")
               if dict(e.data)["miner"] == "m5"]
    assert results and results[-1]["idle"] is True, results


def test_ocw_mines_unsigned_election_solution():
    """VERDICT r4 Next #6, OCW side: during the unsigned window each
    validator's OCW mines a solution and submits it feeless; the era
    boundary adopts it (UnsignedElected) instead of the fallback —
    replicas stay in lockstep throughout."""
    spec, nodes = make_net()
    net = Network(nodes)
    for i, node in enumerate(nodes):
        node.offchain_agents.append(
            ValidatorOcw(f"v{i}", spec.session_key(f"v{i}")))
    # run through the first era boundary (era_blocks=40)
    net.run_slots(42)
    rt = nodes[0].runtime
    queued = rt.state.events_of("election", "UnsignedQueued")
    assert queued, "no OCW submitted during the unsigned window"
    elected = rt.state.events_of("election", "UnsignedElected")
    assert elected, "boundary did not adopt the OCW solution"
    assert rt.election.result()          # a non-empty authority set
    roots = {n.runtime.state.state_root() for n in nodes}
    assert len(roots) == 1


def _break_fragment(node, miners, row):
    """Delete one active file's row-``row`` fragment from whichever
    miner holds it and open its restoral order. Returns (frag, file)."""
    rt = node.runtime
    fh, f = next(((k[0], v) for k, v in
                  rt.state.iter_prefix("file_bank", "file")
                  if v.state == "active"))
    frag = f.segments[0].fragment_hashes[row]
    victim = next(m for m in miners if frag in m.store)
    del victim.store[frag]
    victim.tags.pop(frag, None)
    node.submit_extrinsic(victim.account, "file_bank.generate_restoral_order",
                          fh, frag)
    return frag, f


def test_repair_symbols_mode_cuts_ingress(storage_net):
    """Regenerating repair: the rebuilder ingresses ONE fragment-sized
    aggregate off the helper chain instead of k whole fragments, and
    the result still re-hashes to the on-chain identity."""
    spec, net, node, gw, miners, tee, cfg = storage_net
    rt = node.runtime
    frag, f = _break_fragment(node, miners, row=1)
    net.run_slots(1)
    rescuer = next(m for m in miners if frag not in m.store)
    rescuer.repair_mode = "symbols"
    ingress0 = rescuer.repair_ingress_bytes
    recovered0 = rescuer.repair_recovered_bytes
    try:
        assert rescuer.try_repair(frag, miners, [gw])
    finally:
        rescuer.repair_mode = "fragments"
    assert fragment_hash(rescuer.store[frag]) == frag
    # one aggregate in, k fragments' worth recovered-to-ingress ratio 1
    assert rescuer.repair_ingress_bytes - ingress0 == cfg.fragment_size
    assert rescuer.repair_recovered_bytes - recovered0 == cfg.fragment_size
    assert rescuer.repair_symbol_repairs >= 1
    assert rescuer.repair_fallbacks == 0
    net.run_slots(1)
    assert rt.file_bank.restoral_order(frag) is None
    ev = rt.state.events_of("file_bank", "RestoralComplete")
    assert dict(ev[-1].data)["miner"] == rescuer.account


def test_repair_symbol_corruption_falls_back_to_fragments(storage_net):
    """A corrupted symbol aggregate fails the rebuilder's hash check;
    the repair falls back to whole-fragment fetch, stores only
    verified bytes, and the fallback is counted + accounted."""
    from cess_tpu.resilience import faults
    from cess_tpu.resilience.faults import FaultPlan, FaultSpec

    spec, net, node, gw, miners, tee, cfg = storage_net
    rt = node.runtime
    frag, f = _break_fragment(node, miners, row=2)
    net.run_slots(1)
    rescuer = next(m for m in miners if frag not in m.store)
    rescuer.repair_mode = "symbols"
    ingress0 = rescuer.repair_ingress_bytes
    fallbacks0 = rescuer.repair_fallbacks
    whole0 = rescuer.repair_whole_repairs
    plan = FaultPlan({"offchain.symbol_bytes": {0: FaultSpec("corrupt",
                                                             xor=0x01)}})
    try:
        with faults.armed(plan):
            assert rescuer.try_repair(frag, miners, [gw])
    finally:
        rescuer.repair_mode = "fragments"
    assert fragment_hash(rescuer.store[frag]) == frag
    assert rescuer.repair_fallbacks - fallbacks0 == 1
    assert rescuer.repair_whole_repairs - whole0 == 1
    # the corrupt aggregate (n) still counts as ingress, then the
    # whole-fragment path pays k*n on top — honest accounting
    assert rescuer.repair_ingress_bytes - ingress0 \
        == (1 + cfg.k) * cfg.fragment_size
    net.run_slots(1)
    assert rt.file_bank.restoral_order(frag) is None


@pytest.mark.parametrize("mode,row", [("fragments", 0), ("symbols", 2)])
def test_try_repair_and_the_entry_point_are_one_path(storage_net, mode,
                                                     row):
    """``try_repair`` is ``restore_fragment`` after its chain lookups: a
    second miner handed what the chain holds (the segment's hashes, the
    lost row) stores the same bytes, counts the same and submits the
    same two extrinsics as the miner that went through the order."""
    spec, net, node, gw, miners, tee, cfg = storage_net
    rt = node.runtime
    frag, f = _break_fragment(node, miners, row=row)
    net.run_slots(1)
    first, second = [m for m in miners if frag not in m.store][:2]
    seg = next(s for s in f.segments if frag in s.fragment_hashes)

    class Recorder:
        def __init__(self, real=None):
            self.real, self.sent = real, []
            self.runtime = getattr(real, "runtime", None)

        def submit_extrinsic(self, who, call, *args):
            self.sent.append((who, call, args))
            if self.real is not None:
                self.real.submit_extrinsic(who, call, *args)

    handed = []
    entry = first.restore_fragment
    first.restore_fragment = lambda *a: handed.append(a) or entry(*a)
    nodes = first.node, second.node
    first.node, second.node = Recorder(node), Recorder()
    before = [m.counters() for m in (first, second)]
    try:
        for m in (first, second):
            m.repair_mode = mode
        assert first.try_repair(frag, miners, [gw])
        assert second.restore_fragment(seg.fragment_hashes, row, miners,
                                       [gw])
        sent = first.node.sent, second.node.sent
    finally:
        del first.restore_fragment
        (first.node, second.node) = nodes
        for m in (first, second):
            m.repair_mode = "fragments"
    assert handed == [(seg.fragment_hashes, row, miners, [gw])]
    assert first.store[frag] == second.store[frag]
    assert fragment_hash(first.store[frag]) == frag
    assert np.array_equal(first.tags[frag], second.tags[frag])
    calls = ["file_bank.claim_restoral_order",
             "file_bank.restoral_order_complete"]
    for m, got in zip((first, second), sent):
        assert got == [(m.account, call, (frag,)) for call in calls]
    deltas = []
    for m, was in zip((first, second), before):
        now = m.counters()
        deltas.append({k: now[k] - was[k] for k in was
                       if not k.startswith("stage_")})
        ran = {k: n - was["stage_count"].get(k, 0)
               for k, n in now["stage_count"].items()
               if k != "miner.symbol.hop"}
        assert {k: n for k, n in ran.items() if n} == {
            "miner.repair": 1, "miner.repair.holders": 1,
            "miner.repair.chain" if mode == "symbols"
            else "miner.repair.fragments": 1,
            "miner.repair.hash": 1, "miner.repair.store": 1,
            "miner.repair.report": 1}
    assert deltas[0] == deltas[1]
    n = cfg.fragment_size
    assert deltas[0] == {
        "repair_ingress_bytes": n if mode == "symbols" else cfg.k * n,
        "repair_recovered_bytes": n, "repair_fallbacks": 0, "repairs": 1,
        "repair_symbol_repairs": int(mode == "symbols"),
        "repair_whole_repairs": int(mode == "fragments")}
    # the order the first miner went through completes on the chain
    net.run_slots(1)
    assert rt.file_bank.restoral_order(frag) is None
    ev = rt.state.events_of("file_bank", "RestoralComplete")
    assert dict(ev[-1].data)["miner"] == first.account
    # the second copy is set aside: one custodian a fragment
    del second.store[frag]
    second.tags.pop(frag, None)


def test_repair_rejects_corrupt_reconstruction(storage_net):
    """Integrity regression: a decode fed bad survivor bytes must NOT
    be stored or claimed — the reconstructed fragment re-hashes
    against the on-chain identity first, on both dispatch modes."""
    spec, net, node, gw, miners, tee, cfg = storage_net
    rt = node.runtime
    frag, f = _break_fragment(node, miners, row=1)
    net.run_slots(1)
    assert rt.file_bank.restoral_order(frag) is not None
    rescuer = next(m for m in miners if frag not in m.store)
    # poison the first-scanned survivor row (same key, wrong bytes)
    other_row = next(j for j, h in enumerate(f.segments[0].fragment_hashes)
                     if j != 1)
    survivor_hash = f.segments[0].fragment_hashes[other_row]
    holder = next(m for m in miners if survivor_hash in m.store)
    good = holder.store[survivor_hash]
    holder.store[survivor_hash] = bytes(len(good))
    try:
        for mode in ("fragments", "symbols"):
            rescuer.repair_mode = mode
            assert not rescuer.try_repair(frag, miners, [gw])
            assert frag not in rescuer.store
    finally:
        rescuer.repair_mode = "fragments"
        holder.store[survivor_hash] = good
    # with honest survivors the same order then repairs cleanly
    assert rescuer.try_repair(frag, miners, [gw])
    assert fragment_hash(rescuer.store[frag]) == frag
    net.run_slots(1)
    assert rt.file_bank.restoral_order(frag) is None
