"""Real process-level networking: ≥3 OS processes gossiping over TCP
sockets (round-2 VERDICT item #2 done-criteria): tx broadcast, block
propagation, catch-up sync, vote-based finality between processes —
plus a lossy-link run where one node drops every 3rd outbound message
and the network still converges via sync requests.
"""
import multiprocessing as mp
import socket
import time

from cess_tpu import constants

D = constants.DOLLARS
N = 3
SLOT = 0.25
MIN_FINALIZED = 2      # what _worker waits for and _assert_converged holds


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _worker(idx, ports, q, cap_s, drop_every, genesis_time, ready, stop):
    """Runs CONDITION-based, not duration-based (as _chain_worker and
    _code_worker do): the worker signals ``ready[idx]`` once it has
    finalized >= MIN_FINALIZED blocks AND executed the gossiped
    transfer, then keeps serving (so stragglers can still fetch from
    it) until the coordinator, which waits for ALL ready flags, sets
    ``stop``. On a loaded host everything simply takes longer;
    ``cap_s`` only bounds a genuine hang."""
    from cess_tpu.chain.extrinsic import sign_extrinsic
    from cess_tpu.node.chain_spec import ChainSpec, ValidatorGenesis
    from cess_tpu.node.net import FaultPolicy, NodeService
    from cess_tpu.node.network import Node

    spec = ChainSpec(
        name="t", chain_id="tcp-net",
        endowed=(("alice", 1_000_000_000 * D),),
        validators=tuple(ValidatorGenesis(f"v{i}", 4_000_000 * D)
                         for i in range(N)),
        era_blocks=1000, epoch_blocks=1000, sudo="alice")
    node = Node(spec, f"n{idx}", {f"v{idx}": spec.session_key(f"v{idx}")})
    faults = FaultPolicy(drop_every=drop_every) if idx == 0 and drop_every \
        else None
    svc = NodeService(node, ports[idx],
                      [p for j, p in enumerate(ports) if j != idx],
                      slot_time=SLOT, genesis_time=genesis_time,
                      faults=faults)
    svc.start()
    deadline = time.time() + cap_s
    if idx == 0:
        time.sleep(4 * SLOT)   # let the mesh form
        xt = sign_extrinsic(
            spec.account_key("alice"), node.runtime.genesis_hash(),
            "alice", 0, "balances.transfer", ("bob", 7 * D), ())
        svc.submit(xt)
    while time.time() < deadline and not stop.is_set():
        if not ready[idx].is_set():
            with svc.lock:
                if node.finalized >= MIN_FINALIZED \
                        and node.runtime.balances.free("bob") == 7 * D:
                    ready[idx].set()
        time.sleep(SLOT / 2)
    svc.stop()
    with svc.lock:
        q.put((idx,
               node.finalized,
               [h.hash().hex() for h in node.chain],
               node.runtime.balances.free("bob"),
               node.runtime.state.state_root().hex()
               if node.finalized == node.head().number else None))


def _run_cluster(cap_s=90.0, drop_every=0):
    ctx = mp.get_context("spawn")
    ports = _free_ports(N)
    q = ctx.Queue()
    ready = [ctx.Event() for _ in range(N)]
    stop = ctx.Event()
    genesis_time = time.time()
    procs = [ctx.Process(target=_worker,
                         args=(i, ports, q, cap_s, drop_every,
                               genesis_time, ready, stop))
             for i in range(N)]
    for p in procs:
        p.start()
    try:
        for i, ev in enumerate(ready):
            assert ev.wait(timeout=cap_s), \
                f"node {i} never converged (finality or the tx stalled)"
    finally:
        stop.set()
    results = [q.get(timeout=cap_s + 60) for _ in range(N)]
    for p in procs:
        p.join(timeout=30)
        assert p.exitcode == 0
    return sorted(results)


def _assert_converged(results):
    fins = [r[1] for r in results]
    assert min(fins) >= MIN_FINALIZED, f"finality stalled: {fins}"
    # all replicas agree on the finalized prefix
    upto = min(fins)
    prefixes = {tuple(r[2][:upto + 1]) for r in results}
    assert len(prefixes) == 1, "finalized prefixes diverged"
    # the gossiped tx executed everywhere
    assert all(r[3] == 7 * D for r in results), [r[3] for r in results]


def test_three_process_gossip_converges():
    # an idle box converges in ~3 s at SLOT=0.25, a loaded one whenever
    # it does: _run_cluster's cap only bounds a hang
    _assert_converged(_run_cluster())


def test_lossy_link_still_converges():
    """Node 0 drops every 3rd outbound message (blocks, votes, status
    alike); redundancy + sync requests must still converge the
    cluster."""
    _assert_converged(_run_cluster(drop_every=3))


def _chain_worker(idx, ports, q, deadline_s, genesis_time, ready, stop):
    """Like _worker but each node initially knows ONLY its predecessor
    (a chain topology): full connectivity must come from the peer
    exchange (net.py's schedulable discovery loop).

    Runs CONDITION-based, not duration-based: the worker signals
    ``ready[idx]`` once it has finalized >= 3 blocks AND learned the
    full peer set, then keeps serving until the coordinator (which
    waits for ALL ready flags) sets ``stop``. There is no fixed sleep
    to race against — on a loaded host everything simply takes longer;
    ``deadline_s`` only bounds a genuine hang."""
    from cess_tpu.node.chain_spec import ChainSpec, ValidatorGenesis
    from cess_tpu.node.net import NodeService
    from cess_tpu.node.network import Node

    spec = ChainSpec(
        name="t", chain_id="tcp-disc",
        endowed=(("alice", 1_000_000_000 * D),),
        validators=tuple(ValidatorGenesis(f"v{i}", 4_000_000 * D)
                         for i in range(N)),
        era_blocks=1000, epoch_blocks=1000, sudo="alice")
    node = Node(spec, f"n{idx}", {f"v{idx}": spec.session_key(f"v{idx}")})
    peers = [ports[idx - 1]] if idx > 0 else []
    svc = NodeService(node, ports[idx], peers, slot_time=SLOT,
                      genesis_time=genesis_time)
    svc.start()
    deadline = time.time() + deadline_s
    while time.time() < deadline and not stop.is_set():
        with svc.lock:
            fin = node.finalized
            known = len(svc._known_peers)
        if not ready[idx].is_set() and fin >= 3 and known >= 2:
            ready[idx].set()
        time.sleep(SLOT / 2)
    svc.stop()
    with svc.lock:
        q.put((idx, node.finalized,
               [h.hash().hex() for h in node.chain],
               len(svc._known_peers)))


def test_peer_discovery_chain_topology():
    """Node i only knows node i-1 at startup; the peer exchange must
    build enough connectivity for votes from ALL authorities to reach
    everyone (finality needs 2/3 of 3 = full vote flow).

    Previously a fixed-duration run and the suite's one known flake:
    under load, votes gossiped into the partially-formed mesh were
    lost forever (no re-request path) and the one-phase gadget could
    assemble CONFLICTING quorums — a permanent 2-way finalized-prefix
    split at the assert below. Fixed by the resilience round: vote
    re-gossip healing + pending-justification re-apply + the own-vote
    lock (finality.py), plus the schedulable discovery loop; the test
    itself now runs to a convergence CONDITION instead of a timer."""
    ctx = mp.get_context("spawn")
    ports = _free_ports(N)
    q = ctx.Queue()
    ready = [ctx.Event() for _ in range(N)]
    stop = ctx.Event()
    genesis_time = time.time()
    procs = [ctx.Process(target=_chain_worker,
                         args=(i, ports, q, 90.0, genesis_time, ready,
                               stop))
             for i in range(N)]
    for p in procs:
        p.start()
    try:
        for i, ev in enumerate(ready):
            assert ev.wait(timeout=90), \
                f"node {i} never converged (finality or discovery stalled)"
    finally:
        stop.set()
    results = sorted(q.get(timeout=90) for _ in range(N))
    for p in procs:
        p.join(timeout=30)
        assert p.exitcode == 0
    fins = [r[1] for r in results]
    assert min(fins) >= 3, f"finality stalled: {fins}"
    upto = min(fins)
    assert len({tuple(r[2][:upto + 1]) for r in results}) == 1, \
        "finalized prefixes diverged"
    # everyone learned the full peer set (2 others)
    assert all(r[3] >= 2 for r in results), [r[3] for r in results]


def _degree_worker(idx, ports, q, duration, genesis_time, n, degree,
                   n_validators):
    """Every node knows the full port list but the ring-successor rule
    must keep its actual connection degree bounded. Only the first
    ``n_validators`` processes author/vote (pure-python ed25519 costs
    ~6 ms/verify — 10 authorities x 10 replicas of vote verification
    would exceed the 1-core CI slot budget); the other processes are
    full nodes, so finality data still has to cross the ring
    multi-hop to reach them."""
    from cess_tpu.node.chain_spec import ChainSpec, ValidatorGenesis
    from cess_tpu.node.net import NodeService
    from cess_tpu.node.network import Node

    spec = ChainSpec(
        name="t", chain_id="tcp-degree",
        endowed=(("alice", 1_000_000_000 * D),),
        validators=tuple(ValidatorGenesis(f"v{i}", 4_000_000 * D)
                         for i in range(n_validators)),
        era_blocks=1000, epoch_blocks=1000, sudo="alice")
    keys = {f"v{idx}": spec.session_key(f"v{idx}")} \
        if idx < n_validators else {}
    node = Node(spec, f"n{idx}", keys)
    svc = NodeService(node, ports[idx],
                      [p for j, p in enumerate(ports) if j != idx],
                      slot_time=0.75, genesis_time=genesis_time,
                      degree=degree)
    svc.start()
    deadline = time.time() + duration
    peak_alive = 0
    while time.time() < deadline:
        peak_alive = max(peak_alive,
                         len([c for c in svc.conns if c.alive]))
        time.sleep(0.25)
    svc.stop()
    with svc.lock:
        q.put((idx, node.finalized,
               [h.hash().hex() for h in node.chain],
               peak_alive, svc.msgs_sent))


def test_ten_process_bounded_degree_converges():
    """10 processes, degree cap 4 (2 ring dials out + <=2 in under the
    same rule): the cluster must still finalize a common prefix, every
    node's connection count stays <= the cap, and the transport's
    total message count is sub-quadratic — bounded-degree flooding
    costs O(n*degree) sends per gossip item vs O(n^2) for the old
    full mesh (the libp2p-role scaling fix, VERDICT r3 #6)."""
    n, degree, n_validators = 10, 4, 4
    ctx = mp.get_context("spawn")
    ports = _free_ports(n)
    q = ctx.Queue()
    genesis_time = time.time() + 3.0   # cover slow 10-proc spawn
    procs = [ctx.Process(target=_degree_worker,
                         args=(i, ports, q, 20.0, genesis_time, n, degree,
                               n_validators))
             for i in range(n)]
    for p in procs:
        p.start()
    results = sorted(q.get(timeout=120) for _ in range(n))
    for p in procs:
        p.join(timeout=30)
        assert p.exitcode == 0
    fins = [r[1] for r in results]
    assert min(fins) >= 1, f"finality stalled: {fins}"
    upto = min(fins)
    assert len({tuple(r[2][:upto + 1]) for r in results}) == 1
    degrees = [r[3] for r in results]
    # the accept loop allows ONE slack slot above `degree` (late-joiner
    # admission, net.py accept cap) — the bound is degree + 1
    assert max(degrees) <= degree + 1, f"degree cap violated: {degrees}"
    # sub-quadratic gossip: total live links is at most n*(degree+1) —
    # strictly below the full mesh's n*(n-1) links; message volume
    # scales with links, so bounded degree => sub-quadratic traffic
    assert sum(degrees) <= n * (degree + 1) < n * (n - 1)


def _warp_worker(idx, ports, q, genesis_time):
    """Two validators build a finalized chain; a third FRESH full node
    (no keys) joins late and must checkpoint-sync over the wire."""
    from cess_tpu.node.chain_spec import ChainSpec, ValidatorGenesis
    from cess_tpu.node import net as _net
    from cess_tpu.node.net import NodeService
    from cess_tpu.node.network import Node

    spec = ChainSpec(
        name="t", chain_id="tcp-warp",
        endowed=(("alice", 1_000_000_000 * D),),
        validators=tuple(ValidatorGenesis(f"v{i}", 4_000_000 * D)
                         for i in range(2)),
        era_blocks=10000, epoch_blocks=10000, sudo="alice")
    keys = {f"v{idx}": spec.session_key(f"v{idx}")} if idx < 2 else {}
    node = Node(spec, f"n{idx}", keys)
    peers = [p for j, p in enumerate(ports) if j != idx] if idx < 2 else \
        [ports[0]]
    svc = NodeService(node, ports[idx], peers, slot_time=0.15,
                      genesis_time=genesis_time)
    if idx == 2:
        _net.WARP_THRESHOLD = 5   # warp sooner in the test
        time.sleep(7.0)           # join late, well past the threshold
        # (generous margins: the 1-vCPU CI box runs 3 interpreters)
    svc.start()
    deadline = time.time() + (14.0 if idx < 2 else 7.0)
    while time.time() < deadline:
        time.sleep(0.2)
    svc.stop()
    with svc.lock:
        q.put((idx, node.finalized, node.head().number,
               min(node.block_bodies, default=-1)))


def test_warp_sync_over_tcp():
    ctx = mp.get_context("spawn")
    ports = _free_ports(3)
    q = ctx.Queue()
    genesis_time = time.time()
    procs = [ctx.Process(target=_warp_worker,
                         args=(i, ports, q, genesis_time))
             for i in range(3)]
    for p in procs:
        p.start()
    results = sorted(q.get(timeout=90) for _ in range(3))
    for p in procs:
        p.join(timeout=30)
        assert p.exitcode == 0
    late = results[2]
    assert late[0] == 2
    # the late full node reached a finalized height far beyond zero
    # without any authority keys — it warped + tail-synced
    assert late[1] >= 5, f"late node finality stalled: {results}"
    # and it genuinely WARPED: historical bodies were never replayed
    # (a full replay would have body #1; warp + tail sync starts from
    # the checkpoint head)
    assert late[3] > 1, f"late node replayed instead of warping: {results}"


def test_stale_dials_are_pruned_down_to_the_out_degree():
    """What stalled the chain-bootstrapped DHT test, held without
    sockets or clocks: a node whose ring moved keeps at most ``degree//2``
    outbound links, dropping only links that are no longer ring
    targets — so its accept cap keeps the slack slot a late joiner
    needs — and a node at the bound drops nothing."""
    from cess_tpu.node.chain_spec import dev_spec
    from cess_tpu.node.net import NodeService
    from cess_tpu.node.network import Node

    class Link:
        inbound = False

        def __init__(self, dial_port):
            self.dial_port, self.alive = dial_port, True

        def close(self):
            self.alive = False

    svc = NodeService(Node(dev_spec(), "n0", {}), 30000,
                      [30001, 30002, 30003, 30004], degree=4)
    assert svc._dial_targets() == [30001, 30002]
    # dialed 30003 and 30004 when they were all it knew, then the ring
    # moved to 30001 and 30002: four outbound links, two of them stale
    links = {p: Link(p) for p in (30003, 30004, 30001, 30002)}
    inbound = Link(None)
    inbound.inbound = True
    svc.conns = [*links.values(), inbound]
    svc._prune_stale_dials()
    assert {p for p, c in links.items() if c.alive} == {30001, 30002}
    assert inbound.alive
    # at the bound nothing goes, stale or not: the ring slid past a
    # cooling 30001 to 30003, and 30001's return must not cut the
    # substitute link before 30001 itself is connected
    links = {p: Link(p) for p in (30003, 30002)}
    svc.conns = list(links.values())
    svc._prune_stale_dials()
    assert all(c.alive for c in links.values())


def _dht_worker(idx, ports, q, cap_s, genesis_time, n, done):
    """Chain bootstrap (node i initially knows only node i-1): node 0's
    authority record must reach the FAR end of the chain through
    structured DHT lookups, not via a direct connection.

    Runs CONDITION-based (as _chain_worker does): the tail keeps
    looking v0 up until it holds the record and a routing table that
    grew past its bootstrap neighbour, then sets ``done``; every other
    node serves until then. ``cap_s`` only bounds a genuine hang —
    which this topology has shown: stale outbound links once filled a
    middle node's connection cap and locked the chain's last two nodes
    out for good (net.py ``_prune_stale_dials``; held above, without
    sockets, by test_stale_dials_are_pruned_down_to_the_out_degree)."""
    from cess_tpu.node.chain_spec import ChainSpec, ValidatorGenesis
    from cess_tpu.node.net import NodeService
    from cess_tpu.node.network import Node

    n_validators = 3
    spec = ChainSpec(
        name="t", chain_id="tcp-dht",
        endowed=(("alice", 1_000_000_000 * D),),
        validators=tuple(ValidatorGenesis(f"v{i}", 4_000_000 * D)
                         for i in range(n_validators)),
        era_blocks=1000, epoch_blocks=1000, sudo="alice")
    keys = {f"v{idx}": spec.session_key(f"v{idx}")} \
        if idx < n_validators else {}
    node = Node(spec, f"n{idx}", keys)
    peers = [ports[idx - 1]] if idx > 0 else []
    svc = NodeService(node, ports[idx], peers, slot_time=0.75,
                      genesis_time=genesis_time, degree=4)
    svc.start()
    deadline = time.time() + cap_s
    rec = None
    while time.time() < deadline and not done.is_set():
        if idx == n - 1:
            if rec is None:
                rec = svc.discover_authority("v0")
            if rec is not None and len(svc.kad.contacts()) >= 2:
                done.set()
        time.sleep(0.5)
    svc.stop()
    q.put((idx, None if rec is None else (rec.authority, rec.port),
           len(svc.kad.contacts())))


def test_dht_authority_discovery_across_chain():
    """6 processes bootstrapped as a chain: the tail node resolves the
    head node's validator address via signed DHT records (the
    authority-discovery role, service.rs:508-537). The record must
    name v0's actual gossip port — proof it came from v0's signed
    publication, not from local guessing."""
    n = 6
    cap_s = 120.0
    ctx = mp.get_context("spawn")
    ports = _free_ports(n)
    q = ctx.Queue()
    done = ctx.Event()
    genesis_time = time.time() + 2.0
    procs = [ctx.Process(target=_dht_worker,
                         args=(i, ports, q, cap_s, genesis_time, n,
                               done))
             for i in range(n)]
    for p in procs:
        p.start()
    try:
        assert done.wait(timeout=cap_s), \
            "tail node never resolved v0 through the DHT"
    finally:
        done.set()
    results = sorted(q.get(timeout=cap_s + 60) for _ in range(n))
    for p in procs:
        p.join(timeout=30)
        assert p.exitcode == 0
    tail = results[n - 1]
    assert tail[1] == ("v0", ports[0]), \
        f"tail node failed to discover v0: {results}"
    # routing tables grew past the bootstrap neighbor via lookups
    assert tail[2] >= 2


def _code_worker(idx, ports, q, duration, genesis_time):
    """VERDICT r4 Next #9 done-criteria: canonical contract bytecode +
    deploy-by-hash round-trips over the real TCP transport — upload
    once, instantiate by 32-byte hash, call; every replica must hold
    identical deduped code and contract state."""
    import hashlib

    from cess_tpu.chain.contracts import code_hash
    from cess_tpu.chain.extrinsic import sign_extrinsic
    from cess_tpu.node.chain_spec import ChainSpec, ValidatorGenesis
    from cess_tpu.node.net import NodeService
    from cess_tpu.node.network import Node

    counter = (
        ("input",), ("push", 0), ("index",),           # 0-2: method
        ("dup", 0), ("push", "init"), ("eq",), ("jumpi", 13),
        ("dup", 0), ("push", "inc"), ("eq",), ("jumpi", 18),
        ("push", 0), ("return",),                      # 11-12: unknown
        ("push", "count"), ("push", 0), ("sput",),     # 13-15: init
        ("push", 0), ("return",),                      # 16-17
        ("push", "count"), ("sget",),                  # 18-: inc
        ("input",), ("push", 1), ("index",), ("add",),
        ("push", "count"), ("dup", 1), ("sput",),
        ("return",),
    )
    spec = ChainSpec(
        name="t", chain_id="tcp-code",
        endowed=(("alice", 1_000_000_000 * D),),
        validators=tuple(ValidatorGenesis(f"v{i}", 4_000_000 * D)
                         for i in range(N)),
        era_blocks=1000, epoch_blocks=1000, sudo="alice")
    node = Node(spec, f"n{idx}", {f"v{idx}": spec.session_key(f"v{idx}")})
    svc = NodeService(node, ports[idx],
                      [p for j, p in enumerate(ports) if j != idx],
                      slot_time=SLOT, genesis_time=genesis_time)
    svc.start()
    h = code_hash(counter)
    # the instantiate address is predictable client-side: alice's
    # first contracts nonce
    addr = hashlib.sha256(b"cvm-create:" + b"alice"
                          + (0).to_bytes(8, "little")).digest()[:20]
    if idx == 0:
        time.sleep(4 * SLOT)   # let the mesh form
        key = spec.account_key("alice")
        g = node.runtime.genesis_hash()
        for nonce, (call, args) in enumerate((
                ("contracts.upload_code", (counter,)),
                ("contracts.instantiate", (h,)),
                ("contracts.call", (addr, "init")),
                ("contracts.call", (addr, "inc", (5,))))):
            svc.submit(sign_extrinsic(key, g, "alice", nonce, call,
                                      args, ()))
    # condition-based, not a fixed wall-clock budget (the PR-4
    # discovery-test lesson): run until THIS replica has synced the
    # full deploy->init->inc state, then keep serving a grace period
    # so stragglers can still fetch those blocks from us. `duration`
    # is the floor; the hard cap only bounds a genuinely broken run —
    # on a loaded single-cpu box the spawned processes lose seconds
    # to imports and the fixed 9 s cut the last extrinsic off ~50%.
    deadline = time.time() + duration
    hard_deadline = time.time() + max(duration, 45.0)
    converged_at = None
    while time.time() < hard_deadline:
        time.sleep(SLOT)
        if converged_at is None:
            with svc.lock:
                rt = node.runtime
                if rt.contracts.code_at(addr) == counter \
                        and _counter_state(rt, addr) == 5:
                    converged_at = time.time()
        elif time.time() >= max(deadline, converged_at + 4 * SLOT):
            break
    svc.stop()
    with svc.lock:
        rt = node.runtime
        stored = rt.state.get("contracts", "code_store", h)
        q.put((idx, node.finalized,
               stored == counter,
               rt.contracts.code_at(addr) == counter,
               _counter_state(rt, addr)
               if rt.contracts.code_at(addr) else None))


def _counter_state(rt, addr):
    """The counter contract's current count via a non-committing
    query, or None while unreadable — between instantiate and the
    init call the storage is unset and `inc` TRAPS (add on None), so
    a bare query would kill the probing worker process."""
    try:
        return rt.contracts.query(addr, "inc", (0,))
    except Exception:
        return None


def test_deploy_by_hash_over_tcp():
    ctx = mp.get_context("spawn")
    ports = _free_ports(N)
    q = ctx.Queue()
    genesis_time = time.time()
    procs = [ctx.Process(target=_code_worker,
                         args=(i, ports, q, 9.0, genesis_time))
             for i in range(N)]
    for p in procs:
        p.start()
    # the collection window must comfortably cover spawn/import
    # overhead (tens of seconds on the loaded single-cpu box) PLUS
    # the worker's 45 s non-convergence hard cap
    results = [q.get(timeout=150) for _ in range(N)]
    for p in procs:
        p.join(timeout=30)
        assert p.exitcode == 0
    for idx, finalized, stored_ok, code_ok, count in sorted(results):
        assert stored_ok, f"node {idx}: code_store missing/diverged"
        assert code_ok, f"node {idx}: instantiate-by-hash failed"
        assert count == 5, f"node {idx}: contract state {count}"
