"""Traffic kind ``prove_round``: a closed loop of one storage miner that
answers audit rounds over everything it holds, through
``MinerAgent.prove_round`` (cess_tpu/node/offchain.py), the call
``MinerAgent._submit_proof`` makes for ``audit.submit_proof``.

Set-up fills the ``MinerAgent``'s own store: ``fragments`` fragments of
the configuration's size, seeded, made ``setup_batch`` at a time — each
batch hashed, tagged through ``engine.tag_fragments`` and copied into
the store as ``bytes`` under its hash before the next is made, so the
set is never held twice. Per operation a fresh round seed -> the service
proof's wire bytes over the whole owed set and the idle proof (the idle
set is empty: the zero proof), handed to the recording node as
``audit.submit_proof``. The TEE is stood in for after the window: every
round's wire bytes are read back and judged against the owed hashes
through ``TeeAgent.verify_round``, a round a call, and an operation is
``ok`` when both its proofs are accepted; its ``frags`` count then.

In set-up a round over a store with one flipped byte in a challenged
block, and a round with one owed fragment removed, must both be rejected.

Parameters: fragments, setup_batch, check_rounds, check_tags.
"""
from __future__ import annotations

import concurrent.futures
import importlib.util
import os
import resource
import sys
import time

import numpy as np

import bench_lib
from reference import podr2_ref, prove_round_ref

_HERE = os.path.dirname(os.path.abspath(__file__))
ACCOUNT = "miner-deal-cap"


def _host_memory() -> dict:
    """The process's resident set now and at its peak, bytes."""
    out = {"rss_bytes": None, "peak_rss_bytes": 1024 * resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss}
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    out["rss_bytes"] = 1024 * int(line.split()[1])
    except OSError:
        pass
    return out


def _recording_node():
    spec = importlib.util.spec_from_file_location(
        "bench_recording_node", os.path.join(_HERE, "_recording_node.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.RecordingNode()


def setup(ctx) -> None:
    from cess_tpu import codec
    from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
    from cess_tpu.node.offchain import MinerAgent, TeeAgent
    from cess_tpu.ops import podr2

    if not hasattr(MinerAgent, "prove_round"):
        # before any of the set-up: filling the store takes a minute
        print("traffic/prove_round.py: the program in this checkout has no "
              "MinerAgent.prove_round (PR 38). Nothing was run.",
              file=sys.stderr)
        raise SystemExit(3)
    c, t = ctx.config, ctx.traffic
    ctx.key_seed = bench_lib.key_seed(ctx)
    n = c["fragment_size"]
    ctx.blocks = n // c["podr2_block_bytes"]
    key = podr2.Podr2Key.generate(ctx.key_seed)
    ctx.engine = bench_lib.make_engine(ctx, key)
    cfg = PipelineConfig(k=c["k"], m=c["m"], segment_size=c["segment_size"])
    ctx.node = _recording_node()
    ctx.miner = MinerAgent(
        ctx.node, ACCOUNT, [],
        StoragePipeline(cfg, podr2_key=key, engine=ctx.engine),
        engine=ctx.engine)
    # the verifier that stands in for the TEE: the program's own entry
    # point (a partial agent: no chain under it), on the same engine
    tee = object.__new__(TeeAgent)
    tee.key, tee.blocks, tee.engine = key, ctx.blocks, ctx.engine
    tee.controller, tee.bls_sk, tee._submitted = "tee", None, set()
    ctx.tee = tee
    ctx.decode = codec.decode      # the check opens the proofs' framing

    ctx.owed = []
    with ctx.spans.span("fill_store"), \
            concurrent.futures.ThreadPoolExecutor(8) as pool:
        for lo in range(0, t["fragments"], t["setup_batch"]):
            count = min(t["setup_batch"], t["fragments"] - lo)
            rows = bench_lib.seeded_bytes(
                bench_lib.sub_seed(ctx.seed, 2, lo), count * n).reshape(
                    count, n)
            hashes = list(pool.map(bench_lib.sha256, rows))
            ids = np.stack([podr2_ref.fragment_id_from_hash(h)
                            for h in hashes])
            tags = np.ascontiguousarray(
                ctx.engine.tag_fragments(ids, rows), dtype=np.uint32)
            for h, row, tag in zip(hashes, rows, tags):
                ctx.miner.store[h] = row.tobytes()
                ctx.miner.tags[h] = tag
            ctx.owed.extend(hashes)
    ctx.owed = tuple(ctx.owed)
    ctx.say(info="store filled", held=len(ctx.miner.store),
            held_bytes=sum(map(len, ctx.miner.store.values())),
            **_host_memory())
    ctx.round = 0
    ctx.seeds = []             # the round seed of every operation
    ctx.fault = None
    ctx.tampers_accepted = {}


def _round(ctx) -> dict:
    seed = b"bench-round:%d:%d" % (ctx.seed, ctx.round)
    ctx.round += 1
    t0 = time.perf_counter()
    with ctx.spans.span("prove_round.round"):
        with ctx.spans.span("miner.prove_round"):
            service = ctx.miner.prove_round(seed, ctx.owed)
            idle = ctx.miner.prove_round(seed, (), idle=True)
        if ctx.fault is not None:
            service = ctx.fault(service)
        ctx.node.submit_extrinsic(ACCOUNT, "audit.submit_proof", idle,
                                  service)
    # judged after the window (check): ``ok`` and ``frags`` are set there
    rec = bench_lib.op_record(t0, ok=False, frags=0, index=len(ctx.seeds))
    ctx.seeds.append(seed)
    return rec


def _judge(ctx, index: int) -> bool:
    """One recorded round through the verifier: both proofs of its
    ``audit.submit_proof``, read back from the node."""
    account, call, (idle, service) = ctx.node.extrinsics[index]
    if (account, call) != (ACCOUNT, "audit.submit_proof"):
        return False
    return all(ctx.tee.verify_round([service, idle], [ctx.owed, ()],
                                    ctx.seeds[index]))


def warm(ctx) -> None:
    store = ctx.miner.store
    with ctx.spans.span("warm"):
        ctx.tee.warm_verify(1)
        for _ in range(2):
            _round(ctx)
        # one flipped byte in a challenged block: must be rejected
        seed = b"bench-round:%d:%d" % (ctx.seed, ctx.round)   # the next one
        with podr2_ref.on_cpu():
            idx = np.asarray(podr2_ref.gen_challenge(seed, ctx.blocks)[0])
        victim = ctx.owed[len(ctx.owed) // 2]
        held = store[victim]
        bad = bytearray(held)
        bad[int(idx[0]) * ctx.config["podr2_block_bytes"] + 3] ^= 0x40
        store[victim] = bytes(bad)
        del bad
        ctx.tampers_accepted["flipped byte"] = _judge(ctx, _round(ctx)["index"])
        # one owed fragment no longer held: must be rejected
        del store[victim]
        ctx.tampers_accepted["fragment removed"] = _judge(
            ctx, _round(ctx)["index"])
        store[victim] = held
        ctx.warm_ok = all(_judge(ctx, i) for i in range(2))
    ctx.seeds.clear()
    ctx.node.extrinsics.clear()
    ctx.say(info="warmed", **_host_memory())


def op(ctx):
    return _round(ctx)


def drain(ctx) -> list:
    return []


def counters(ctx) -> dict:
    return {"engine": bench_lib.engine_counters(ctx.engine)}


def check(ctx, ops) -> list[dict]:
    """Every round judged by the verifier (sets ``ok`` and ``frags``); a
    sample of rounds against the plain reference's proof over the same
    held bytes and tags, and the reference verifier's verdict on them; a
    sample of the store's tags against the reference's."""
    t, store, tags = ctx.traffic, ctx.miner.store, ctx.miner.tags
    ctx.say(info="window done", **_host_memory())
    rejected = 0
    for o in ops:
        o["ok"] = _judge(ctx, o["index"])
        o["frags"] = len(ctx.owed) if o["ok"] else 0
        rejected += not o["ok"]
    with podr2_ref.on_cpu():
        key = podr2_ref.generate_key(ctx.key_seed)
    held = [h for h in ctx.owed if h in store]
    sample = bench_lib.draw_sample(ctx.seed, len(ops), t["check_rounds"], 0)
    proof_diff = ref_rejected = 0
    for j in sample:
        proof = ctx.decode(ctx.node.extrinsics[j][2][1])
        want_mu, want_sigma = prove_round_ref.prove(
            ctx.seeds[j], held, [store[h] for h in held],
            [tags[h] for h in held], ctx.blocks)
        proof_diff += bench_lib.n_differ(proof.mu, want_mu) \
            + bench_lib.n_differ(proof.sigma, want_sigma)
        ref_rejected += not prove_round_ref.accepted(
            key, ctx.seeds[j], ctx.blocks, ctx.owed, proof.mu, proof.sigma)
    rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 4))
    picked = rng.choice(len(held), min(t["check_tags"], len(held)),
                        replace=False)
    tag_diff = 0
    for f in picked:
        h = held[f]
        with podr2_ref.on_cpu():
            want = podr2_ref.tag_fragment(
                key, podr2_ref.fragment_id_from_hash(h),
                np.frombuffer(store[h], dtype=np.uint8))
        tag_diff += bench_lib.n_differ(tags[h], want)
    ctx.say(info="check", rounds=len(ops), rounds_compared=sample,
            tags_compared=len(picked), held=len(store),
            held_bytes=sum(map(len, store.values())), **_host_memory())
    return [{"what": "a round over a store with a flipped byte in a "
                     "challenged block was accepted",
             "value": int(ctx.tampers_accepted.get("flipped byte", True)),
             "limit": 0},
            {"what": "a round with an owed fragment removed was accepted",
             "value": int(ctx.tampers_accepted.get("fragment removed",
                                                   True)), "limit": 0},
            {"what": "honest warm-up rounds rejected (any: 1)",
             "value": int(not ctx.warm_ok), "limit": 0},
            {"what": "rounds the verifier rejected", "value": rejected,
             "limit": 0},
            {"what": "rounds compared with the reference (none: 1)",
             "value": 0 if sample else 1, "limit": 0},
            {"what": "(mu, sigma) differ from the reference proof over the "
                     "same held bytes and tags (words)",
             "value": proof_diff, "limit": 0},
            {"what": "proofs the reference verifier rejects",
             "value": ref_rejected, "limit": 0},
            {"what": "tags differ from reference PoDR2 (words)",
             "value": tag_diff, "limit": 0},
            *bench_lib.engine_comparisons(ctx.engine)]


def close(ctx) -> None:
    if getattr(ctx, "engine", None) is not None:
        ctx.engine.close()


# -- tests only ------------------------------------------------------------
def _stale_proof(ctx):
    """The degraded guarantee: the miner answers every round with its
    first proof (a remembered answer)."""
    first = []

    def fault(service):
        first.append(service)
        return first[0]
    ctx.fault = fault


def _drop_fragment(ctx):
    """The degraded guarantee: one owed fragment is skipped in every
    round (the miner no longer holds it)."""
    del ctx.miner.store[ctx.owed[-1]]


CONTROLS = {"stale_proof": _stale_proof, "drop_fragment": _drop_fragment}
