"""Traffic kind ``restoral``: a closed loop of one storage miner that
claimed restoral orders on an archival tier and rebuilds each lost
fragment through ``MinerAgent.restore_fragment``
(cess_tpu/node/offchain.py), the call ``MinerAgent.try_repair`` makes once
the chain has named the segment and the lost row.

Set-up: a pool of reference-encoded segments; one holder agent a row of
the stripe (k + m of them), each holding its row of every segment as
``bytes`` under its hash; one rebuilder; all on the recording node and on
ONE submission engine with a regenerating codec
(``make_engine(k, m, rs_backend="regen")``; in the deployment each agent
has its own: the configuration's ``reduced``). ``mode`` sets the
rebuilder's ``repair_mode``: ``symbols`` walks the chain of the k lowest
live holders, each folding its row into the aggregate it was handed
(``MinerAgent.repair_symbol``, a hop a request of the engine's repair
class); ``fragments`` pulls their whole rows and reconstructs in one call.

Per operation, from the seed: a segment of the pool and the lost row,
uniform of the k + m; the rebuilder's entry point with the k + m - 1 other
holders as peers; the clock stops when it returns (the hash check, the
store and both extrinsics are inside it). Then the repaired fragment is
taken out of the rebuilder's store again (it never becomes a holder, and
memory does not grow) and hashed by the driver against the original's
SHA-256. An operation is ``ok`` when the entry point said so, the driver's
hash agrees and the rebuilder did not fall back.

Warm-up: ``MinerAgent.warm_restoral`` (the repair shape and, on a
regenerating engine, the fold and every coefficient of the k + m
patterns), then two repairs of each pattern from a sub-seed the window
does not draw from.

Parameters: pool_segments, mode.
"""
from __future__ import annotations

import importlib.util
import os
import sys
import time

import numpy as np

import bench_lib
from reference import rs_ref, symbol_chain_ref

_HERE = os.path.dirname(os.path.abspath(__file__))
REBUILDER = "rebuilder"
CALLS = ("file_bank.claim_restoral_order",
         "file_bank.restoral_order_complete")
KEEP_LATER = 64      # the second kept operation's index is drawn below this


def _recording_node():
    spec = importlib.util.spec_from_file_location(
        "bench_recording_node", os.path.join(_HERE, "_recording_node.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.RecordingNode()


def setup(ctx) -> None:
    from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
    from cess_tpu.node.offchain import MinerAgent
    from cess_tpu.serve import make_engine

    if not hasattr(MinerAgent, "restore_fragment"):
        # before any of the set-up: the pool takes seconds to encode
        print("traffic/restoral.py: the program in this checkout has no "
              "MinerAgent.restore_fragment (PR 40). Nothing was run.",
              file=sys.stderr)
        raise SystemExit(1)
    c, t = ctx.config, ctx.traffic
    if t["mode"] not in ("symbols", "fragments"):
        raise ValueError(f"mode {t['mode']!r}: symbols or fragments")
    k, rows = c["k"], c["k"] + c["m"]
    n = c["segment_size"] // k
    ctx.k, ctx.rows, ctx.n, ctx.mode = k, rows, n, t["mode"]
    data = bench_lib.seeded_bytes(
        bench_lib.sub_seed(ctx.seed, 2),
        t["pool_segments"] * c["segment_size"]).reshape(
            t["pool_segments"], k, n)
    # the pool is made by the plain reference: the rows the holders keep
    ctx.ref = rs_ref.ReferenceCodec(k, c["m"])
    pool = ctx.ref.encode(data)
    ctx.hashes = [tuple(bench_lib.sha256(pool[s, j]) for j in range(rows))
                  for s in range(t["pool_segments"])]
    # an engine of its own making: bench_lib.make_engine fixes the plain
    # device codec, and the chain needs the regenerating one. Codec
    # classes only, resilience off
    ctx.engine = make_engine(k, c["m"], rs_backend="regen")
    ctx.node = _recording_node()
    pipeline = StoragePipeline(
        PipelineConfig(k=k, m=c["m"], segment_size=c["segment_size"]),
        engine=ctx.engine)

    def agent(account):
        return MinerAgent(ctx.node, account, [], pipeline,
                          engine=ctx.engine)
    ctx.holders = [agent(f"holder-{j}") for j in range(rows)]
    for j, holder in enumerate(ctx.holders):
        for s in range(t["pool_segments"]):
            holder.store[ctx.hashes[s][j]] = pool[s, j].tobytes()
    del pool, data             # the holders' bytes are the pool now
    ctx.rebuilder = agent(REBUILDER)
    ctx.rebuilder.set_repair_mode(ctx.mode)
    ctx.rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 3))
    # operations kept for the reference: the window's first, one drawn
    # from the seed, and its last. The first two are listened to (their
    # hops' aggregates are held as they pass); of the last only the
    # stored bytes are, which every operation hands over anyway: holding
    # every operation's ten aggregates would change what the allocator
    # does inside every clock. One more repair, listened to, is made
    # after the window (check)
    ctx.keep_at = {0, 1 + bench_lib.sub_seed(ctx.seed, 4) % KEEP_LATER}
    ctx.kept, ctx.last = [], None
    ctx.listen, ctx.hops = False, []
    if ctx.mode == "symbols":
        for holder in ctx.holders:
            holder.repair_symbol = _listening(ctx, holder.repair_symbol)
    ctx.n_ops = ctx.checked = ctx.mismatched = ctx.fell_back = 0
    ctx.unreported = 0


def _listening(ctx, hop):
    """A helper's ``repair_symbol`` with its answer noted as it passes
    while ``ctx.listen`` is set (a reference, no copy: the next hop only
    reads it); ``ctx.hops`` counts the hops of the running operation
    either way."""
    def repair_symbol(frag_hash, coeff, acc=None):
        out = hop(frag_hash, coeff, acc)
        ctx.hops.append(out if ctx.listen else None)
        return out
    return repair_symbol


def _restore(ctx, seg: int, row: int, listen: bool = False) -> dict:
    reb, hashes = ctx.rebuilder, ctx.hashes[seg]
    peers = [h for j, h in enumerate(ctx.holders) if j != row]
    ctx.listen, ctx.hops = listen, []
    fallbacks, sent = reb.repair_fallbacks, len(ctx.node.extrinsics)
    t0 = time.perf_counter()
    with ctx.spans.span("miner.restore_fragment"):
        done = reb.restore_fragment(hashes, row, peers)
    rec = bench_lib.op_record(t0, ok=False)
    with ctx.spans.span("restoral.set_aside_and_hash"):   # after the clock
        blob = reb.store.pop(hashes[row], None)
        reb.tags.pop(hashes[row], None)
        same = blob is not None and bench_lib.sha256(blob) == hashes[row]
        fell_back = reb.repair_fallbacks - fallbacks
        reported = ctx.node.extrinsics[sent:] == [
            (REBUILDER, call, (hashes[row],)) for call in CALLS]
    ctx.checked += 1
    ctx.mismatched += not same
    ctx.fell_back += fell_back
    ctx.unreported += not reported
    rec["ok"] = bool(done) and same and not fell_back and reported
    ctx.last = (seg, row, blob, ctx.hops)
    return rec


def warm(ctx) -> None:
    rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 5))
    with ctx.spans.span("warm"):
        ctx.rebuilder.warm_restoral()
        for row in range(ctx.rows):
            for _ in range(2):
                _restore(ctx, int(rng.integers(len(ctx.hashes))), row)
    ctx.checked = ctx.mismatched = ctx.fell_back = ctx.unreported = 0
    ctx.last = None
    ctx.node.extrinsics.clear()


def op(ctx):
    seg = int(ctx.rng.integers(len(ctx.hashes)))
    row = int(ctx.rng.integers(ctx.rows))
    rec = _restore(ctx, seg, row, listen=ctx.n_ops in ctx.keep_at)
    if ctx.n_ops in ctx.keep_at:
        ctx.kept.append(ctx.last)
    ctx.n_ops += 1
    return rec


def drain(ctx) -> list:
    return []


def counters(ctx) -> dict:
    return {"engine": bench_lib.engine_counters(ctx.engine),
            "miner": ctx.rebuilder.counters()}


def check(ctx, ops) -> list[dict]:
    """Every repaired fragment's SHA-256 against the original's, the
    fallbacks and the extrinsics (counted as the window went); the
    rebuilder's ingress against the repairs it made, exactly; the kept
    operations (the window's first, one drawn, its last, and one more
    made now through the same path) rebuilt by the plain reference from
    the same ten survivors, byte for byte, and in mode ``symbols`` every
    hop's aggregate of those that were listened to (all but the
    window's last) against the reference chain's."""
    if ctx.last is not None and all(ctx.last is not x for x in ctx.kept):
        ctx.kept.append(ctx.last)
    after = _restore(ctx, int(ctx.rng.integers(len(ctx.hashes))),
                     int(ctx.rng.integers(ctx.rows)), listen=True)
    ctx.kept.append(ctx.last)
    k, m = ctx.k, ctx.rows - ctx.k
    differ = hops_differ = hops_missing = hops_compared = 0
    for seg, row, blob, hops in ctx.kept:
        present = tuple(j for j in range(ctx.rows) if j != row)[:k]
        rows = [np.frombuffer(ctx.holders[j].store[ctx.hashes[seg][j]],
                              dtype=np.uint8) for j in present]
        want = ctx.ref.reconstruct(np.stack(rows), present, (row,))[0]
        differ += bench_lib.n_differ(
            np.frombuffer(blob or b"", dtype=np.uint8), want)
        if ctx.mode == "symbols":
            hops_missing += len(hops) != k
            if any(h is not None for h in hops):
                wanted = symbol_chain_ref.chain(k, m, present, row, rows)
                hops_differ += sum(
                    bench_lib.n_differ(
                        got if got is not None else np.zeros(0, np.uint8),
                        ref_acc) for got, ref_acc in zip(hops, wanted))
                hops_compared += len(hops)
    made = ctx.rebuilder.counters()
    per = ctx.n * (1 if ctx.mode == "symbols" else k)
    ctx.say(info="check", repairs_hashed=ctx.checked, mode=ctx.mode,
            kept=[[seg, row] for seg, row, _, _ in ctx.kept],
            hops_compared=hops_compared, after_the_window_ok=after["ok"],
            ingress_bytes_per_repair=per,
            extrinsics=len(ctx.node.extrinsics))
    out = [{"what": "repaired fragments whose SHA-256 differs from the "
                    "original's (or that were not stored)",
            "value": ctx.mismatched, "limit": 0},
           {"what": "repairs hashed (none: 1)",
            "value": 0 if ctx.checked else 1, "limit": 0},
           {"what": "repairs that fell back to whole fragments "
                    "(repair_fallbacks over the window)",
            "value": ctx.fell_back, "limit": 0},
           {"what": "bytes of the rebuilder's repair_ingress_bytes over "
                    "the run that are not repairs x the mode's bytes a "
                    "repair", "value": abs(
                        made["repair_ingress_bytes"]
                        - per * made["repairs"]), "limit": 0},
           {"what": "repairs whose two extrinsics were not read back as "
                    "submitted", "value": ctx.unreported, "limit": 0},
           {"what": "bytes of the kept repairs that differ from the plain "
                    "reference's reconstruction from the same survivors",
            "value": differ, "limit": 0},
           {"what": "kept repairs compared with the reference (none: 1)",
            "value": 0 if ctx.kept else 1, "limit": 0}]
    if ctx.mode == "symbols":
        out += [{"what": "bytes of the kept repairs' hop aggregates that "
                         "differ from the reference chain's",
                 "value": hops_differ, "limit": 0},
                {"what": "kept repairs whose chain was not k hops",
                 "value": hops_missing, "limit": 0},
                {"what": "hop aggregates compared with the reference "
                         "chain (none: 1)",
                 "value": 0 if hops_compared else 1, "limit": 0}]
    return out + bench_lib.engine_comparisons(ctx.engine)


def close(ctx) -> None:
    if getattr(ctx, "engine", None) is not None:
        ctx.engine.close()


# -- tests only ------------------------------------------------------------
def _flipped(arr):
    out = np.array(arr, dtype=np.uint8, copy=True)
    out[-1] ^= 0x40
    return out


def _flip_hop(ctx):
    """One helper's contribution arrives with a byte flipped: in mode
    ``symbols`` the fourth helper's outgoing aggregate, in mode
    ``fragments`` the whole row one holder hands over. The rebuilder's
    hash check must fail it; a fallback that then succeeds still counts
    the operation failed."""
    if ctx.mode == "fragments":
        holder = ctx.holders[2]
        for h, blob in list(holder.store.items()):
            holder.store[h] = _flipped(np.frombuffer(blob, np.uint8)
                                       ).tobytes()
        return
    for holder in ctx.holders:
        hop = holder.repair_symbol

        def repair_symbol(frag_hash, coeff, acc=None, hop=hop):
            out = hop(frag_hash, coeff, acc)
            return _flipped(out) if len(ctx.hops) == 4 else out
        holder.repair_symbol = repair_symbol


def _wrong_coeff(ctx):
    """One helper folds with another row's coefficient."""
    if ctx.mode != "symbols":
        raise ValueError("wrong_coeff is a control of mode symbols")
    for holder in ctx.holders:
        hop = holder.repair_symbol

        def repair_symbol(frag_hash, coeff, acc=None, hop=hop):
            if len(ctx.hops) == 2:
                coeff = coeff ^ 0x1D or 1
            return hop(frag_hash, coeff, acc)
        holder.repair_symbol = repair_symbol


def _skip_hash(ctx):
    """The degraded guarantee: the rebuilder stores without checking,
    over a flipped contribution. The driver's own hash must catch it."""
    from cess_tpu.node import offchain

    _flip_hop(ctx)
    restore = ctx.rebuilder.restore_fragment

    def restore_fragment(hashes, row, peers, gateways=None):
        # whatever the bytes, the id the rebuilder is looking for
        offchain.fragment_hash = lambda blob: hashes[row]
        return restore(hashes, row, peers, gateways)
    ctx.rebuilder.restore_fragment = restore_fragment


CONTROLS = {"flip_hop": _flip_hop, "wrong_coeff": _wrong_coeff,
            "skip_hash": _skip_hash}
