"""Traffic kind ``repair_helpers``: a closed loop of one miner on an
archival tier that rebuilds a stripe from whichever helpers answer. Per
operation, all drawn from the seed: a segment of a pool of
reference-encoded segments; how many rows are lost (the configuration's
``loss_mix``); which rows (uniform of the k+m); and the ``helpers`` rows
that answer, ascending: ``answering`` "uniform" draws them uniformly of
the survivors — the worst case for anything kept per pattern, and no
source's: 4,004 single-loss patterns at RS(10,4) — and "lowest" takes the
k lowest survivors, the order MinerAgent.try_repair asks its peers in: 14.
The helpers are stacked
on the host, handed to ``engine.reconstruct(stack, helpers, lost)`` and
every lost row is fetched as host bytes — MinerAgent._repair_via_fragments
(node/offchain.py) with a helper set that is not the k lowest survivors.
After its clock stops, each repaired row is hashed and compared with the
original's SHA-256.

Warm-up: ``engine.warm_repair`` for the SHAPES of one, two and three lost
rows at bucket 1, and two repairs of each, with patterns from a sub-seed
the window does not draw from: the window's patterns are new to the
program.

Parameters: pool_segments, helpers (= the configuration's k), answering.
"""
from __future__ import annotations

import time

import numpy as np

import bench_lib
from reference import rs_ref

KEEP_LATER = 64      # the second kept operation's index is drawn below this


def setup(ctx) -> None:
    c, t = ctx.config, ctx.traffic
    k, rows = c["k"], c["k"] + c["m"]
    if t["helpers"] != k:
        raise ValueError(f"{t['helpers']} helpers cannot rebuild RS({k},"
                         f"{c['m']}): exactly k answer")
    if t["answering"] not in ("uniform", "lowest"):
        raise ValueError(f"answering {t['answering']!r}: uniform or lowest")
    n = c["segment_size"] // k
    data = bench_lib.seeded_bytes(
        bench_lib.sub_seed(ctx.seed, 2),
        t["pool_segments"] * c["segment_size"]).reshape(
            t["pool_segments"], k, n)
    # the pool is made by the plain reference: the rows the holders keep
    ctx.ref = rs_ref.ReferenceCodec(k, c["m"])
    ctx.pool = ctx.ref.encode(data)
    ctx.hashes = [[bench_lib.sha256(ctx.pool[s, j]) for j in range(rows)]
                  for s in range(t["pool_segments"])]
    ctx.losses = sorted(int(e) for e in c["loss_mix"])
    p = np.array([c["loss_mix"][str(e)] for e in ctx.losses], dtype=float)
    ctx.loss_p = p / p.sum()
    ctx.n, ctx.rows = n, rows
    ctx.engine = bench_lib.make_engine(ctx)
    ctx.rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 3))
    ctx.fault = None
    ctx.mismatched = ctx.checked = ctx.n_ops = 0
    # operations whose repaired bytes are kept for the reference: the
    # window's first, a later one drawn from the seed, and the first with
    # two or more rows lost
    ctx.keep_at = {0, 1 + bench_lib.sub_seed(ctx.seed, 4) % KEEP_LATER}
    ctx.kept = []


def draw(ctx, rng, e=None):
    """(segment, helpers, lost): ``e`` lost rows uniform of the stripe's,
    the helpers uniform of its survivors or the k lowest of them
    (``answering``), both ascending."""
    k = ctx.config["k"]
    seg = int(rng.integers(ctx.pool.shape[0]))
    if e is None:
        e = int(rng.choice(ctx.losses, p=ctx.loss_p))
    lost = tuple(sorted(rng.choice(ctx.rows, e, replace=False).tolist()))
    survivors = [j for j in range(ctx.rows) if j not in lost]
    if ctx.traffic["answering"] == "lowest":
        return seg, tuple(survivors[:k]), lost
    helpers = tuple(sorted(rng.choice(survivors, k, replace=False).tolist()))
    return seg, helpers, lost


def _repair(ctx, seg: int, helpers: tuple, lost: tuple, keep=False) -> dict:
    t0 = time.perf_counter()
    with ctx.spans.span("repair.stack_survivors"):
        stack = np.stack([ctx.pool[seg, j] for j in helpers])
    with ctx.spans.span("engine.reconstruct"):
        rec = ctx.engine.reconstruct(stack, helpers, lost)
    with ctx.spans.span("repair.fetch_bytes"):
        rec = np.asarray(rec)
        out = [rec[i].tobytes() for i in range(len(lost))]
    if ctx.fault is not None:
        out = [ctx.fault(b) for b in out]
    rec = bench_lib.op_record(t0, lost_rows=len(lost))
    with ctx.spans.span("repair.hash_check"):      # after the clock
        bad = sum(bench_lib.sha256(b) != ctx.hashes[seg][row]
                  for b, row in zip(out, lost))
    rec["ok"] = not bad
    ctx.checked += len(lost)
    ctx.mismatched += bad
    if keep:
        ctx.kept.append((seg, helpers, lost, out))
    return rec


def warm(ctx) -> None:
    k = ctx.config["k"]
    rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 5))
    shapes = [e for e in ctx.losses if e <= 3] or ctx.losses[:1]
    with ctx.spans.span("warm"):
        ctx.engine.warm_repair(
            [(tuple(range(e, e + k)), tuple(range(e))) for e in shapes],
            ctx.n, buckets=(1,))
        for e in shapes:
            for _ in range(2):
                _repair(ctx, *draw(ctx, rng, e))
    ctx.checked = ctx.mismatched = 0
    ctx.kept = []


def op(ctx):
    seg, helpers, lost = draw(ctx, ctx.rng)
    keep = ctx.n_ops in ctx.keep_at or (
        len(lost) > 1 and not any(len(x[2]) > 1 for x in ctx.kept))
    ctx.n_ops += 1
    return _repair(ctx, seg, helpers, lost, keep)


def drain(ctx) -> list:
    return []


def counters(ctx) -> dict:
    return {"engine": bench_lib.engine_counters(ctx.engine)}


def check(ctx, ops) -> list[dict]:
    """Every repaired row's SHA-256 against the original's (counted as
    the window went); the kept operations rebuilt by the plain reference
    from the same survivors, byte for byte — one of them with two or more
    rows lost: where the window drew none, one is made now, through the
    same path."""
    forced = not any(len(lost) > 1 for _, _, lost, _ in ctx.kept)
    if forced:
        rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 6))
        e = next((e for e in ctx.losses if e > 1), ctx.losses[-1])
        _repair(ctx, *draw(ctx, rng, e), keep=True)
    differ = compared = 0
    for seg, helpers, lost, out in ctx.kept:
        want = ctx.ref.reconstruct(ctx.pool[seg, list(helpers)], helpers,
                                   lost)
        for i in range(len(lost)):
            differ += bench_lib.n_differ(
                np.frombuffer(out[i], dtype=np.uint8), want[i])
            compared += 1
    ctx.say(info="check", repairs_hashed=ctx.checked,
            kept=[[list(h), list(lo)] for _, h, lo, _ in ctx.kept],
            rows_compared=compared, multi_loss_forced=forced)
    return [{"what": "repaired rows whose SHA-256 differs from the "
                     "original's", "value": ctx.mismatched, "limit": 0},
            {"what": "repaired rows hashed (none: 1)",
             "value": 0 if ctx.checked else 1, "limit": 0},
            {"what": "bytes of the kept repairs that differ from the "
                     "plain reference's reconstruction from the same "
                     "survivors", "value": differ, "limit": 0},
            {"what": "kept repairs compared with the reference, one of "
                     "them with two or more rows lost (missing: 1)",
             "value": 0 if compared and any(
                 len(lo) > 1 for _, _, lo, _ in ctx.kept) else 1,
             "limit": 0},
            *bench_lib.engine_comparisons(ctx.engine)]


def close(ctx) -> None:
    if getattr(ctx, "engine", None) is not None:
        ctx.engine.close()


# -- tests only ------------------------------------------------------------
def _flip_byte(ctx):
    """Every repaired row comes back with one byte altered."""
    ctx.fault = lambda out: out[:-1] + bytes([out[-1] ^ 0x40])


def _wrong_helpers(ctx):
    """The degraded guarantee: whichever helpers were stacked, the engine
    is told the k lowest survivors (the one pattern a per-row cache
    would hold)."""
    k = ctx.config["k"]
    real = ctx.engine.reconstruct

    def reconstruct(stack, helpers, lost):
        lowest = tuple(j for j in range(ctx.rows) if j not in lost)[:k]
        return real(stack, lowest, lost)
    ctx.engine.reconstruct = reconstruct


CONTROLS = {"flip_byte": _flip_byte, "wrong_helpers": _wrong_helpers}
