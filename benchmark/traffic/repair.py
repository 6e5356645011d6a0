"""Traffic kind ``repair``: a closed loop of one miner that waits for its
fragment. Per operation: a segment from a seeded pool of reference-encoded
segments and the lost row, both drawn from the seed; the k survivors are
stacked on the host, handed to ``engine.reconstruct`` and the repaired
fragment is fetched as host bytes — MinerAgent._repair_via_fragments
(node/offchain.py). After its clock stops, every operation's bytes are
hashed and compared with the original fragment's SHA-256.

Parameters: pool_segments, lost_rows (the rows that can be lost).
"""
from __future__ import annotations

import time

import numpy as np

import bench_lib
from reference import rs_ref


def setup(ctx) -> None:
    c, t = ctx.config, ctx.traffic
    k, rows = c["k"], c["k"] + c["m"]
    n = c["segment_size"] // k
    data = bench_lib.seeded_bytes(
        bench_lib.sub_seed(ctx.seed, 2),
        t["pool_segments"] * c["segment_size"]).reshape(
            t["pool_segments"], k, n)
    # the pool is made by the plain reference: the survivors a miner holds
    ctx.pool = rs_ref.ReferenceCodec(k, c["m"]).encode(data)
    ctx.hashes = [[bench_lib.sha256(ctx.pool[s, j]) for j in range(rows)]
                  for s in range(t["pool_segments"])]
    ctx.present = {row: tuple(j for j in range(rows) if j != row)[:k]
                   for row in t["lost_rows"]}
    ctx.n = n
    ctx.engine = bench_lib.make_engine(ctx)
    ctx.rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 3))
    ctx.fault = None
    ctx.mismatched = 0
    ctx.checked = 0


def _repair(ctx, seg: int, row: int) -> dict:
    present = ctx.present[row]
    t0 = time.perf_counter()
    with ctx.spans.span("repair.stack_survivors"):
        stack = np.stack([ctx.pool[seg, j] for j in present])
    with ctx.spans.span("engine.reconstruct"):
        rec = ctx.engine.reconstruct(stack, present, (row,))
    with ctx.spans.span("repair.fetch_bytes"):
        out = np.asarray(rec)[0].tobytes()
    if ctx.fault is not None:
        out = ctx.fault(out)
    rec = bench_lib.op_record(t0)
    with ctx.spans.span("repair.hash_check"):      # after the clock
        rec["ok"] = bench_lib.sha256(out) == ctx.hashes[seg][row]
    ctx.checked += 1
    ctx.mismatched += not rec["ok"]
    return rec


def warm(ctx) -> None:
    with ctx.spans.span("warm"):
        ctx.engine.warm_repair(
            [(ctx.present[row], (row,)) for row in ctx.present], ctx.n)
        for row in ctx.present:
            for _ in range(2):
                _repair(ctx, 0, row)
    ctx.checked = ctx.mismatched = 0


def op(ctx):
    seg = int(ctx.rng.integers(ctx.pool.shape[0]))
    row = ctx.traffic["lost_rows"][
        int(ctx.rng.integers(len(ctx.traffic["lost_rows"])))]
    return _repair(ctx, seg, row)


def drain(ctx) -> list:
    return []


def counters(ctx) -> dict:
    return {"engine": bench_lib.engine_counters(ctx.engine)}


def check(ctx, ops) -> list[dict]:
    ctx.say(info="check", repairs_hashed=ctx.checked)
    return [{"what": "repaired fragments whose SHA-256 differs from the "
                     "original's", "value": ctx.mismatched, "limit": 0},
            {"what": "repairs hashed (none: 1)",
             "value": 0 if ctx.checked else 1, "limit": 0},
            *bench_lib.engine_comparisons(ctx.engine)]


def close(ctx) -> None:
    if getattr(ctx, "engine", None) is not None:
        ctx.engine.close()


# -- tests only ------------------------------------------------------------
def _flip_byte(ctx):
    """Every repaired fragment comes back with one byte altered."""
    ctx.fault = lambda out: out[:-1] + bytes([out[-1] ^ 0x40])


def _wrong_row(ctx):
    """The degraded guarantee: whichever row is lost, the engine is asked
    for the first lost row's pattern (a cached answer for another row)."""
    first = ctx.traffic["lost_rows"][0]
    real = ctx.engine.reconstruct

    def reconstruct(stack, present, missing):
        return real(stack, ctx.present[first], (first,))
    ctx.engine.reconstruct = reconstruct


CONTROLS = {"flip_byte": _flip_byte, "wrong_row": _wrong_row}
