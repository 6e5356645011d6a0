"""Traffic kind ``repair_burst``: a closed loop of one rebuilder working
through the restoral backlog of deals that lost ``lost_rows`` of their
holders at once. A deal's segments share their holders (miner j of the
deal's list holds row j of every segment), so every segment of a deal has
lost the same rows: per operation, all drawn from the seed, a deal of a
pool of reference-encoded deals and the lost set, uniform of the
C(k+m, lost_rows); the helpers are the k lowest surviving rows, ascending
(``answering`` "lowest", the order MinerAgent.try_repair asks its peers
in). The operation is one BURST: one ``engine.submit_reconstruct`` a
segment of the deal, ``deal_segments`` submits in a row from one thread,
each handing over the segment's k survivors as a list of 1-D views of the
pool (never stacked on the host), then the ``result()``s in submit order,
every rebuilt row copied out with ``tobytes`` (as MinerAgent stores them).
The clock runs from the first submit until the burst's last rebuilt row is
host ``bytes``. After it, every row is hashed (SHA-256, a few threads:
``hashlib`` lets go of the interpreter lock and the engine is idle then)
and compared with the original's hash.

Warm-up: ``engine.warm_repair`` for the shape (k present, ``lost_rows``
missing) at buckets 1, 2, 4 ... up to a burst's, every count of requests
that pads to them included, then two whole bursts with patterns from a
sub-seed the window does not draw from: the window's patterns are new to
the program, and however the batcher splits a burst nothing compiles.

What the check's "host side" line says of every run, traced or not: the
burst's clock split where the first ``result()`` returns (the batch is
resolved whole, so before it lies the engine — put, program, fetch,
regroup — and behind it the caller's ``tobytes``), and the process's
minor page faults a burst over the window (``getrusage``; a burst's two
host copies of 128 MiB into fresh pages are 65,536 of them). PERF.md §7:
a process is of a faster or a slower kind from its first burst to its
last, and these readings say which part carries it.

Parameters: pool_deals, deal_segments, lost_rows, answering.
"""
from __future__ import annotations

import concurrent.futures
import resource
import time

import numpy as np

import bench_lib
from reference import rs_ref

KEEP_LATER = 32      # the second kept burst's index is drawn below this
HASH_THREADS = 4


def setup(ctx) -> None:
    c, t = ctx.config, ctx.traffic
    k, rows = c["k"], c["k"] + c["m"]
    if not 1 <= t["lost_rows"] <= c["m"]:
        raise ValueError(f"{t['lost_rows']} lost rows: RS({k},{c['m']}) "
                         f"survives 1 to {c['m']}")
    if t["answering"] != "lowest":
        raise ValueError(f"answering {t['answering']!r}: lowest")
    n = c["segment_size"] // k
    segments = t["pool_deals"] * t["deal_segments"]
    data = bench_lib.seeded_bytes(
        bench_lib.sub_seed(ctx.seed, 2),
        segments * c["segment_size"]).reshape(segments, k, n)
    # the pool is made by the plain reference: the rows the holders keep
    ctx.ref = rs_ref.ReferenceCodec(k, c["m"])
    ctx.pool = ctx.ref.encode(data)
    ctx.hashers = concurrent.futures.ThreadPoolExecutor(HASH_THREADS)
    ctx.hashes = [list(ctx.hashers.map(bench_lib.sha256, ctx.pool[s]))
                  for s in range(segments)]
    ctx.n, ctx.rows = n, rows
    ctx.engine = bench_lib.make_engine(ctx)
    ctx.rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 3))
    ctx.fault = ctx.dropped = None
    ctx.mismatched = ctx.checked = ctx.n_ops = 0
    # bursts whose rebuilt bytes are kept for the reference: the window's
    # first and a later one drawn from the seed
    ctx.keep_at = {0, 1 + bench_lib.sub_seed(ctx.seed, 4) % KEEP_LATER}
    ctx.kept = []


def draw(ctx, rng) -> tuple:
    """(deal, helpers, lost): the lost set uniform of the stripe's rows,
    the helpers the k lowest survivors, both ascending."""
    deal = int(rng.integers(ctx.traffic["pool_deals"]))
    lost = tuple(sorted(rng.choice(
        ctx.rows, ctx.traffic["lost_rows"], replace=False).tolist()))
    survivors = [j for j in range(ctx.rows) if j not in lost]
    return deal, tuple(survivors[:ctx.config["k"]]), lost


def _burst(ctx, deal: int, helpers: tuple, lost: tuple, keep=False) -> dict:
    per = ctx.traffic["deal_segments"]
    segs = range(deal * per, (deal + 1) * per)
    t0 = time.perf_counter()
    with ctx.spans.span("engine.submit_burst"):
        futs = [None if i == ctx.dropped else ctx.engine.submit_reconstruct(
            [ctx.pool[seg, j] for j in helpers], helpers, lost)
            for i, seg in enumerate(segs)]
    out, t_first = [], None
    for fut in futs:
        if fut is None:             # tests only: a segment never asked for
            out.extend(bytes(ctx.n) for _ in lost)
            continue
        with ctx.spans.span("engine.result"):
            rec = fut.result()
        if t_first is None:
            t_first = time.perf_counter()
        with ctx.spans.span("repair.fetch_bytes"):
            out.extend(rec[i].tobytes() for i in range(len(lost)))
    if ctx.fault is not None:
        out = ctx.fault(out)
    rec = bench_lib.op_record(t0, rebuilt_bytes=sum(map(len, out)),
                              lost_rows=len(lost),
                              first_result_s=t_first - t0)
    with ctx.spans.span("repair.hash_check"):      # after the clock
        want = [ctx.hashes[seg][row] for seg in segs for row in lost]
        bad = sum(got != w for got, w in zip(
            ctx.hashers.map(bench_lib.sha256, out), want))
    rec["ok"] = not bad
    ctx.checked += len(out)
    ctx.mismatched += bad
    if keep:
        ctx.kept.append((deal, helpers, lost, out))
    return rec


def warm(ctx) -> None:
    k, e = ctx.config["k"], ctx.traffic["lost_rows"]
    rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 5))
    # 1, 2, 4 ... up to the power of two a whole burst pads to
    top = (ctx.traffic["deal_segments"] - 1).bit_length()
    with ctx.spans.span("warm"):
        ctx.engine.warm_repair(
            [(tuple(range(e, e + k)), tuple(range(e)))], ctx.n,
            buckets=tuple(1 << i for i in range(top + 1)))
        for _ in range(2):
            _burst(ctx, *draw(ctx, rng))
    ctx.checked = ctx.mismatched = 0
    ctx.kept = []
    ctx.faults_warm = resource.getrusage(resource.RUSAGE_SELF)


def op(ctx):
    keep = ctx.n_ops in ctx.keep_at
    ctx.n_ops += 1
    return _burst(ctx, *draw(ctx, ctx.rng), keep)


def drain(ctx) -> list:
    ctx.faults_window = resource.getrusage(resource.RUSAGE_SELF)
    return []


def counters(ctx) -> dict:
    return {"engine": bench_lib.engine_counters(ctx.engine)}


def _data_row_lost(ctx, lost) -> bool:
    return any(row < ctx.config["k"] for row in lost)


def _say_host_side(ctx, ops) -> None:
    """Which part of a burst's clock this process spent where, and how
    many pages it faulted in for it (the module's note)."""
    a, b = ctx.faults_warm, getattr(ctx, "faults_window", None)
    if not ops or b is None:
        return
    engine = sorted(o["first_result_s"] for o in ops)
    copy = sorted(o["latency_s"] - o["first_result_s"] for o in ops)
    ctx.say(info="host side", bursts=len(ops),
            first_result_ms_p50=1e3 * engine[len(ops) // 2],
            copy_out_ms_p50=1e3 * copy[len(ops) // 2],
            minor_faults_per_burst=(b.ru_minflt - a.ru_minflt) / len(ops),
            major_faults=b.ru_majflt - a.ru_majflt,
            system_s_per_burst=(b.ru_stime - a.ru_stime) / len(ops),
            user_s_per_burst=(b.ru_utime - a.ru_utime) / len(ops))


def check(ctx, ops) -> list[dict]:
    """Every rebuilt row's SHA-256 against the original's (counted as the
    window went); the kept bursts rebuilt by the plain reference from the
    same survivors, byte for byte — two of them, one with a data row
    among the lost (the matrix is then a true inverse, not parity rows):
    what the window did not give is made now, through the same path."""
    _say_host_side(ctx, ops)
    rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 6))
    forced = 0
    while len(ctx.kept) < 2 or not any(
            _data_row_lost(ctx, lost) for _, _, lost, _ in ctx.kept):
        deal, helpers, lost = draw(ctx, rng)
        if _data_row_lost(ctx, lost):
            _burst(ctx, deal, helpers, lost, keep=True)
            forced += 1
    per, e = ctx.traffic["deal_segments"], ctx.traffic["lost_rows"]
    differ = compared = 0
    for deal, helpers, lost, out in ctx.kept:
        for i in range(per):
            want = ctx.ref.reconstruct(
                ctx.pool[deal * per + i, list(helpers)], helpers, lost)
            for j in range(e):
                differ += bench_lib.n_differ(
                    np.frombuffer(out[i * e + j], dtype=np.uint8), want[j])
                compared += 1
    ctx.say(info="check", rows_hashed=ctx.checked,
            kept=[[deal, list(h), list(lo)] for deal, h, lo, _ in ctx.kept],
            rows_compared=compared, bursts_forced=forced)
    return [{"what": "rebuilt rows whose SHA-256 differs from the "
                     "original's", "value": ctx.mismatched, "limit": 0},
            {"what": "rebuilt rows hashed (none: 1)",
             "value": 0 if ctx.checked else 1, "limit": 0},
            {"what": "bytes of the kept bursts that differ from the plain "
                     "reference's reconstruction from the same survivors",
             "value": differ, "limit": 0},
            {"what": "kept bursts compared with the reference, one of them "
                     "with a data row among the lost (fewer than two, or "
                     "none such: 1)",
             "value": 0 if len(ctx.kept) >= 2 and any(
                 _data_row_lost(ctx, lo) for _, _, lo, _ in ctx.kept)
             else 1, "limit": 0},
            *bench_lib.engine_comparisons(ctx.engine)]


def close(ctx) -> None:
    if getattr(ctx, "engine", None) is not None:
        ctx.engine.close()
    if getattr(ctx, "hashers", None) is not None:
        ctx.hashers.shutdown()


# -- tests only ------------------------------------------------------------
def _flip_byte(ctx):
    """A rebuilt row of every burst comes back with a byte altered."""
    ctx.fault = lambda out: out[:-1] + [
        out[-1][:-1] + bytes([out[-1][-1] ^ 0x40])]


def _wrong_helpers(ctx):
    """The engine is told another helper set than the rows it is handed:
    the k survivors from the second lowest on."""
    k = ctx.config["k"]
    real = ctx.engine.submit_reconstruct

    def submit_reconstruct(rows, helpers, lost):
        survivors = [j for j in range(ctx.rows) if j not in lost]
        return real(rows, tuple(survivors[1:k + 1]), lost)
    ctx.engine.submit_reconstruct = submit_reconstruct


def _dropped_segment(ctx):
    """One of a burst's segments is never submitted and its rows are
    zeros."""
    ctx.dropped = ctx.traffic["deal_segments"] - 1


CONTROLS = {"flip_byte": _flip_byte, "wrong_helpers": _wrong_helpers,
            "dropped_segment": _dropped_segment}
