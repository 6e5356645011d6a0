"""Traffic kind ``verify_round``: a closed loop of one TEE worker that
judges audit rounds at its full quota. One operation = one round of
``missions`` verify missions, their owed sets ``fragments_total``
fragment hashes in all, ragged by miner size, through the TEE's own
entry point ``TeeAgent.judge_round`` (node/offchain.py): proofs as wire
bytes and owed sets as fragment hashes in, one
``audit.submit_verify_result`` extrinsic a mission out, recorded by the
stand-in node below and read back. The verifier reads no fragment
bytes.

Set-up, all from the seed: the mission sizes (Zipf, s = 1, over rank:
``round(fragments_total / (rank * H_missions))``, the remainder to rank
1), 32-byte fragment hashes for every owed fragment, the order of the
missions (one shuffle), and ``rounds_prepared`` round seeds whose
honest proofs the PLAIN REFERENCE makes from the key
(reference/verify_round_ref.py ``honest_proofs``; on whichever device
JAX runs on: threefry and the field are exact on both). The last rank
is a real miner: it holds seeded fragment bytes, its hashes are their
SHA-256 and its proof is the frozen reference prover's over those bytes
(podr2_ref.prove_aggregate, on the CPU device). The wire bytes are the
program's codec over the reference's (mu, sigma), as a miner frames
them; every idle proof is the all-zero proof over an empty filler set.

Per operation the window cycles through the prepared rounds and draws
its dishonest missions afresh from the seed and the operation's number,
one tamper of each kind in ``dishonest``: ``flip_mu`` / ``flip_sigma``
(one bit of one word), ``uncovered_fragment`` (one owed hash replaced
by one the proof does not cover), ``swapped`` (two missions' proofs
exchanged: both fail), ``malformed`` (the blob cut short). The largest
mission and the real miner stay honest. The operation is ``ok`` when
every mission's recorded verdict is the expected one (six False, the
rest True) and every idle verdict True; ``frags`` counts the round's
owed fragments then.

Check, after the window: ``check_rounds`` operations (the last, and
others drawn from the seed) have ``check_missions`` of their missions —
every dishonest one, the largest and the real miner — judged again by
the plain reference on the CPU device from the regenerated inputs, and
every recorded extrinsic is read back.

Parameters: missions, fragments_total, rounds_prepared, dishonest,
check_rounds, check_missions (sizes, real_miner: prose for the reader).
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

import bench_lib
from reference import podr2_ref, verify_round_ref

CALL = "audit.submit_verify_result"
CONTROLLER = "tee0"
TAMPERS = ("flip_mu", "flip_sigma", "uncovered_fragment", "swapped",
           "malformed")


class _Node:
    """The stand-in for the chain on the verify path: ``judge_round``
    makes one chain call a mission; this records each, and the check
    reads them back (tee-verify-caps.json, ``reduced``: chain)."""

    def __init__(self):
        from cess_tpu.node import chain_spec

        self.spec = chain_spec.dev_spec()    # the TEE's account key
        self.extrinsics: list[tuple] = []

    def submit_extrinsic(self, account: str, call: str, *args) -> None:
        self.extrinsics.append((account, call, args))


def mission_sizes(missions: int, total: int) -> list[int]:
    harmonic = sum(1.0 / r for r in range(1, missions + 1))
    sizes = [max(1, round(total / (r * harmonic)))
             for r in range(1, missions + 1)]
    sizes[0] += total - sum(sizes)
    return sizes


def ids_of(hashes) -> np.ndarray:
    """Fragment ids (the low 8 bytes of each hash as two uint32 words),
    the benchmark's own reading of 32-byte hashes."""
    if not len(hashes):
        return np.zeros((0, 2), np.uint32)
    return np.frombuffer(b"".join(hashes), dtype="<u4").reshape(
        len(hashes), 8)[:, :2].astype(np.uint32)


def setup(ctx) -> None:
    from cess_tpu import codec
    from cess_tpu.chain import audit as chain_audit
    from cess_tpu.node import offchain
    from cess_tpu.ops import podr2

    if not hasattr(offchain.TeeAgent, "judge_round"):
        # a program from before PR 33: fail at once, before any data
        raise SystemExit("benchmark/traffic/verify_round.py: this "
                         "program's TeeAgent has no judge_round "
                         "(it judges missions one by one)")
    c, t = ctx.config, ctx.traffic
    unknown = set(t["dishonest"]) - set(TAMPERS)
    if unknown:
        raise ValueError(f"dishonest {sorted(unknown)}: one of {TAMPERS}")
    ctx.blocks = c["fragment_size"] // c["podr2_block_bytes"]
    ctx.sizes = mission_sizes(t["missions"], t["fragments_total"])
    offs = np.concatenate([[0], np.cumsum(ctx.sizes)])
    raw = bench_lib.seeded_bytes(bench_lib.sub_seed(ctx.seed, 2),
                                 32 * t["fragments_total"]).tobytes()
    owed = [sorted(raw[32 * f:32 * f + 32]
                   for f in range(offs[m], offs[m + 1]))
            for m in range(t["missions"])]
    # the real miner: the last rank holds bytes, its hashes are theirs
    n = c["fragment_size"]
    ctx.real = t["missions"] - 1
    frags = bench_lib.seeded_bytes(
        bench_lib.sub_seed(ctx.seed, 3), ctx.sizes[-1] * n).reshape(-1, n)
    held = sorted((bench_lib.sha256(f), i) for i, f in enumerate(frags))
    owed[ctx.real] = [h for h, _ in held]
    frags = frags[[i for _, i in held]]
    ctx.owed = [tuple(hs) for hs in owed]
    ctx.ids = [ids_of(hs) for hs in owed]

    ctx.ref_key = podr2_ref.generate_key(bench_lib.key_seed(ctx))
    flat = np.concatenate(ctx.ids)
    ctx.rounds = []              # (seed, mu [M, s], sigma [M, limbs])
    with ctx.spans.span("reference.honest_proofs"):
        for j in range(t["rounds_prepared"]):
            seed = b"bench-verify:%d:%d" % (ctx.seed, j)
            mu, sigma = verify_round_ref.honest_proofs(
                ctx.ref_key, seed, ctx.blocks, flat, ctx.sizes,
                bench_lib.sub_seed(ctx.seed, 4, j))
            with podr2_ref.on_cpu():
                idx, nu = podr2_ref.gen_challenge(seed, ctx.blocks)
                mu[ctx.real], sigma[ctx.real] = podr2_ref.prove_aggregate(
                    ctx.ref_key, ctx.ids[ctx.real], frags, idx, nu,
                    podr2_ref.aggregate_coeffs(seed, ctx.ids[ctx.real]))
            ctx.rounds.append((seed, mu, sigma))
    del frags

    # as a miner frames them: the program's codec over the reference's
    # numbers; the chain's own record types around them
    def wire(mu, sigma) -> bytes:
        return codec.encode(offchain.Proof(
            mu=np.ascontiguousarray(mu, dtype=np.uint32),
            sigma=np.ascontiguousarray(sigma, dtype=np.uint32)))
    ctx.wire = wire
    limbs = c["podr2_limbs"]
    zero = wire(np.zeros(c["podr2_sectors"]), np.zeros(limbs))
    rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 5))
    ctx.order = rng.permutation(t["missions"]).tolist()
    ctx.largest = 0
    snaps = [chain_audit.MinerSnapshot(
        miner=f"miner{m:03d}", idle_space=0, service_space=ctx.sizes[m] * n,
        service_frags=ctx.owed[m], fillers=()) for m in range(t["missions"])]
    ctx.missions = [[chain_audit.ProveInfo(
        miner=snaps[m].miner, snapshot=snaps[m], idle_proof=zero,
        service_proof=wire(mu[m], sigma[m])) for m in ctx.order]
        for _, mu, sigma in ctx.rounds]

    ctx.engine = bench_lib.make_engine(
        ctx, podr2.Podr2Key.generate(bench_lib.key_seed(ctx)))
    ctx.node = _Node()
    ctx.tee = offchain.TeeAgent(ctx.node, CONTROLLER, ctx.engine.audit.key,
                                ctx.blocks, engine=ctx.engine)
    ctx.n_ops = 0
    ctx.judged = {}              # op number -> its recorded verdicts


def tampers(ctx, op: int):
    """{kind: missions} of one operation, from the seed and its number
    alone: the positions (in rank numbering) and every drawn detail."""
    rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 6, op + 2))
    kinds = list(ctx.traffic["dishonest"])
    free = [m for m in range(len(ctx.sizes))
            if m not in (ctx.largest, ctx.real)]
    picks = rng.choice(free, len(kinds) + ("swapped" in kinds),
                       replace=False).tolist()
    draw = {}
    for kind in kinds:
        ms = [picks.pop(), picks.pop()] if kind == "swapped" \
            else [picks.pop()]
        draw[kind] = (ms, int(rng.integers(1 << 30)), rng.bytes(32))
    return draw


def dishonest_round(ctx, op: int):
    """The operation's round as the TEE and as the reference take it:
    (missions in the round's order, {rank: (ids, proof or None)} of the
    tampered missions, the ranks expected to fail)."""
    j = op % len(ctx.rounds)
    _, mu, sigma = ctx.rounds[j]
    missions = list(ctx.missions[j])
    at = {m: p for p, m in enumerate(ctx.order)}
    seen, bad = {}, set()

    def put(m, **changed):
        missions[at[m]] = dataclasses.replace(missions[at[m]], **changed)

    for kind, (ms, number, fresh) in tampers(ctx, op).items():
        bad.update(ms)
        m = ms[0]
        if kind in ("flip_mu", "flip_sigma"):
            u, s = mu[m].copy(), sigma[m].copy()
            words = u if kind == "flip_mu" else s
            words[number % len(words)] ^= np.uint32(
                1 << (number // 256 % 31))
            put(m, service_proof=ctx.wire(u, s))
            seen[m] = (ctx.ids[m], (u, s))
        elif kind == "malformed":
            blob = missions[at[m]].service_proof
            put(m, service_proof=blob[:number % len(blob)])
            seen[m] = (ctx.ids[m], None)
        elif kind == "uncovered_fragment":
            hashes = list(ctx.owed[m])
            hashes[number % len(hashes)] = fresh
            put(m, snapshot=dataclasses.replace(
                missions[at[m]].snapshot, service_frags=tuple(hashes)))
            seen[m] = (ids_of(hashes), (mu[m], sigma[m]))
        else:                                   # swapped
            a, b = ms
            blob_a = missions[at[a]].service_proof
            put(a, service_proof=missions[at[b]].service_proof)
            put(b, service_proof=blob_a)
            seen[a] = (ctx.ids[a], (mu[b], sigma[b]))
            seen[b] = (ctx.ids[b], (mu[a], sigma[a]))
    return missions, seen, bad


def _round(ctx, op: int) -> dict:
    with ctx.spans.span("verify_round.draw_dishonest"):
        missions, _, bad = dishonest_round(ctx, op)
    seed = ctx.rounds[op % len(ctx.rounds)][0]
    first = len(ctx.node.extrinsics)
    t0 = time.perf_counter()
    with ctx.spans.span("tee.judge_round"):
        ctx.tee.judge_round(ctx.node, missions, seed, op)
    rec = bench_lib.op_record(t0, index=op)
    with ctx.spans.span("verify_round.read_back"):       # after the clock
        said = ctx.node.extrinsics[first:]
        got = {args[0]: args for _, _, args in said}
        ok = len(said) == len(got) == len(missions)
        verdicts = []
        for m in range(len(ctx.sizes)):
            _, idle_ok, service_ok, _ = got.get(
                f"miner{m:03d}", (None, False, None, b""))
            verdicts.append(service_ok)
            ok = ok and idle_ok is True and service_ok is (m not in bad)
    ctx.judged[op] = verdicts
    rec["ok"] = bool(ok)
    rec["frags"] = sum(ctx.sizes) if ok else 0
    return rec


def warm(ctx) -> None:
    with ctx.spans.span("warm"):
        ctx.tee.warm_verify()           # every shape a round can meet
        for op in (-1, -2):             # draws the window does not make
            _round(ctx, op)
    ctx.node.extrinsics.clear()
    ctx.judged.clear()


def op(ctx):
    ctx.n_ops += 1
    return _round(ctx, ctx.n_ops - 1)


def drain(ctx) -> list:
    return []


def counters(ctx) -> dict:
    return {"engine": bench_lib.engine_counters(ctx.engine)}


def check(ctx, ops) -> list[dict]:
    """Sampled missions of sampled rounds judged again by the plain
    reference on the CPU device, from inputs regenerated from the seed;
    every recorded extrinsic read back."""
    t = ctx.traffic
    sample = bench_lib.draw_sample(ctx.seed, ctx.n_ops, t["check_rounds"],
                                   ctx.n_ops - 1)
    differ = judged = honest_rejected = dishonest_accepted = 0
    for op_no in sample:
        _, seen, bad = dishonest_round(ctx, op_no)
        seed, mu, sigma = ctx.rounds[op_no % len(ctx.rounds)]
        picked = sorted(bad) + [ctx.largest, ctx.real]
        rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 7, op_no))
        rest = [m for m in rng.permutation(len(ctx.sizes)).tolist()
                if m not in picked]
        picked += rest[:max(0, t["check_missions"] - len(picked))]
        owed_ids = [seen[m][0] if m in seen else ctx.ids[m] for m in picked]
        proofs = [seen[m][1] if m in seen else (mu[m], sigma[m])
                  for m in picked]
        with podr2_ref.on_cpu():
            want = verify_round_ref.verdicts(ctx.ref_key, seed, ctx.blocks,
                                             owed_ids, proofs)
        for m, verdict in zip(picked, want):
            judged += 1
            differ += ctx.judged[op_no][m] is not verdict
            honest_rejected += m not in bad and not verdict
            dishonest_accepted += m in bad and verdict
    unread = sum(
        not (account == CONTROLLER and call == CALL and len(args) == 4
             and args[1] is True and isinstance(args[2], bool)
             and args[3] == b"")
        for account, call, args in ctx.node.extrinsics)
    ctx.say(info="check", rounds=ctx.n_ops, rounds_compared=sample,
            missions_compared=judged,
            extrinsics=len(ctx.node.extrinsics))
    return [{"what": "verdicts that differ from the plain reference's "
                     "(sampled missions of sampled rounds)",
             "value": differ, "limit": 0},
            {"what": "missions judged again by the reference (none: 1)",
             "value": 0 if judged else 1, "limit": 0},
            {"what": "honest proofs the reference rejects",
             "value": honest_rejected, "limit": 0},
            {"what": "tampered proofs the reference accepts",
             "value": dishonest_accepted, "limit": 0},
            {"what": "extrinsics recorded that are not one well-formed "
                     "audit.submit_verify_result a mission",
             "value": unread + abs(len(ctx.node.extrinsics)
                                   - ctx.n_ops * len(ctx.sizes)),
             "limit": 0},
            *bench_lib.engine_comparisons(ctx.engine)]


def close(ctx) -> None:
    if getattr(ctx, "engine", None) is not None:
        ctx.engine.close()


# -- tests only ------------------------------------------------------------
def _stale_verdicts(ctx):
    """The degraded guarantee: the TEE answers every round with the
    verdicts of the first round it judged (remembered, not computed)."""
    real, first = ctx.tee.verify_round, []

    def verify_round(proofs, owed_sets, seed, challenge=None):
        if not first:
            first.append(real(proofs, owed_sets, seed, challenge))
        return list(first[0])
    ctx.tee.verify_round = verify_round


def _accept_all(ctx):
    """Every proof is accepted unjudged."""
    ctx.tee.verify_round = \
        lambda proofs, owed_sets, seed, challenge=None: [True] * len(proofs)


CONTROLS = {"stale_verdicts": _stale_verdicts, "accept_all": _accept_all}
