"""Traffic kind ``audit``: a closed loop of one client that runs audit
rounds over one miner's service set. Set-up tags the fragments through
``engine.tag_fragments``. Per operation a fresh round seed ->
``podr2.gen_challenge`` and ``aggregate_coeffs`` -> the miner's
``engine.prove_aggregate`` -> the verifier's ``engine.verify_aggregate``; the
set's fragments count when the proof is accepted. In set-up a proof over a
fragment with one flipped byte in a challenged block must be rejected.

Parameters: fragments (the service set), check_tags, check_rounds.
"""
from __future__ import annotations

import time

import numpy as np

import bench_lib
from reference import podr2_ref


def setup(ctx) -> None:
    from cess_tpu.ops import podr2

    c, t = ctx.config, ctx.traffic
    ctx.podr2 = podr2
    ctx.key_seed = bench_lib.key_seed(ctx)
    n = c["fragment_size"]
    ctx.frags = bench_lib.seeded_bytes(
        bench_lib.sub_seed(ctx.seed, 2), t["fragments"] * n).reshape(
            t["fragments"], n)
    ctx.ids = np.stack([podr2_ref.fragment_id_from_hash(bench_lib.sha256(f))
                        for f in ctx.frags])
    ctx.blocks = n // c["podr2_block_bytes"]
    ctx.engine = bench_lib.make_engine(
        ctx, podr2.Podr2Key.generate(ctx.key_seed))
    with ctx.spans.span("tag_service_set"):
        ctx.tags = np.asarray(ctx.engine.tag_fragments(ctx.ids, ctx.frags))
    ctx.round = 0
    ctx.rounds = []            # (seed, mu, sigma) of every round
    ctx.fault = None
    ctx.corrupt_accepted = None


def _round(ctx, frags=None) -> dict:
    eng, podr2 = ctx.engine, ctx.podr2
    frags = ctx.frags if frags is None else frags
    seed = b"bench-round:%d:%d" % (ctx.seed, ctx.round)
    ctx.round += 1
    t0 = time.perf_counter()
    with ctx.spans.span("audit.round"):
        with ctx.spans.span("podr2.gen_challenge"):
            idx, nu = (np.asarray(a) for a in
                       podr2.gen_challenge(seed, ctx.blocks))
            r = np.asarray(podr2.aggregate_coeffs(seed, ctx.ids))
        with ctx.spans.span("engine.prove_aggregate"):
            mu, sigma = eng.prove_aggregate(frags, ctx.tags, idx, nu, r)
        if ctx.fault is not None:
            mu, sigma = ctx.fault(mu, sigma)
        with ctx.spans.span("engine.verify_aggregate"):
            accepted = eng.verify_aggregate(ctx.ids, ctx.blocks, idx, nu,
                                            r, mu, sigma) is True
    rec = bench_lib.op_record(t0, ok=accepted,
                              frags=len(frags) if accepted else 0,
                              index=len(ctx.rounds))
    ctx.rounds.append((seed, idx, np.asarray(mu), np.asarray(sigma)))
    return rec


def warm(ctx) -> None:
    with ctx.spans.span("warm"):
        for _ in range(2):
            _round(ctx)
        # one flipped byte in a challenged block: must be rejected
        seed = b"bench-round:%d:%d" % (ctx.seed, ctx.round)   # the next one
        idx, _ = ctx.podr2.gen_challenge(seed, ctx.blocks)
        bad = ctx.frags.copy()
        bad[len(bad) // 2,
            int(np.asarray(idx)[0]) * ctx.config["podr2_block_bytes"] + 3] ^= 0x40
        ctx.corrupt_accepted = _round(ctx, bad)["ok"]
        del bad
    ctx.rounds.clear()


def op(ctx):
    return _round(ctx)


def drain(ctx) -> list:
    return []


def counters(ctx) -> dict:
    return {"engine": bench_lib.engine_counters(ctx.engine)}


def check(ctx, ops) -> list[dict]:
    """A sample of the set's tags and of the window's (mu, sigma) against
    the plain reference; the reference verifier accepts them too."""
    t = ctx.traffic
    rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 4))
    with podr2_ref.on_cpu():
        key = podr2_ref.generate_key(ctx.key_seed)
    tag_diff = 0
    picked = rng.choice(len(ctx.frags), min(t["check_tags"],
                                            len(ctx.frags)), replace=False)
    for f in picked:
        with podr2_ref.on_cpu():
            want = podr2_ref.tag_fragment(key, ctx.ids[f], ctx.frags[f])
        tag_diff += bench_lib.n_differ(ctx.tags[f], want)
    done = ctx.rounds
    sample = bench_lib.draw_sample(ctx.seed, len(done), t["check_rounds"],
                                   len(done) - 1)
    proof_diff = ref_rejected = 0
    for j in sample:
        seed, _, mu, sigma = done[j]
        with podr2_ref.on_cpu():
            idx, nu = podr2_ref.gen_challenge(seed, ctx.blocks)
            r = podr2_ref.aggregate_coeffs(seed, ctx.ids)
            # the reference makes its own tags (at the challenged
            # blocks) from the key: nothing of the program's goes in
            want_mu, want_sigma = podr2_ref.prove_aggregate(
                key, ctx.ids, ctx.frags, idx, nu, r)
            ref_rejected += not podr2_ref.verify_aggregate(
                key, ctx.ids, idx, nu, r, mu, sigma)
        proof_diff += bench_lib.n_differ(mu, want_mu) \
            + bench_lib.n_differ(sigma, want_sigma)
    ctx.say(info="check", rounds=len(ctx.rounds), tags_compared=len(picked),
            rounds_compared=sample)
    return [{"what": "a proof over a fragment with a flipped byte was "
                     "accepted", "value": int(bool(ctx.corrupt_accepted)),
             "limit": 0},
            {"what": "tags differ from reference PoDR2 (words)",
             "value": tag_diff, "limit": 0},
            {"what": "rounds compared with the reference (none: 1)",
             "value": 0 if sample else 1, "limit": 0},
            {"what": "(mu, sigma) differ from the reference proof (words)",
             "value": proof_diff, "limit": 0},
            {"what": "proofs the reference verifier rejects",
             "value": ref_rejected, "limit": 0},
            *bench_lib.engine_comparisons(ctx.engine)]


def close(ctx) -> None:
    if getattr(ctx, "engine", None) is not None:
        ctx.engine.close()


# -- tests only ------------------------------------------------------------
def _stale_proof(ctx):
    """The degraded guarantee: the miner answers every round with its
    first proof (a cached answer)."""
    first = []

    def fault(mu, sigma):
        first.append((mu, sigma))
        return first[0]
    ctx.fault = fault


def _flip_mu(ctx):
    """One word of every proof altered where it is produced."""
    def fault(mu, sigma):
        mu = np.array(mu)
        mu[0] ^= 1
        return mu, sigma
    ctx.fault = fault


CONTROLS = {"stale_proof": _stale_proof, "flip_mu": _flip_mu}
