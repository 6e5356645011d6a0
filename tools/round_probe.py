#!/usr/bin/env python
"""Round probe: on which thread, and when, a TEE's round should decode its
wire proofs (PERF.md section 6, PR 56).

    chiprun -- python tools/round_probe.py

One TEE at the chain's caps (500 missions, 100,000 owed fragments in Zipf
sizes, 1,000 wire proofs of which the 500 idle ones are the zero proof)
judges the same rounds under four orders, interleaved round by round in
one process on one engine:

  in_hand   decode -> ids -> challenge -> submit with (mu, sigma) ->
            gather: the order before PR 56, host and device taking turns
  signal    ids -> challenge -> submit -> wait for the engine's "folds
            out" -> decode on the caller's thread -> put -> gather:
            ``TeeAgent.verify_round`` itself, the form in the code
  executor  the same, but the decode runs on the executor's (batcher's)
            thread after its last fold's enqueue: a ``LateProofs`` whose
            ``take`` decodes; the caller sleeps in ``result()``
  racing    the caller decodes right after the submit, while the batcher
            assembles the rows and dispatches the folds: two threads that
            both want the interpreter (the form to avoid)

The proofs are random well-formed words, so every service verdict is
False: neither the decode nor the device's work depends on a proof's
truth, and the probe needs no prover. Per form: the round's wall
milliseconds (median, quartiles) and, from the engine's own stages
differenced over the form's rounds, the batch's milliseconds in assemble,
dispatch, proofs, wait and fetch. The four forms must agree on every
verdict. Then, under an ``obs.Tracer`` (a pass of its own: the timed rounds
run untraced), the stages of ``in_hand`` and ``signal`` by name: mean
milliseconds a round of every ``tee.round*``, ``engine.verify*`` and
``podr2.challenge`` span, on whichever thread it ran. ``--rehearse`` runs
tiny sizes on whatever JAX finds and says nothing about time: a CPU run
never does.
"""
import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

FORMS = ("in_hand", "signal", "executor", "racing")
STAGES = ("assemble", "dispatch", "wait", "fetch")


def zipf_sizes(missions: int, total: int) -> list:
    harmonic = sum(1.0 / r for r in range(1, missions + 1))
    sizes = [max(1, round(total / (r * harmonic)))
             for r in range(1, missions + 1)]
    sizes[0] += total - sum(sizes)
    return sizes


def make_round(rng, tee, missions: int, total: int):
    """(wire proofs, owed sets) as ``judge_round`` hands them over: the
    service missions first, then as many idle ones under the zero proof."""
    from cess_tpu import codec
    from cess_tpu.node.offchain import Proof
    from cess_tpu.ops import pfield as pf

    sectors, limbs = tee.key.alpha.shape

    def wire(mu, sigma) -> bytes:
        return codec.encode(Proof(mu=mu.astype(np.uint32),
                                  sigma=sigma.astype(np.uint32)))
    raw = rng.bytes(32 * total)
    owed, at = [], 0
    for size in zipf_sizes(missions, total):
        owed.append(tuple(raw[32 * f:32 * f + 32]
                          for f in range(at, at + size)))
        at += size
    proofs = [wire(rng.integers(0, pf.P, sectors),
                   rng.integers(0, pf.P, limbs)) for _ in range(missions)]
    zero = wire(np.zeros(sectors), np.zeros(limbs))
    return proofs + [zero] * missions, owed + [()] * missions


def judge(tee, form: str, proofs, owed, seed: bytes) -> list:
    """One round under ``form``: the device's verdicts of the missions
    with an owed set."""
    from cess_tpu.obs import trace
    from cess_tpu.ops import podr2
    from cess_tpu.serve import LateProofs

    if form == "signal":
        return [v for v, hs in zip(tee.verify_round(proofs, owed, seed),
                                   owed) if len(hs)]
    live = [i for i, hs in enumerate(owed) if len(hs)]

    def decoded():
        with trace.stage("tee.round.decode"):
            return tee._stacked(tee._decode_round(proofs), live)

    class OnTake(LateProofs):       # the executor's thread decodes
        def here(self):
            return False

        def take(self, deadline, mu_shape, sigma_shape):
            return self.shaped(*decoded(), mu_shape, sigma_shape)

    with trace.stage("tee.round"):  # the stages' names as the TEE's own
        if form == "in_hand":
            early = decoded()
        with trace.stage("tee.round.ids"):
            sizes = [len(owed[i]) for i in live]
            ids = np.concatenate([podr2.fragment_ids_from_hashes(owed[i])
                                  for i in live])
            words = podr2.aggregate_words(seed)
        with trace.stage("tee.round.challenge"):
            idx, nu = (np.asarray(a)
                       for a in podr2.gen_challenge(seed, tee.blocks))
        args = (ids, sizes, tee.blocks, idx, nu, words)
        with trace.stage("tee.round.submit"):
            if form == "in_hand":
                fut = tee.engine.submit_verify_round(*args, *early)
            else:
                late = OnTake() if form == "executor" else LateProofs()
                fut = tee.engine.submit_verify_round(*args, proofs=late)
        if form == "racing":
            late.put(*decoded())
        with trace.stage("tee.round.gather"):
            return [bool(v) for v in fut.result()]


def stage_ms(a: dict, b: dict, rounds: int) -> dict:
    out = {s: 1e3 * (b["stages"][s]["s"] - a["stages"][s]["s"]) / rounds
           for s in STAGES}
    out["proofs"] = 1e3 * (b["late"]["proofs"]["s"]
                           - a["late"]["proofs"]["s"]) / rounds
    return {k: round(v, 3) for k, v in out.items()}


def stages_ms(tee, form: str, proofs, owed, rounds: int) -> dict:
    """Mean ms a round of the program's own stages under ``form``, from
    an armed tracer's spans."""
    from cess_tpu import obs

    tracer = obs.Tracer(capacity=1 << 16)
    with obs.armed(tracer):
        for r in range(rounds):
            judge(tee, form, proofs, owed, b"round-probe-traced:%d" % r)
        tee.engine.flush()
    total: dict = {}
    for span in tracer.finished():
        if span["name"].startswith(("tee.round", "engine.verify",
                                    "podr2.challenge")):
            total[span["name"]] = total.get(span["name"], 0.0) \
                + span["dur_s"]
    return {name: round(1e3 * s / rounds, 3)
            for name, s in sorted(total.items())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--missions", type=int, default=500)
    ap.add_argument("--total", type=int, default=100_000)
    ap.add_argument("--blocks", type=int, default=16384)
    ap.add_argument("--rounds", type=int, default=60,
                    help="rounds of each form, after two that warm up")
    ap.add_argument("--seed", type=int, default=56)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        args.missions, args.total, args.blocks, args.rounds = 12, 240, 64, 3

    import jax

    from cess_tpu import jaxcache
    from cess_tpu.node.offchain import TeeAgent
    from cess_tpu.ops import podr2
    from cess_tpu.serve import make_engine

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not (on_chip or args.rehearse):
        print("round_probe: no chip found (--rehearse runs tiny sizes "
              "anywhere)", file=sys.stderr)
        return 3
    if on_chip:
        jaxcache.enable()
    key = podr2.Podr2Key.generate(args.seed)
    eng = make_engine(2, 1, rs_backend="tpu" if on_chip else "jax",
                      podr2_key=key,
                      audit_backend="tpu" if on_chip else "cpu")
    try:
        tee = object.__new__(TeeAgent)
        tee.key, tee.blocks, tee.engine = key, args.blocks, eng
        tee.controller, tee.bls_sk, tee._submitted = "tee0", None, set()
        tee.warm_verify(args.missions)
        rng = np.random.default_rng(args.seed)
        proofs, owed = make_round(rng, tee, args.missions, args.total)
        wall = {form: [] for form in FORMS}
        stages = {form: None for form in FORMS}
        said = {}
        for r in range(-2, args.rounds):
            seed = b"round-probe:%d" % r
            for form in FORMS[r % 4:] + FORMS[:r % 4]:   # no form always first
                eng.flush()
                a = eng.stats_snapshot()["classes"]["verify"]
                t0 = time.perf_counter()
                verdicts = judge(tee, form, proofs, owed, seed)
                ms = 1e3 * (time.perf_counter() - t0)
                eng.flush()
                b = eng.stats_snapshot()["classes"]["verify"]
                if said.setdefault(r, verdicts) != verdicts:
                    print(f"round_probe: {form} disagrees in round {r}",
                          file=sys.stderr)
                    return 1
                if r >= 0:
                    wall[form].append(ms)
                    d = stage_ms(a, b, 1)
                    acc = stages[form] or dict.fromkeys(d, 0.0)
                    stages[form] = {k: acc[k] + d[k] for k in d}
        for form in FORMS:
            q1, med, q3 = statistics.quantiles(wall[form], n=4)
            print(json.dumps({
                "form": form, "rounds": len(wall[form]),
                "round_ms": {"median": round(med, 3), "q1": round(q1, 3),
                             "q3": round(q3, 3),
                             "min": round(min(wall[form]), 3)},
                "batch_ms": {k: round(v / args.rounds, 3)
                             for k, v in stages[form].items()},
                "missions": args.missions, "owed": args.total,
                "on": f"{dev.platform}/{dev.device_kind}"}))
        for form in ("in_hand", "signal"):
            print(json.dumps({
                "stages_of": form, "ms_a_round": stages_ms(
                    tee, form, proofs, owed, max(args.rounds // 3, 1)),
                "on": f"{dev.platform}/{dev.device_kind}"}))
    finally:
        eng.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
