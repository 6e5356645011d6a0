#!/usr/bin/env python3
"""Chip smoke: the storage lifecycle on one TPU, at protocol widths.

    python chip_smoke.py [--seed N]       one chip: phases A, B, C, D
    python chip_smoke.py --chips 4        four chips: pool lanes + (2, 2) mesh only
    python chip_smoke.py --rehearse       tiny sizes, any backend; proves control
                                          flow only and never prints the chip's
                                          last line

Phase A  RS(4,8) codec + PoDR2 audit through the submission engine, checked
         against rs_ref.ReferenceCodec and the jnp tag path on the CPU device;
         a deal's burst of eight host repairs with four rows lost, by hash.
Phase B  the lifecycle at CESS's own geometry, RS(2,1) with 16 MiB segments:
         validators, gateway, miners, TEE; upload -> audit -> repair.
Phase C  streamed ingest through the fused encode+tag program, at RS(4,8)
         and at the archival tier's RS(10,4) (80 MiB segments, the
         benchmark's batch of 8).
Phase D  the regenerating repair plane at RS(2,1), 8 MiB fragments: one fold
         and one three-hop chain through a regen engine, every hop against
         the host twin and the chain's end against the oracle.

One process, no platform set here, resilience off: a device failure fails the
run instead of degrading to the CPU reference. Without --rehearse the script
refuses to run unless JAX reports a TPU, and on its path a Pallas kernel in
interpret mode is an error. Each phase prints one JSON line; the last line of
a passing run is {"ok": true, "device": {...}} and nothing else.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics
import sys
import time


class SmokeFailure(AssertionError):
    """A comparison differed."""


def check(what: str, ok) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds spent in compile-or-load-from-cache, and what the
    persistent cache did, from JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_writes = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1     # compiled here and written


class Run:
    """What every phase needs: sizes, backends, the report line."""

    def __init__(self, args):
        import jax

        from cess_tpu import constants

        self.seed = args.seed
        self.rehearse = args.rehearse
        # widths are the protocol's; the rehearsal alone shrinks them
        self.segment = 64 * 1024 if args.rehearse \
            else constants.SEGMENT_SIZE
        on_chip = jax.devices()[0].platform == "tpu"
        self.rs_backend = "tpu" if on_chip else "jax"
        self.audit_backend = "tpu" if on_chip else "cpu"
        self.clock = CompileClock()

    def engine(self, k, m, key, **kw):
        from cess_tpu.serve import make_engine

        return make_engine(k, m, rs_backend=self.rs_backend, podr2_key=key,
                           audit_backend=self.audit_backend, **kw)

    def require_kernels(self, encode, key, data_shape, frag_shape) -> dict:
        """Whether the lowered programs of ``encode`` and of the tag
        step hold the Mosaic kernels. On the chip both must: the silent
        jnp/interpret fallbacks are errors here."""
        import jax
        import jax.numpy as jnp

        from cess_tpu.ops import podr2, podr2_pallas, target

        enc = jax.jit(encode).lower(
            jax.ShapeDtypeStruct(data_shape, jnp.uint8)).as_text()
        tag = jax.jit(lambda i, f: podr2.tag_fragments(key, i, f)).lower(
            jax.ShapeDtypeStruct((frag_shape[0], 2), jnp.uint32),
            jax.ShapeDtypeStruct(frag_shape, jnp.uint8)).as_text()
        out = {"encode": "tpu_custom_call" in enc,
               "tag": "tpu_custom_call" in tag}
        if not self.rehearse:
            check("pallas kernels would be interpreted",
                  not target.interpret())
            check("fused tag kernel refused the protocol shape",
                  podr2_pallas.supported(
                      key.alpha.shape[0],
                      frag_shape[1] // (2 * key.alpha.shape[0])))
            check(f"no tpu_custom_call in lowered programs: {out}",
                  all(out.values()))
        return out

    @contextlib.contextmanager
    def phase(self, name: str):
        """Times the phase and prints its JSON line on success; an
        exception passes through untouched and fails the run."""
        line = {"phase": name}
        t0, c0 = time.perf_counter(), self.clock.seconds
        yield line
        line["seconds"] = round(time.perf_counter() - t0, 3)
        line["compile_s"] = round(self.clock.seconds - c0, 3)
        print(json.dumps(line, default=str), flush=True)


def engine_totals(eng) -> dict:
    """stats_snapshot() totals; failed/fallback/degraded must be 0
    (resilience is off, so nothing CAN fall back: it would fail)."""
    snap = eng.stats_snapshot()
    check("resilience must be off", "resilience" not in snap)
    keys = ("submitted", "completed", "failed", "timeouts", "saturated",
            "shed")
    tot = {k: sum(c[k] for c in snap["classes"].values()) for k in keys}
    tot.update(fallback=0, degraded=0,
               programs_built=snap["programs_built"])
    check(f"engine counted failures: {tot}",
          tot["failed"] == tot["timeouts"] == tot["saturated"]
          == tot["shed"] == 0 and tot["submitted"] == tot["completed"])
    return tot


def devices_of(*arrays) -> list[str]:
    return sorted({str(d) for a in arrays for d in a.devices()})


# ---------------------------------------------------------------------------
# Phase A — codec and audit through the engine, RS(4,8)
# ---------------------------------------------------------------------------
def phase_a(run: Run) -> None:
    import jax
    import numpy as np

    from cess_tpu.crypto.hashing import fragment_hash
    from cess_tpu.ops import podr2
    from cess_tpu.ops.rs_ref import ReferenceCodec

    k, m, segs = 4, 8, 8
    rows, n = k + m, run.segment // k
    key = podr2.Podr2Key.generate(run.seed)
    rng = np.random.default_rng(run.seed)
    data = rng.integers(0, 256, (segs, k, n), dtype=np.uint8)
    with run.phase("A") as line:
        eng = run.engine(k, m, key)
        try:
            want = ReferenceCodec(k, m).encode(data)
            frags = eng.encode(jax.device_put(data))
            got = np.asarray(frags)
            check("A: encode differs from ReferenceCodec",
                  np.array_equal(got, want))
            # lose 4 fragments of every segment, data rows among them
            missing, present = (0, 2, 5, 9), (1, 3, 4, 6)
            rec = eng.reconstruct(
                jax.device_put(np.ascontiguousarray(got[:, present])),
                present, missing)
            check("A: reconstruct differs from ReferenceCodec",
                  np.array_equal(np.asarray(rec), want[:, missing]))
            line["burst"] = _burst_repair(eng, want)

            flat = got.reshape(segs * rows, n)
            ids = np.stack([podr2.fragment_id_from_hash(
                fragment_hash(f.tobytes())) for f in flat])
            tags = eng.tag_fragments(ids, jax.device_put(flat))
            tags_np = np.asarray(tags)
            # reference: the jnp tag path (no Pallas) on the CPU device
            with jax.default_device(jax.devices("cpu")[0]):
                ref_tags = jax.jit(jax.vmap(
                    lambda i, d: podr2.tag_fragment(key, i, d)))(
                        ids[:rows], flat[:rows])
            check("A: tags differ from the jnp path on the CPU device",
                  np.array_equal(tags_np[:rows], np.asarray(ref_tags)))

            blocks = n // podr2.BLOCK_BYTES
            round_seed = b"chip-smoke-round:%d" % run.seed
            idx, nu = (np.asarray(a) for a in
                       podr2.gen_challenge(round_seed, blocks))
            r = np.asarray(podr2.aggregate_coeffs(round_seed, ids))
            mu, sigma = eng.prove_aggregate(flat, tags_np, idx, nu, r)
            check("A: honest proof rejected", eng.verify_aggregate(
                ids, blocks, idx, nu, r, mu, sigma) is True)
            # one flipped byte in a challenged block: must be rejected
            bad = flat.copy()
            bad[5, int(idx[0]) * podr2.BLOCK_BYTES + 3] ^= 0x40
            mu2, sigma2 = eng.prove_aggregate(bad, tags_np, idx, nu, r)
            check("A: proof over corrupted data accepted",
                  eng.verify_aggregate(ids, blocks, idx, nu, r, mu2,
                                       sigma2) is False)

            line.update(
                bytes_in=int(data.nbytes),
                devices=devices_of(frags, rec, tags),
                audit_device=str(eng.audit.device),
                engine=engine_totals(eng),
                tpu_custom_call=run.require_kernels(
                    eng.codec.encode, key, data.shape, flat.shape),
                sync=sync_probe(eng.codec.encode, jax.device_put(data)))
        finally:
            eng.close()


def _burst_repair(eng, coded) -> dict:
    """BASELINE's 4-erasure repair as a rebuilder's burst (PR 52): the
    eight segments of ``coded`` [8, 12, n] have lost the same four rows,
    data rows among them; each is one host request of its four lowest
    survivors' rows as they lie, all submitted in a row through the
    engine's repair class after ``warm_repair`` of the shape at buckets
    1 to 8; every rebuilt row is hashed against the original's, and
    every result was handed over as a view of its fetched piece (no
    byte regrouped on the host, PR 53)."""
    from cess_tpu.crypto.hashing import fragment_hash

    lost = (0, 3, 6, 10)
    helpers = tuple(j for j in range(coded.shape[1]) if j not in lost)[:4]
    n = coded.shape[2]
    eng.warm_repair([(helpers, lost)], n, buckets=(1, 2, 4, 8))
    before = eng.stats_snapshot()
    futs = [eng.submit_reconstruct([seg[j] for j in helpers], helpers, lost)
            for seg in coded]
    for seg, fut in zip(coded, futs):
        rec = fut.result()
        for i, row in enumerate(lost):
            check(f"A: burst: row {row} hashes to another value",
                  fragment_hash(rec[i].tobytes())
                  == fragment_hash(seg[row].tobytes()))
    eng.flush()
    after = eng.stats_snapshot()
    a, b = (s["classes"]["repair"] for s in (before, after))
    check("A: burst: a warmed shape built a program",
          after["programs_built"] == before["programs_built"])
    out = {key: b[key] - a[key] for key in (
        "batches", "batched_requests", "padded_rows", "linear_puts",
        "result_bytes", "regrouped_bytes")}
    check(f"A: burst: counters {out}",
          out["batched_requests"] == len(futs)
          and out["linear_puts"] == out["batches"]
          and out["result_bytes"] == len(futs) * len(lost) * n
          and out["regrouped_bytes"] == 0)
    return out


def sync_probe(fn, x, reps: int = 7) -> dict:
    """One dispatch timed both ways: ended by block_until_ready, and by
    fetching a scalar that depends on the result (a parity byte). Where
    block_until_ready synchronises, the two agree."""
    import jax

    def fetched():
        return int(fn(x)[-1, -1, -1])

    def blocked():
        jax.block_until_ready(fn(x))

    out = {}
    for name, call in (("fetched_scalar_s", fetched),
                       ("block_until_ready_s", blocked)):
        call()                                  # warm: compiles the fetch
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            call()
            times.append(time.perf_counter() - t0)
        out[name] = statistics.median(times)
    return out


# ---------------------------------------------------------------------------
# Phase B — the lifecycle at CESS's own geometry, RS(2,1)
# ---------------------------------------------------------------------------
def phase_b(run: Run) -> None:
    import jax
    import numpy as np

    from cess_tpu import constants
    from cess_tpu.chain.attestation import issue_cert, issue_report
    from cess_tpu.crypto import bls12381
    from cess_tpu.crypto.hashing import fragment_hash
    from cess_tpu.crypto.rsa import generate_rsa_keypair
    from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
    from cess_tpu.node.chain_spec import ChainSpec, ValidatorGenesis
    from cess_tpu.node.network import Network, Node
    from cess_tpu.node.offchain import (MinerAgent, OssGateway, TeeAgent,
                                        ValidatorOcw)
    from cess_tpu.ops import podr2

    D = constants.DOLLARS
    k, m = constants.REF_K, constants.REF_M            # RS(2,1)
    n_validators, file_segments = 3, 4
    miner_names = ("m1", "m2", "m3", "m4")
    # buy_space(1) = 1 GiB needs >= 128 fillers of idle space, counted
    # in protocol units (8 MiB each) whatever the payload size
    fillers_per_miner = 32
    cuts = [f"validators={n_validators}", f"miners={len(miner_names)}",
            f"fillers_per_miner={fillers_per_miner} (the least that "
            "lets buy_space(1) through)",
            f"file={file_segments} segments", "one file, one repair"]

    cfg = PipelineConfig(k=k, m=m, segment_size=run.segment)
    key = podr2.Podr2Key.generate(run.seed + 1)
    rng = np.random.default_rng(run.seed + 1)
    with run.phase("B") as line:
        eng = run.engine(k, m, key)
        try:
            spec = ChainSpec(
                name="smoke", chain_id="chip-smoke",
                endowed=(("alice", 1_000_000_000 * D), ("gw", 1_000_000 * D),
                         ("stash1", 10_000_000 * D), ("tee1", 1_000 * D),
                         *((w, 10_000 * D) for w in miner_names)),
                validators=tuple(ValidatorGenesis(f"v{i}", 4_000_000 * D)
                                 for i in range(n_validators)),
                era_blocks=40, epoch_blocks=10, audit_challenge_life=6,
                audit_verify_life=8, sudo="alice")
            nodes = [Node(spec, f"node{i}",
                          {f"v{i}": spec.session_key(f"v{i}")})
                     for i in range(n_validators)]
            net, node = Network(nodes), nodes[0]
            rt = node.runtime
            pipe = StoragePipeline(cfg, podr2_key=key, engine=eng)

            # TEE attestation chain + BLS-sealed verdicts
            kp = generate_rsa_keypair(1024, seed=5)
            signer_kp = generate_rsa_keypair(1024, seed=6)
            mr = b"\x02" * 32
            for nd in nodes:
                nd.runtime.apply_extrinsic(
                    "root", "tee_worker.update_whitelist", mr)
                nd.runtime.apply_extrinsic(
                    "root", "tee_worker.pin_ias_signer", kp.public)
            cert = issue_cert(kp, "ias-signer", signer_kp.public)
            bls_sk, bls_pk = bls12381.keygen(b"smoke-tee-master")
            report, rsig = issue_report(signer_kp, mr, b"tee-pk", "tee1",
                                        bls_pk=bls_pk)
            node.submit_extrinsic(
                "tee1", "tee_worker.register", "stash1", b"tp", b"tee-pk",
                report, rsig, (cert,), bls_pk,
                bls12381.prove_possession(bls_sk, bls_pk))
            for w in miner_names:
                node.submit_extrinsic(w, "sminer.regnstk", w,
                                      b"p" + w.encode(), 2000 * D)
            net.run_slots(2)

            gw = OssGateway(node, "gw", pipe)
            miners = [MinerAgent(node, w, [gw], pipe, engine=eng)
                      for w in miner_names]
            tee = TeeAgent(node, "tee1", key, cfg.blocks_per_fragment,
                           bls_seed=b"smoke-tee-master", engine=eng)
            t0 = time.perf_counter()
            for mn in miners:
                mn.setup_fillers(tee, fillers_per_miner)
            line["fillers_s"] = round(time.perf_counter() - t0, 3)
            net.run_slots(2)
            node.submit_extrinsic("alice", "storage_handler.buy_space", 1)
            node.submit_extrinsic("alice", "oss.authorize", "gw")
            net.run_slots(2)
            node.submit_extrinsic("gw", "file_bank.create_bucket", "alice",
                                  "smoke")
            net.run_slots(2)
            node.offchain_agents.extend(
                [*miners, tee,
                 ValidatorOcw("v0", spec.session_key("v0")),
                 ValidatorOcw("v1", spec.session_key("v1"))])
            for nd in nodes:
                nd.runtime.fund("sminer_reward_pool", 10_000 * D)

            # -- upload ----------------------------------------------------
            data = rng.integers(0, 256, file_segments * run.segment,
                                dtype=np.uint8)
            t0 = time.perf_counter()
            fh = gw.upload("alice", "smoke", "smoke.bin", data.tobytes())
            line["upload_s"] = round(time.perf_counter() - t0, 3)
            net.run_slots(1)
            check("B: no deal after the declaration",
                  rt.file_bank.deal(fh) is not None)
            net.run_slots(2)                    # miners fetch and report
            f = rt.file_bank.file(fh)
            check("B: file not in 'calculate' after transfer reports",
                  f is not None and f.state == "calculate")
            node.submit_extrinsic("root", "file_bank.calculate_end", fh)
            net.run_slots(1)
            f = rt.file_bank.file(fh)
            check("B: file not active", f.state == "active")
            for seg in f.segments:
                for row, h in enumerate(seg.fragment_hashes):
                    holder = next(x for x in miners
                                  if x.account == f.miners[row])
                    check("B: a holder's bytes do not hash to the chain's "
                          "fragment hash", fragment_hash(holder.store[h]) == h)
            # the stored fragments are what a fresh engine encode gives,
            # and that output lives on the device
            segs = data.reshape(file_segments, k, cfg.fragment_size)
            probe = eng.encode(jax.device_put(segs))
            probe_np = np.asarray(probe)
            for i, seg in enumerate(f.segments):
                for row, h in enumerate(seg.fragment_hashes):
                    check("B: gateway fragment differs from engine encode",
                          gw.fragment_store[h] == probe_np[i, row].tobytes())

            # -- audit -----------------------------------------------------
            # Rounds run from the moment the agents are attached, so
            # count only the verdicts of a round whose frozen snapshot
            # owes every holder its row of every segment of the file
            # (rounds are sequential: a new one starts only after the
            # last one's verdicts are in).
            t0 = time.perf_counter()

            def covers_file(ch) -> bool:
                owed = {s.miner: s for s in ch.miners}
                return set(miner_names) <= set(owed) and all(
                    seg.fragment_hashes[row]
                    in owed[f.miners[row]].service_frags
                    for seg in f.segments for row in range(k + m))

            passed: set[str] = set()
            seen_before = None
            for _ in range(200):
                net.run_slots(1)
                verdicts = [dict(ev.data) for ev in
                            rt.state.events_of("audit", "VerifyResult")]
                for d in verdicts:
                    check(f"B: honest miner failed an audit: {d}",
                          d["idle"] and d["service"])
                ch = rt.audit.challenge()
                if seen_before is None:
                    if ch is not None and covers_file(ch):
                        seen_before = len(verdicts)
                    continue
                passed = {d["miner"] for d in verdicts[seen_before:]}
                if passed >= set(miner_names):
                    break
            check(f"B: verdicts over the file only for {sorted(passed)}",
                  passed >= set(miner_names))
            line["audit_s"] = round(time.perf_counter() - t0, 3)

            # -- repair ----------------------------------------------------
            t0 = time.perf_counter()
            victim = next(x for x in miners if x.account == f.miners[0])
            frag = f.segments[0].fragment_hashes[0]
            del victim.store[frag]
            del victim.tags[frag]
            node.submit_extrinsic(victim.account,
                                  "file_bank.generate_restoral_order", fh,
                                  frag)
            net.run_slots(1)
            check("B: no restoral order",
                  rt.file_bank.restoral_order(frag) is not None)
            rescuer = next(x for x in miners if x.account not in f.miners)
            check("B: try_repair failed",
                  rescuer.try_repair(frag, miners, [gw]))
            net.run_slots(1)
            check("B: restoral order still open",
                  rt.file_bank.restoral_order(frag) is None)
            check("B: repaired bytes do not hash to the chain's hash",
                  fragment_hash(rescuer.store[frag]) == frag)
            check("B: replicas diverged", all(
                nd.runtime.state.state_root() == rt.state.state_root()
                for nd in nodes))
            line["repair_s"] = round(time.perf_counter() - t0, 3)

            flat_shape = (file_segments * (k + m), cfg.fragment_size)
            line.update(
                bytes_in=int(data.nbytes), blocks=int(rt.state.block),
                devices=devices_of(probe),
                audit_device=str(eng.audit.device),
                engine=engine_totals(eng),
                tpu_custom_call=run.require_kernels(
                    eng.codec.encode, key, segs.shape, flat_shape),
                cuts=cuts)
        finally:
            eng.close()


# ---------------------------------------------------------------------------
# Phase C — streamed ingest through the fused program, RS(4,8)
# ---------------------------------------------------------------------------
def phase_c(run: Run) -> None:
    import numpy as np

    from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
    from cess_tpu.ops import podr2
    from cess_tpu.serve.stream import StreamingIngest

    k, m, total, batch = 4, 8, 20, 8           # ragged tail of 4
    cfg = PipelineConfig(k=k, m=m, segment_size=run.segment)
    key = podr2.Podr2Key.generate(run.seed + 2)
    segs = np.random.default_rng(run.seed + 2).integers(
        0, 256, (total, run.segment), dtype=np.uint8)
    with run.phase("C") as line:
        pipe = StoragePipeline(cfg, podr2_key=key)
        ing = StreamingIngest(pipe, batch=batch)
        out = ing.ingest(segs)
        shards = pipe.encode_step(segs)
        tags = pipe.tag_step(shards)
        check("C: streamed fragments differ from encode_step",
              np.array_equal(np.asarray(out["fragments"]),
                             np.asarray(shards)))
        check("C: streamed tags differ from encode_step -> tag_step",
              np.array_equal(np.asarray(out["tags"]), np.asarray(tags)))
        check("C: systematic rows are not the segment bytes",
              np.array_equal(np.asarray(out["fragments"][:, :k]),
                             segs.reshape(total, k, cfg.fragment_size)))
        # on the chip every batch's rows go to the RS kernel unstacked
        # (PR 51); the CPU's lowering stacks them
        check("C: direct_rows is not what the program says of its batches",
              ing.stats.direct_rows == ing.stats.batches
              * pipe.rows_direct(batch, cfg.fragment_size))
        line.update(
            bytes_in=int(segs.nbytes),
            devices=devices_of(out["fragments"], out["tags"]),
            stream={kk: ing.stats.snapshot()[kk] for kk in
                    ("batches", "segments", "padded_segments",
                     "direct_rows")},
            tpu_custom_call=run.require_kernels(
                pipe._parity, key, (batch, k, cfg.fragment_size),
                (batch * (k + m), cfg.fragment_size)),
            wide=_stream_wide(run))


def _stream_wide(run: Run) -> dict:
    """Phase C's second geometry: the archival tier's RS(10,4) at
    8 MiB fragments (80 MiB segments), through the same driver and
    program at the benchmark's own batch of 8 (``stream-10p4.corpus``),
    a ragged tail of 1. The batch is not shrunk: the fused program over
    a batch that is no multiple of 8 compiles for minutes on this
    compiler (369 s at batch 2, PERF.md section 7), and the shape to
    meet before the benchmark does is the benchmark's. Checked against
    the host: every systematic row, the tail segment's fourteen
    fragments by ``ReferenceCodec``, a data and a parity fragment's
    tags by the jnp path on the CPU device."""
    import jax
    import numpy as np

    from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
    from cess_tpu.ops import podr2
    from cess_tpu.ops.rs_ref import ReferenceCodec
    from cess_tpu.serve.stream import StreamingIngest

    k, m, total, batch = 10, 4, 9, 8
    rows, n = k + m, run.segment // 2    # the protocol's 8 MiB fragment
    cfg = PipelineConfig(k=k, m=m, segment_size=k * n)
    key = podr2.Podr2Key.generate(run.seed + 3)
    segs = np.random.default_rng(run.seed + 3).integers(
        0, 256, (total, k * n), dtype=np.uint8)
    pipe = StoragePipeline(cfg, podr2_key=key)
    ing = StreamingIngest(pipe, batch=batch)
    done = 0
    for out in ing.run(segs):            # a batch at a time: 896 MiB each
        frags = np.asarray(out["fragments"])
        check("C wide: systematic rows are not the segment bytes",
              np.array_equal(frags[:, :k].reshape(out["rows"], -1),
                             segs[done:done + out["rows"]]))
        done += out["rows"]
    check("C wide: the stream lost segments", done == total)
    last, tags = total - 1, np.asarray(out["tags"])     # the tail's
    check("C wide: fragments differ from ReferenceCodec",
          np.array_equal(frags[0], ReferenceCodec(k, m).encode(
              segs[last].reshape(k, n))))
    picked = (0, rows - 1)               # a data row, a parity row
    ids = np.array([last * rows + r for r in picked], np.int32)
    with jax.default_device(jax.devices("cpu")[0]):
        ref_tags = jax.jit(jax.vmap(
            lambda i, d: podr2.tag_fragment(key, i, d)))(
                ids, frags[0, picked])
    check("C wide: tags differ from the jnp path on the CPU device",
          np.array_equal(tags[0, picked], np.asarray(ref_tags)))
    snap = ing.stats.snapshot()
    check("C wide: stored bytes per user byte is not (k + m) / k",
          (snap["bytes_out"] - total * tags.nbytes) * k
          == snap["bytes_in"] * rows)
    check("C wide: direct_rows is not what the program says of its batches",
          snap["direct_rows"] == snap["batches"] * pipe.rows_direct(batch, n))
    return {"k": k, "m": m, "bytes_in": int(segs.nbytes),
            "devices": devices_of(out["fragments"], out["tags"]),
            "stream": {kk: snap[kk] for kk in
                       ("batches", "segments", "padded_segments",
                        "bytes_out", "direct_rows")}}


# ---------------------------------------------------------------------------
# Phase D — the regenerating repair plane (ops/regen.py), RS(2,1)
# ---------------------------------------------------------------------------
def phase_d(run: Run) -> None:
    """The first evidence that the plane runs on the chip, without the
    benchmark: a regen engine's fold against the host twin, and a chain
    of three hops, each aggregate a host array between them."""
    import numpy as np

    from cess_tpu.ops import regen
    from cess_tpu.ops.rs_ref import ReferenceCodec
    from cess_tpu.serve import make_engine

    k, m = 2, 1
    n = run.segment // k
    rng = np.random.default_rng(run.seed + 3)
    coded = ReferenceCodec(k, m).encode(
        rng.integers(0, 256, (k, n), dtype=np.uint8))
    with run.phase("D") as line:
        eng = make_engine(k, m, rs_backend="regen")
        try:
            eng.warm_repair([((1, 2), (0,)), ((0, 2), (1,)),
                             ((0, 1), (2,))], n, buckets=(1,))
            programs = run.clock.programs
            acc = rng.integers(0, 256, n, dtype=np.uint8)
            out = eng.repair_symbol([acc, coded[1]], 0x8E)[0]
            check("D: the device fold differs from the host twin",
                  np.array_equal(out, regen.fold_symbol_host(
                      acc, coded[1], 0x8E)))
            # row 0 from rows 1 and 2, then a third hop that folds row
            # 0's own coefficient-1 copy back in: the aggregate is zero
            chain = list(zip((1, 2), regen.repair_coeffs(k, m, (1, 2),
                                                         (0,)))) + [(0, 1)]
            hops, acc = [], None
            for j, coeff in chain:
                first = np.zeros(n, np.uint8) if acc is None else acc
                acc = eng.repair_symbol([first, coded[j]], coeff)[0]
                check("D: an aggregate is not a host array",
                      type(acc) is np.ndarray)
                hops.append(acc)
                check(f"D: hop {len(hops)} differs from the host twin",
                      np.array_equal(acc, regen.fold_symbol_host(
                          first, coded[j], coeff)))
            check("D: two hops do not rebuild the lost row",
                  np.array_equal(hops[1], coded[0]))
            check("D: the third hop does not cancel it", not hops[2].any())
            check("D: a warmed fold compiled",
                  run.clock.programs == programs)
            eng.flush()
            repair = eng.stats_snapshot()["classes"]["repair"]
            check("D: folds not counted", repair["symbol_folds"] == 4)
            line.update(bytes_a_hop=int(2 * n), hops=len(hops),
                        strategy=eng.codec.strategy,
                        engine=engine_totals(eng),
                        repair={kk: repair[kk] for kk in
                                ("batches", "symbol_folds", "linear_puts",
                                 "linear_fetches", "patterns_new")})
        finally:
            eng.close()


# ---------------------------------------------------------------------------
# --chips 4: the two paths that exist only across chips
# ---------------------------------------------------------------------------
def phase_pool(run: Run) -> None:
    """DevicePool lanes under concurrent encode and tag submits,
    compared with the single-device engine of the same process."""
    import jax.numpy as jnp
    import numpy as np

    from cess_tpu.ops import podr2
    from cess_tpu.serve.policy import AdmissionPolicy

    k, m, reqs, per = 4, 8, 8, 2
    n = run.segment // k
    key = podr2.Podr2Key.generate(run.seed + 3)
    rng = np.random.default_rng(run.seed + 3)
    enc_in = [rng.integers(0, 256, (per, k, n), dtype=np.uint8)
              for _ in range(reqs)]
    tag_in = [rng.integers(0, 256, (per * k, n), dtype=np.uint8)
              for _ in range(reqs)]
    tag_ids = [rng.integers(0, 2 ** 32, (per * k, 2), dtype=np.uint32)
               for _ in range(reqs)]
    with run.phase("pool") as line:
        # one request per batch, so that placement sees 16 batches
        policy = AdmissionPolicy(max_batch_requests=1)
        solo = run.engine(k, m, key, policy=policy)
        pooled = run.engine(k, m, key, policy=policy, pool=4)
        try:
            # uncommitted device arrays: the lane's default-device
            # scope decides where each batch runs
            futs = [pooled.submit_encode(jnp.asarray(x)) for x in enc_in]
            futs += [pooled.submit_tag(i, jnp.asarray(f))
                     for i, f in zip(tag_ids, tag_in)]
            outs = [f.result(timeout=600) for f in futs]
            want = [solo.encode(x) for x in enc_in]
            want += [solo.tag_fragments(i, f)
                     for i, f in zip(tag_ids, tag_in)]
            for got, ref in zip(outs, want):
                check("pool: result differs from the single-device engine",
                      np.array_equal(np.asarray(got), np.asarray(ref)))
            snap = pooled.pool.snapshot()
            lanes = [(ln["device"], ln["batches"]) for ln in snap["lanes"]]
            check(f"pool: a lane served nothing: {lanes}",
                  len(lanes) == 4 and all(b > 0 for _, b in lanes))
            devs = devices_of(*outs)
            check(f"pool: outputs lived on {devs}", len(devs) == 4)
            line.update(
                bytes_in=int(sum(x.nbytes for x in enc_in + tag_in)),
                devices=devs, lanes=lanes,
                engine=engine_totals(pooled),
                tpu_custom_call=run.require_kernels(
                    pooled.codec.encode, key, enc_in[0].shape,
                    tag_in[0].shape))
        finally:
            pooled.close()
            solo.close()


def phase_mesh(run: Run) -> None:
    """sharded_pipeline_step on a (seg=2, byte=2) mesh at RS(2,1)
    protocol geometry, compared with the single-device fused forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cess_tpu import constants
    from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
    from cess_tpu.ops import podr2
    from cess_tpu.parallel.mesh import make_mesh, sharded_pipeline_step

    k, m, b = constants.REF_K, constants.REF_M, 2
    cfg = PipelineConfig(k=k, m=m, segment_size=run.segment)
    key = podr2.Podr2Key.generate(run.seed + 4)
    data = np.random.default_rng(run.seed + 4).integers(
        0, 256, (b, k, cfg.fragment_size), dtype=np.uint8)
    ids = np.arange(b * (k + m), dtype=np.int32).reshape(b, k + m)
    with run.phase("mesh") as line:
        pipe = StoragePipeline(cfg, podr2_key=key)
        mesh = make_mesh(jax.devices()[:4], seg=2, byte=2)
        step = sharded_pipeline_step(pipe, mesh)
        idx, nu = podr2.gen_challenge(b"chip-smoke-mesh:%d" % run.seed,
                                      cfg.blocks_per_fragment)
        shards, tags, ok = step(jnp.asarray(data), jnp.asarray(ids), idx, nu)
        check("mesh: audit verify failed", bool(np.asarray(ok).all()))
        check("mesh: systematic rows are not the data",
              np.array_equal(np.asarray(shards[:, :k]), data))
        ref = pipe.forward(data.reshape(b, run.segment),
                           fragment_ids=jnp.asarray(ids))
        check("mesh: fragments differ from the single-device forward",
              np.array_equal(np.asarray(shards),
                             np.asarray(ref["fragments"])))
        check("mesh: tags differ from the single-device forward",
              np.array_equal(np.asarray(tags), np.asarray(ref["tags"])))
        devs = devices_of(shards, tags)
        check(f"mesh: outputs lived on {devs}", len(devs) == 4)
        line.update(bytes_in=int(data.nbytes), devices=devs,
                    mesh=dict(mesh.shape))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="data and keys are made from it")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the pool and mesh paths only")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever JAX finds; never prints "
                         "the chip's last line")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    # the native libraries are built by make — a child that never
    # touches JAX — before anything loads them
    from cess_tpu import native

    for lib in ("libcessrs.so", "libcessbls.so"):
        native.ensure_built(lib)

    import jax

    from cess_tpu import jaxcache

    cache_dir = jaxcache.enable()
    cache_was = "warm" if os.path.isdir(cache_dir) \
        and os.listdir(cache_dir) else "cold"
    if args.rehearse and args.chips > 1:
        jax.config.update("jax_num_cpu_devices", args.chips)
    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: no chip found: JAX reports platform "
              f"{dev.platform!r}, not 'tpu'. Nothing was run "
              f"(--rehearse runs the control flow at tiny sizes).",
              file=sys.stderr)
        return 3
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX reports {len(jax.devices())}.",
              file=sys.stderr)
        return 3

    run = Run(args)
    phases = (phase_a, phase_b, phase_c, phase_d) if args.chips == 1 \
        else (phase_pool, phase_mesh)
    for phase in phases:
        phase(run)
    print(json.dumps({
        "phase": "total",
        "seconds": round(time.perf_counter() - t_start, 3),
        "compile_s": round(run.clock.seconds, 3),
        "programs": run.clock.programs,
        "cache_dir": cache_dir, "cache_was": cache_was,
        "cache_hits": run.clock.cache_hits,
        "cache_writes": run.clock.cache_writes}), flush=True)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "ran_on": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
