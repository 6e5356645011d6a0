"""Off-chain ecosystem agents: OSS gateway, storage miner, TEE, OCW.

The reference's L6 (SURVEY.md §1): OSS gateways chunk+encode files,
storage miners hold fragments and prove storage, TEE workers tag and
verify, validator offchain workers generate challenges — all external
repos interacting via extrinsics and events. Here they are in-process
agents around a Node, driving the TPU data plane
(cess_tpu.models.pipeline / cess_tpu.ops.podr2) for the heavy math:

- OssGateway.upload(): segments the file, RS-encodes + PoDR2-tags the
  whole batch on device, declares on chain, serves fragments. The
  systematic rows never come back from the device: they are hashed
  and stored from the user's bytes while the device encodes, and only
  parity is fetched.
- MinerAgent: fetches assigned fragments, reports transfer, computes
  aggregated (mu, sigma) proofs over its REAL stored bytes each
  challenge round (drop its ``store`` entries to simulate data loss),
  claims restoral orders and repairs via RS reconstruction.
- TeeAgent: holds the PoDR2 secret key, verifies queued proofs
  batch-wise on device, reports results.
- ValidatorOcw: the audit offchain worker (lib.rs:347-369): builds the
  deterministic challenge snapshot and submits the proposal.

Every agent's ``on_block`` runs after each imported block (Substrate
OCW semantics) and communicates ONLY via extrinsics + events + the
fragment transfer channel, like the reference's network boundary.

Device submission: each agent accepts an optional ``engine``
(cess_tpu/serve) — OssGateway encodes/tags through its pipeline's
engine, MinerAgent proves and RS-repairs through the prove/repair
queues, TeeAgent verifies through the (highest-priority) verify
queue. Results are bit-identical to the direct calls; None (the
default) keeps every path direct and synchronous. ValidatorOcw has no
device op on its path (challenge snapshots are chain-side host math),
so it takes no engine.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import hashlib
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import codec, constants
from ..obs import flight as _flight
from ..obs import trace
from ..resilience import faults
from ..chain.file_bank import UserBrief
from ..chain.state import DispatchError
from ..crypto import bls12381
from ..crypto.hashing import fragment_hash
from ..models.pipeline import PipelineConfig, StoragePipeline
from ..ops import pfield as pf
from ..ops import podr2
from .network import Node


# ceiling of a gateway's hash workers: an upload at RS(2,1) x 4
# segments is sixteen jobs, and a row's copy into ``bytes`` holds the
# GIL (only its SHA-256 runs beside the others), so a wider pool would
# mostly queue for it
_HASH_WORKERS_MAX = 8


@functools.partial(jax.jit, static_argnames="k")
def _parity_rows(frags, k: int):
    """The parity rows of an encode result ``[segments, k+m, n]`` as
    ``segments * m`` 1-D arrays, segment by segment: the only bytes of
    an upload that the device made, in the shape in which bytes leave
    it (a dense ``u8[n]``; serve/engine.py ``_linear_rows`` has the
    reason and the same index forms, never a ``reshape`` that moves
    bytes between dimensions). The data rows are the user's own bytes
    and stay where the host has them."""
    return tuple(frags[i, j] for i in range(frags.shape[0])
                 for j in range(k, frags.shape[1]))


def _worker_sink(sinks: dict) -> dict:
    """This worker thread's stage sink for the upload whose jobs share
    ``sinks`` (thread id -> sink): obs.trace.stage's sinks are unlocked,
    so every writer thread has its own; the upload merges them once its
    jobs are done."""
    return sinks.setdefault(threading.get_ident(), {})


def _hashed(data, sinks: dict, span) -> bytes:
    """A worker's job for one segment, and the second half of one for a
    fragment: the SHA-256 of ``data``, one ``gateway.worker.hash`` stage
    a job on the worker's thread (``span``: the upload's, handed across
    the thread boundary)."""
    with trace.stage("gateway.worker.hash", _worker_sink(sinks),
                     parent=span):
        return fragment_hash(data)


def _hashed_copy(row, sinks: dict, span) -> tuple[bytes, bytes]:
    """A worker's job for one fragment: the ``bytes`` that the store
    will hold, copied once from the row's memory (a view of the
    upload's input, or a fetched parity row; the ``gateway.worker.copy``
    stage, which holds the GIL), and their identity, hashed from that
    same copy (``gateway.worker.hash``, which does not)."""
    with trace.stage("gateway.worker.copy", _worker_sink(sinks),
                     parent=span):
        blob = bytes(row)
    return blob, _hashed(blob, sinks, span)


def _merge_sinks(stages: dict, *sinks: dict) -> None:
    """obs.trace.stage sinks (``name -> [count, seconds]``) added into
    an agent's totals; the caller holds the lock that guards them."""
    for sink in sinks:
        for name, (count, seconds) in sink.items():
            acc = stages.setdefault(name, [0, 0.0])
            acc[0] += count
            acc[1] += seconds


def _series(role: str, counters: dict) -> dict[str, float]:
    """An agent's ``counters()`` as ``cess_<role>_<name>_total`` series
    and, a stage, ``cess_<role>_stage_<name>_seconds`` / ``_count``
    (``<name>``: the stage's without its first part, dots as
    underscores)."""
    counts = counters.pop("stage_count")
    seconds = counters.pop("stage_seconds")
    out = {f"cess_{role}_{name}_total": float(value)
           for name, value in counters.items()}
    for stage, n in counts.items():
        name = stage.partition(".")[2].replace(".", "_")
        out[f"cess_{role}_stage_{name}_seconds"] = seconds[stage]
        out[f"cess_{role}_stage_{name}_count"] = float(n)
    return out


class OssGateway:
    """The user-facing gateway: chunk -> encode -> tag -> declare.

    The gateway is where the per-tenant accounting contract
    (obs/slo.py) STARTS: every engine submit an upload generates is
    tagged with the uploading OWNER's account, so the exposition's
    ``cess_tenant_*`` series and the batcher's weighted-fair dequeue
    see the user behind the bytes — not just the one shared gateway
    account. Free when the engine has no SLO board.

    The code is systematic, so of an upload's ``k+m`` rows a segment
    only the ``m`` parity rows are made on the device. The gateway
    treats the ``k`` data rows as what they are, host data: they and
    the segments are hashed (and the data rows copied into the store's
    ``bytes``) by the gateway's worker threads, from the memory the
    user handed in, while the device encodes; only parity comes back
    over the link, and each parity row is hashed as it lands.
    ``counters()`` says how many rows took which way, and how long each
    stage of the uploads took: every stage of an upload goes through
    obs.trace.stage with a sink, the upload thread's (``offchain.upload``,
    the seven ``gateway.*`` stages and ``gateway.encode``'s three
    children) and each worker's own (``gateway.worker.copy`` /
    ``gateway.worker.hash``, one a job), merged when the upload counts
    itself."""

    def __init__(self, node: Node, account: str,
                 pipeline: StoragePipeline):
        self.node = node
        self.account = account
        self.pipeline = pipeline
        self.fragment_store: dict[bytes, bytes] = {}   # hash -> bytes
        self.tag_store: dict[bytes, np.ndarray] = {}   # hash -> [blocks] u32
        # SHA-256 (hashlib drops the GIL) off the upload's own thread.
        # As wide as the machine, up to the ceiling; the executor
        # starts a thread only when a job finds none idle, so never
        # more than an upload has rows in flight
        self._hashers = concurrent.futures.ThreadPoolExecutor(
            max_workers=min(os.cpu_count() or 1, _HASH_WORKERS_MAX),
            thread_name_prefix=f"gateway-hash-{account}")
        self._mu = threading.Lock()
        self._counters = dict.fromkeys(
            ("uploads", "rows_from_host", "rows_fetched",
             "bytes_fetched", "hash_jobs"), 0)
        # stage name -> [count, seconds] over completed uploads
        self._stages: dict[str, list] = {}

    def close(self) -> None:
        """Stop the hash workers (idle ones also stop when the gateway
        is collected). An upload after this raises."""
        self._hashers.shutdown()

    def counters(self) -> dict:
        """Totals over this gateway's completed uploads: ``uploads``;
        ``rows_from_host`` (data rows hashed and stored from the
        input, never fetched) and ``rows_fetched`` (parity rows);
        ``bytes_fetched`` (everything that came down from the device:
        the parity rows and the tags); ``hash_jobs`` (SHA-256 handed to
        the workers: every fragment and every segment); and, by stage
        name, ``stage_count`` and ``stage_seconds`` (raw, unrounded:
        the upload thread's stages once an upload, the workers' once a
        job, so their seconds are worker-seconds and may pass the
        upload's own)."""
        with self._mu:
            out = dict(self._counters)
            out["stage_count"] = {k: v[0] for k, v in self._stages.items()}
            out["stage_seconds"] = {k: v[1]
                                    for k, v in self._stages.items()}
        return out

    def metrics(self) -> dict[str, float]:
        """``counters()`` as ``cess_gateway_*_total`` series and, a
        stage, ``cess_gateway_stage_<name>_seconds`` / ``_count``
        (``<name>``: the stage's without its first part, dots as
        underscores: ``upload``, ``encode_put``, ``worker_copy``):
        merged into GET /metrics when the node carries ``node.gateway
        = gw`` (node/metrics.py collect())."""
        return _series("gateway", self.counters())

    def upload(self, owner: str, bucket: str, file_name: str,
               data: bytes) -> bytes:
        """Segment + encode + tag on device; declare on chain; keep
        fragments ready for miners to fetch. Returns the file hash."""
        cfg = self.pipeline.config
        k, m, seg_size, n = cfg.k, cfg.m, cfg.segment_size, \
            cfg.fragment_size
        rows = k + m
        padded = data + b"\0" * ((-len(data)) % seg_size)
        n_segs = len(padded) // seg_size
        segments = np.frombuffer(padded, dtype=np.uint8).reshape(n_segs, seg_size)
        host = memoryview(padded)
        hashers = self._hashers
        # one stage each per upload (obs.trace.stage): the upload, its
        # seven stages and the three children of gateway.encode are
        # cess:offchain.upload / cess:gateway.* in any profiler trace,
        # counted in ``sink``, and spans of an armed tracer; all of
        # those are on this thread. The workers' jobs are stages too
        # (gateway.worker.copy / .hash, one a job), each on its
        # worker's thread and in its worker's own sink of ``sinks``,
        # children of the upload's span
        sink: dict = {}
        sinks: dict = {}
        with trace.stage("offchain.upload", sink, sys="offchain",
                         file=file_name, segments=n_segs,
                         size=len(data)):
            span = trace.current_span()
            with trace.stage("gateway.encode", sink):
                with trace.stage("gateway.encode.jobs", sink):
                    # what needs nothing from the device starts now,
                    # on zero-copy views of the input, and runs behind
                    # the copy up, the encode and the parity's way down
                    seg_jobs = [hashers.submit(
                        _hashed, host[i * seg_size:(i + 1) * seg_size],
                        sinks, span) for i in range(n_segs)]
                    frag_jobs = [[hashers.submit(
                        _hashed_copy, host[i * seg_size + j * n:
                                           i * seg_size + (j + 1) * n],
                        sinks, span)
                        for j in range(k)] for i in range(n_segs)]
                with trace.stage("gateway.encode.put", sink):
                    # the upload's host -> device copy: the call, as
                    # the host sees it (the bytes' own time is in the
                    # device's line of a trace)
                    segments_dev = jnp.asarray(segments)
                with trace.stage("gateway.encode.step", sink):
                    frags_dev = self.pipeline.encode_step(segments_dev,
                                                          tenant=owner)
                # the fragments hold the same bytes: 64 MiB of device
                # memory that the tag step's program would peak over
                del segments_dev
            with trace.stage("gateway.fetch", sink, rows=n_segs * m,
                             bytes=n_segs * m * n):
                # the device-resident fragments feed tag_step DIRECTLY
                # (zero-copy engine handoff) and stay whole; only the
                # parity rows come down, all on their way at once, each
                # handed on as it lands
                parity = _parity_rows(frags_dev, k=k)
                for row in parity:
                    row.copy_to_host_async()
                for at, row in enumerate(parity):
                    frag_jobs[at // m].append(hashers.submit(
                        _hashed_copy, np.asarray(row), sinks, span))
            with trace.stage("gateway.hash", sink):
                # ids feed the tag PRF, so the tags wait for every hash
                # of the batch; a failed hash or fetch fails the upload
                # here, before anything is stored or declared
                frags = [[job.result() for job in seg]
                         for seg in frag_jobs]
                seg_hashes = [job.result() for job in seg_jobs]
                ids = np.array([[podr2.fragment_id_from_hash(h)
                                 for _, h in seg] for seg in frags],
                               dtype=np.uint32)
            with trace.stage("gateway.tag", sink):
                tags_dev = self.pipeline.tag_step(frags_dev,
                                                  jnp.asarray(ids),
                                                  tenant=owner)
            with trace.stage("gateway.fetch", sink):
                tags = np.asarray(tags_dev)
            with trace.stage("gateway.store", sink):
                for i in range(n_segs):
                    for j in range(rows):
                        blob, h = frags[i][j]
                        self.fragment_store[h] = blob
                        self.tag_store[h] = tags[i, j]
            with trace.stage("gateway.declare", sink):
                seg_list = [(seg_hashes[i],
                             tuple(h for _, h in frags[i]))
                            for i in range(n_segs)]
                file_hash = fragment_hash(b"".join(h for _, fs in seg_list
                                                   for h in fs))
                self.node.submit_extrinsic(
                    self.account, "file_bank.upload_declaration",
                    file_hash, seg_list,
                    UserBrief(owner, file_name, bucket), len(data))
                # custody lineage: one encode+dispatch event per upload
                # — the declared seg_list is exactly what the ledger
                # needs (the guarded note is free when no recorder is
                # armed)
                _flight.note("custody", "dispatch", owner=owner,
                             file=file_hash, k=cfg.k, m=cfg.m,
                             segments=seg_list)
        # every job of this upload has handed in its result, so no
        # worker writes its sink of this upload any more
        with self._mu:
            c = self._counters
            c["uploads"] += 1
            c["rows_from_host"] += n_segs * k
            c["rows_fetched"] += n_segs * m
            c["bytes_fetched"] += n_segs * m * n + tags.nbytes
            c["hash_jobs"] += n_segs * (rows + 1)
            _merge_sinks(self._stages, sink, *sinks.values())
        return file_hash


def filler_bytes(miner: str, index: int, size: int) -> bytes:
    """Deterministic filler (idle file) content: a SHA-256 counter-mode
    stream over (miner, index). Anyone — miner, TEE, auditor — can
    regenerate a filler byte-exactly, which is how the TEE certifies
    filler hashes before the chain credits idle space (the reference's
    generated idle files, file-bank/src/lib.rs:798-859).

    Known limitation (documented at file_bank.upload_filler): publicly
    derivable content proves TAG possession, not disk. The
    PoIS-direction upgrade is :func:`slow_filler_bytes`."""
    out = bytearray()
    seed = b"cess-filler:" + miner.encode() + index.to_bytes(8, "little")
    ctr = 0
    while len(out) < size:
        out += hashlib.sha256(seed + ctr.to_bytes(8, "little")).digest()
        ctr += 1
    return bytes(out[:size])


SLOW_FILLER_WORK = 2048   # sequential hashes per 512-B block (cost knob)


def filler_seed_commitment(secret: bytes) -> bytes:
    """The on-chain commitment to a miner's filler seed."""
    return hashlib.sha256(b"cess-filler-seed:" + secret).digest()


def slow_filler_bytes(secret: bytes, index: int, size: int,
                      work: int = SLOW_FILLER_WORK) -> bytes:
    """PoIS-direction filler content (the upgrade CESS itself made —
    SURVEY.md notes idle files were later replaced by PoIS):

    - seeded by a MINER SECRET (committed on chain via
      sminer.commit_filler_seed), so the network at large cannot
      derive the content; the TEE learns the secret once, inside the
      enclave, at certification time;
    - each 512-byte block is the output of a ``work``-step SEQUENTIAL
      hash chain, so even the secret-holding miner cannot cheaply
      regenerate challenged blocks inside an audit window: answering
      a ~47-block challenge without the data costs ~47*work sequential
      hashes per filler, versus one disk read each — dedicated storage
      becomes the rational strategy, which is what the idle-space
      ledger is supposed to measure.

    Audit verification is UNAFFECTED: the TEE tags the content once at
    certification; challenges verify against tags (Shacham-Waters),
    never by regeneration.
    """
    block_bytes = 512
    out = bytearray()
    for j in range(-(-size // block_bytes)):
        state = hashlib.sha256(
            b"cess-pois-filler:" + secret + index.to_bytes(8, "little")
            + j.to_bytes(8, "little")).digest()
        for _ in range(work):          # the sequential cost
            state = hashlib.sha256(state).digest()
        for c in range(block_bytes // 32):   # cheap expansion
            out += hashlib.sha256(state + c.to_bytes(4, "little")).digest()
    return bytes(out[:size])


class MinerAgent:
    """A storage miner's off-chain side: it fetches the fragments of the
    deals it is assigned into ``store`` (fragment hash -> ``bytes``,
    their PoDR2 tags beside them in ``tags``), answers every audit
    round over what it holds, and rebuilds lost fragments for restoral
    orders.

    A round is answered through ONE entry point, ``prove_round(seed,
    owed)`` -> the aggregated proof's wire bytes, which ``on_block`` ->
    ``_submit_proof`` calls for the service and the idle proof alike.
    The fragments stay where they are held: what a round hands on are
    views of the store's ``bytes`` and the tag arrays as they lie (one
    largest deal's share is 1,000 fragments of 8 MiB, 7.8 GiB, and no
    copy of it is made), the prover gathers the round's challenged
    blocks from them ``podr2.PROVE_CHUNK`` fragments at a time, and
    what crosses to the device is those chunks (24 MiB each at the
    protocol's geometry, 373.5 MiB a round of 1,000 fragments) folded
    into one running (mu, sigma) that comes back once. ``store[h]``
    serves transfers, repairs and the tests that index it as before.

    A restoral is done through ONE entry point too,
    ``restore_fragment(hashes, row, peers)``: everything ``try_repair``
    does once the chain has named the segment and the lost row.
    ``counters()`` / ``metrics()`` give its accounts and every stage's
    count and seconds."""

    def __init__(self, node: Node, account: str, gateways: list[OssGateway],
                 pipeline: StoragePipeline, engine=None, retry=None,
                 clock=None):
        self.node = node
        self.account = account
        self.gateways = gateways
        self.pipeline = pipeline
        # optional cess_tpu.resilience.RetryPolicy for fragment
        # transfers: dropped/corrupted fetches (the "offchain.fetch"
        # fault seam) re-attempt with deterministic backoff instead of
        # waiting a whole deal-servicing round. None = one attempt.
        self.retry = retry
        # retry backoff clock: any object with sleep(seconds). None =
        # wall clock; a sim world injects its SimClock so transfer
        # backoff advances virtual time (cess_tpu/sim).
        self.clock = clock
        # optional submission engine (cess_tpu/serve): proving and RS
        # repair go through its prove/repair queues — concurrent miners
        # answering the same round coalesce into shared device batches.
        # None (default) keeps the direct synchronous path.
        self.engine = None
        if engine is not None:
            self.attach_engine(engine)
        # repair dispatch mode (ops/regen.py): "fragments" fetches k
        # whole survivor rows per repair; "symbols" walks the
        # product-matrix repair-symbol chain through the helpers so
        # only the final fragment-sized aggregate is ingressed. The
        # mode can be flipped mid-run (set_repair_mode) by tests or
        # the remediation plane; the lock keeps the flip + flight
        # note atomic against concurrent flippers.
        self.repair_mode = "fragments"
        self._mode_mu = threading.Lock()
        # ingress accounting: every repair is charged by the bytes
        # that crossed the wire INTO this miner vs the bytes it
        # recovered — the regenerating claim is ingress/recovered ~ 1
        # against the whole-fragment baseline of k (sim invariant
        # "repair-ingress-bound", bench ingress_bytes_per_recovered_byte)
        self.repair_ingress_bytes = 0
        self.repair_recovered_bytes = 0
        self.repair_symbol_repairs = 0
        self.repair_whole_repairs = 0
        self.repair_fallbacks = 0
        # stage name -> [count, seconds] over this miner's restorals
        # and the hops it served as a helper (obs.trace.stage sinks,
        # one a call, merged here under the lock: a helper is called on
        # its rebuilder's thread, and several may ask at once)
        self._stages: dict[str, list] = {}
        self._stages_mu = threading.Lock()
        self.store: dict[bytes, bytes] = {}        # fragment hash -> bytes
        self.tags: dict[bytes, np.ndarray] = {}
        self.filler_store: dict[bytes, bytes] = {}  # filler hash -> bytes
        self.filler_tags: dict[bytes, np.ndarray] = {}
        self._reported: set[bytes] = set()
        self._proved_round: int = -1

    def attach_engine(self, engine) -> None:
        """Bind a submission engine, geometry-checked: a mismatched
        codec would feed repair wrong shard geometry, so this is loud —
        like StoragePipeline/TeeAgent — whether it happens at
        construction or late (the sim's repair storm attaches the pool
        engine to rescuers that were built without one)."""
        if engine is not None and engine.codec is not None \
                and (engine.codec.k, engine.codec.m) \
                != (self.pipeline.config.k, self.pipeline.config.m):
            raise ValueError(
                f"engine codec RS({engine.codec.k},{engine.codec.m}) != "
                f"miner pipeline RS({self.pipeline.config.k},"
                f"{self.pipeline.config.m})")
        self.engine = engine

    def set_repair_mode(self, mode: str) -> None:
        """Flip the repair dispatch mode mid-run. Thread-safe and
        flight-noted (("repair", "mode")) so mode changes show up in
        incident bundles; a no-op flip stays silent."""
        if mode not in ("symbols", "fragments"):
            raise ValueError(
                f"repair_mode must be 'symbols' or 'fragments', "
                f"got {mode!r}")
        with self._mode_mu:
            frm = self.repair_mode
            if frm == mode:
                return
            self.repair_mode = mode
        _flight.note("repair", "mode", miner=self.account, frm=frm,
                     to=mode)

    # -- fillers -----------------------------------------------------------------
    def setup_fillers(self, tee: "TeeAgent", count: int) -> None:
        """Generate ``count`` fillers, have the TEE certify + tag them,
        and register them on chain (idle space enters the ledger)."""
        size = self.pipeline.config.fragment_size
        blobs = [filler_bytes(self.account, i, size) for i in range(count)]
        hashes, tags, sig = tee.certify_fillers(self.account,
                                                list(range(count)), blobs)
        for h, blob, tag in zip(hashes, blobs, tags):
            self.filler_store[h] = blob
            self.filler_tags[h] = tag
        self.node.submit_extrinsic(self.account, "file_bank.upload_filler",
                                   tuple(hashes), tee.controller, sig)

    def commit_filler_seed(self, secret: bytes) -> None:
        """Submit the one-time on-chain seed commitment; it must be in
        a block before the TEE will certify (run a slot in between)."""
        self.node.submit_extrinsic(self.account,
                                   "sminer.commit_filler_seed",
                                   filler_seed_commitment(secret))

    def setup_fillers_pois(self, tee: "TeeAgent", count: int,
                           secret: bytes,
                           work: int = SLOW_FILLER_WORK) -> None:
        """Secret-seeded filler setup: the seed commitment must
        already be on chain (commit_filler_seed); the TEE derives +
        certifies against it, then the batch is registered."""
        hashes, tags, sig, blobs = tee.certify_pois_fillers(
            self.account, secret, list(range(count)), work)
        for h, blob, tag in zip(hashes, blobs, tags):
            self.filler_store[h] = blob
            self.filler_tags[h] = tag
        self.node.submit_extrinsic(self.account, "file_bank.upload_filler",
                                   tuple(hashes), tee.controller, sig)

    # -- deal servicing ---------------------------------------------------------
    def _fetch(self, frag_hash: bytes) -> bool:
        for gw in self.gateways:
            blob = self._transfer(gw, frag_hash)
            if blob is not None:
                self.store[frag_hash] = blob
                # the miner's own tag array, laid as a round reads it
                # (the same object where it already is)
                self.tags[frag_hash] = np.ascontiguousarray(
                    gw.tag_store[frag_hash])
                return True
        # repair path: reconstruct from peers (restoral flow fetches
        # survivor rows from other miners via the network harness)
        return False

    def _transfer(self, gw: OssGateway, frag_hash: bytes) -> bytes | None:
        """One gateway fragment transfer: faultable (seam
        "offchain.fetch" drops the transfer, "offchain.fetch_bytes"
        corrupts the payload), INTEGRITY-CHECKED against the on-chain
        fragment hash (a corrupted transfer is a failed transfer,
        never poisoned storage — the same contract try_repair applies
        to reconstructed bytes), and retried under the configured
        policy. Returns the verified bytes or None."""
        attempts = 1 if self.retry is None else self.retry.max_attempts
        for attempt in range(1, attempts + 1):
            if attempt > 1:
                # deterministic jitter keyed by the fragment identity:
                # replayable in chaos tests, decorrelated across frags
                (self.clock or time).sleep(
                    self.retry.delay_for(attempt - 1, token=frag_hash))
            if not faults.allow("offchain.fetch"):
                continue             # transfer dropped: transient
            blob = gw.fragment_store.get(frag_hash)
            if blob is None:
                return None          # gateway lacks it: not transient
            blob = faults.corrupt("offchain.fetch_bytes", blob)
            if fragment_hash(blob) == frag_hash:
                return blob
            # corrupted in flight: counts as a failed attempt
        return None

    def on_block(self, node: Node) -> None:
        rt = node.runtime
        # service assigned deals
        for (fh,), deal in list(rt.state.iter_prefix("file_bank", "deal")):
            if self.account not in deal.assigned or fh in self._reported \
                    or self.account in deal.complete:
                continue
            row = deal.assigned.index(self.account)
            with trace.span("offchain.transfer", sys="offchain",
                            miner=self.account, file=fh):
                done = all(self._fetch(seg.fragment_hashes[row])
                           for seg in deal.segments)
            if done:
                node.submit_extrinsic(self.account,
                                      "file_bank.transfer_report", fh)
                self._reported.add(fh)
                # custody transfer: this miner now holds its row of
                # every segment (the ledger flips gateway -> miner)
                _flight.note("custody", "transfer", miner=self.account,
                             file=fh, row=row,
                             frags=tuple(seg.fragment_hashes[row]
                                         for seg in deal.segments))
        # answer challenges over REAL stored bytes
        ch = rt.audit.challenge()
        if ch is not None and not ch.cleared \
                and rt.state.block <= ch.challenge_deadline \
                and ch.start != self._proved_round \
                and any(s.miner == self.account for s in ch.miners):
            self._submit_proof(node, ch)
            self._proved_round = ch.start

    def _submit_proof(self, node: Node, ch) -> None:
        """Distinct idle + service proofs, each a constant-size
        aggregated (mu, sigma) over the owed sets FROZEN in the
        challenge snapshot — the reference's two-proof submit_proof
        (audit/src/lib.rs:430-479) with honest wire sizing."""
        seed = b"".join(ch.net.randoms)
        snap = next(s for s in ch.miners if s.miner == self.account)
        with trace.span("offchain.prove", sys="offchain",
                        miner=self.account, round=ch.start,
                        service=len(snap.service_frags),
                        idle=len(snap.fillers)):
            service = self.prove_round(seed, snap.service_frags)
            idle = self.prove_round(seed, snap.fillers, idle=True)
            node.submit_extrinsic(self.account, "audit.submit_proof",
                                  idle, service)

    @classmethod
    def custodian(cls, store: dict, tags: dict, *, limbs: int | None = None,
                  engine=None, account: str | None = None) -> "MinerAgent":
        """A miner reduced to what a round reads: its store, its tag
        store, the deployment's limb width and an optional engine — no
        node, no gateways, no pipeline. ``build_proof`` answers through
        one; it holds the caller's dicts, not copies."""
        agent = object.__new__(cls)
        agent.account = account
        agent.engine = engine
        agent.pipeline = None
        agent._limbs = limbs
        agent.store, agent.tags = store, tags
        agent.filler_store, agent.filler_tags = {}, {}
        return agent

    def prove_round(self, seed: bytes, owed, *, idle: bool = False
                    ) -> bytes:
        """THE miner's entry point: one round answered. ``seed`` is the
        round's randomness and ``owed`` the fragment hashes frozen in
        the challenge snapshot (service fragments, or with ``idle`` the
        fillers) -> the aggregated proof (mu, sigma) as wire bytes,
        constant in size. ``_submit_proof`` calls it for both proofs of
        ``audit.submit_proof``.

        A fragment the miner no longer holds simply does not contribute
        — the fold then fails TEE verification (that's the audit); an
        empty held set is the all-zero proof. Nothing is remembered
        from an earlier round and nothing skipped: every challenged
        block of every held owed fragment is read from the store's
        bytes in this call, both limbs always.

        The store is not copied. What goes to the prover is a view of
        each held fragment's ``bytes`` and its tag array as they lie
        (``np.frombuffer``: 1,000 fragments of 8 MiB are 7.8 GiB that
        stay where they are); the ids come from the hashes in one pass
        and r as host words from calls of fixed shapes
        (``podr2.round_coeffs``). With an engine the request joins its
        prove class, where miners answering the same round coalesce;
        without one ``podr2.prove_held`` runs the same steps directly.
        Either gathers the round's challenged blocks (4.6% of the set
        at the protocol's geometry) ``podr2.PROVE_CHUNK`` fragments at
        a time and folds them into a running (mu, sigma) on the device,
        so only what the round reads travels, in pieces of one shape
        however large the custody, and the bytes are the same whichever
        way. A profiler trace holds ``cess:miner.round`` with ``.ids``,
        ``.challenge``, ``.coeffs``, ``.submit`` and ``.encode`` inside
        it (and, between the last two, the caller's wait for the
        engine: ``cess:engine.prove.result``)."""
        store, tags = (self.filler_store, self.filler_tags) if idle \
            else (self.store, self.tags)
        with trace.stage("miner.round"):
            with trace.stage("miner.round.ids"):
                held = [h for h in owed if h in store]
                # the limb WIDTH is a deployment parameter: it comes
                # from the PoDR2 key (hardwiring 2 broke limbs=3
                # deployments; and an EMPTY tags map must not silently
                # fall back to the module default — a fillerless miner
                # in a limbs=3 deployment would emit a wrong-width zero
                # sigma and fail an audit it should pass; both
                # review-caught, r05)
                limbs = self._limbs if self.pipeline is None \
                    else self.pipeline.podr2_key.limbs
                if limbs is None:
                    limbs = next(iter(tags.values())).shape[-1] if tags \
                        else podr2.LIMBS
                if not held:
                    return codec.encode(Proof(
                        mu=np.zeros((podr2.SECTORS,), np.uint32),
                        sigma=np.zeros((limbs,), np.uint32)))
                frags = [np.frombuffer(store[h], dtype=np.uint8)
                         for h in held]
                tag_rows = [tags[h] for h in held]
                ids = podr2.fragment_ids_from_hashes(held)
            with trace.stage("miner.round.challenge"):
                idx, nu = (np.asarray(a) for a in podr2.gen_challenge(
                    seed, tag_rows[0].shape[0]))
            with trace.stage("miner.round.coeffs"):
                r = podr2.round_coeffs(seed, ids)
            with trace.stage("miner.round.submit"):
                engine = self.engine
                if engine is not None and engine.audit is not None:
                    # submission-engine path: miners answering the same
                    # round coalesce in the engine's prove queue
                    # (bit-identical fold)
                    pending = engine.submit_prove_aggregate(
                        frags, tag_rows, idx, nu, r, tenant=self.account)
                else:
                    pending = podr2.prove_held(frags, tag_rows, idx, nu, r)
            mu, sigma = pending.result() if hasattr(pending, "result") \
                else pending
            with trace.stage("miner.round.encode"):
                return codec.encode(Proof(
                    mu=np.ascontiguousarray(np.asarray(mu, dtype=np.uint32)),
                    sigma=np.ascontiguousarray(
                        np.asarray(sigma, dtype=np.uint32))))

    # -- restoral servicing -------------------------------------------------------
    def warm_restoral(self) -> None:
        """Pre-compile + pre-stage the restoral market's reconstruct
        program for the SHAPE of a restoral repair — one lost row
        rebuilt from k survivors of ``fragment_size`` bytes — so a
        claimed order pays kernel time, not first-call compile. The
        program takes the erasure pattern's matrix as an operand
        (ops/rs.py), so it serves whichever k holders answer
        ``try_repair``, not only the k lowest surviving rows. Those
        patterns — one per lost row, the survivor set try_repair
        assembles when every peer holds its fragment — are what is
        handed over: their matrices are built and staged as well, which
        at the protocol's RS(2,1) is every pattern there is; any other
        helper set costs its matrix (a fraction of a millisecond on the
        host) and nothing else. With an engine, the engine's repair
        program cache is warmed under the keys its batcher will hit;
        without one, the codec's own warm path is used directly (no-op
        on the NumPy reference codec)."""
        cfg = self.pipeline.config
        rows = cfg.k + cfg.m
        patterns = []
        for row in range(rows):
            present = tuple(j for j in range(rows) if j != row)[:cfg.k]
            patterns.append((present, (row,)))
        if self.engine is not None and self.engine.codec is not None:
            # restoral repairs are single-order blocking submits, so
            # only the 1-row bucket's shape is ever dispatched —
            # warming bucket 2 as well would double the compiles
            # (per shape x per lane) for programs a repair never hits
            self.engine.warm_repair(patterns, cfg.fragment_size,
                                    buckets=(1,))
            return
        from ..ops.rs import make_codec

        # make_codec is lru_cached: this is the SAME instance
        # try_repair resolves later, so the warm programs persist
        codec_ = make_codec(cfg.k, cfg.m, backend="auto")
        warm = getattr(codec_, "warm_reconstruct", None)
        if warm is not None:
            for present, missing in patterns:
                warm(present, missing, (cfg.k, cfg.fragment_size))

    def repair_symbol(self, frag_hash: bytes, coeff: int,
                      acc: np.ndarray | None = None) -> np.ndarray | None:
        """Helper side of a regenerating repair (ops/regen.py): fold
        THIS miner's survivor fragment into the partial-sum chain,
        acc ^ coeff*fragment, and return the fragment-sized aggregate
        for the next helper (or the rebuilder, on the last hop).
        Returns None when this helper can't serve — fragment not held,
        or the transfer dropped (seam "offchain.symbol"). The outgoing
        aggregate rides the "offchain.symbol_bytes" corruption seam;
        integrity is the REBUILDER's hash check, exactly as for
        whole-fragment transfers.

        The aggregate comes in and goes out as a host array: the
        helpers are machines of their own, so nothing of one hop stays
        on the device for the next. With a regenerating engine the
        fold is one request of the repair class, handed over as its
        two rows where they lie (the accumulator that arrived, a view
        of the held ``bytes``; the first hop's accumulator is a zero
        row); a hop is the stage ``miner.symbol.hop``."""
        blob = self.store.get(frag_hash)
        if blob is None:
            return None
        if not faults.allow("offchain.symbol"):
            return None
        sink: dict = {}
        with trace.stage("miner.symbol.hop", sink):
            frag = np.frombuffer(blob, dtype=np.uint8)
            acc = np.zeros_like(frag) if acc is None \
                else np.asarray(acc, dtype=np.uint8)
            if self.engine is not None and self.engine.codec is not None \
                    and hasattr(self.engine.codec, "fold_symbol"):
                sym = self.engine.repair_symbol([acc, frag], int(coeff),
                                                tenant=self.account)[0]
            else:
                from ..ops import regen

                sym = regen.fold_symbol_host(acc, frag, int(coeff))
            sym = np.asarray(faults.corrupt("offchain.symbol_bytes", sym),
                             dtype=np.uint8)
        self._merge_stages(sink)
        return sym

    def _repair_via_symbols(self, hashes, row: int,
                            present: tuple[int, ...],
                            holders: dict[int, "MinerAgent"],
                            cfg: PipelineConfig) -> bytes | None:
        """Walk the product-matrix repair-symbol chain: each holder
        folds coeff_j * fragment_j into the travelling partial sum, and
        only the FINAL fragment-sized aggregate reaches this miner —
        ingress n bytes for n recovered, vs k*n on the whole-fragment
        path. Returns the (unverified) aggregate bytes, or None when
        any hop refuses (the caller then falls back)."""
        from ..ops import regen

        try:
            coeffs = regen.repair_coeffs(cfg.k, cfg.m, present, (row,))
        except ValueError:
            return None
        acc = None
        for j, coeff in zip(present, coeffs):
            acc = holders[j].repair_symbol(hashes[j], int(coeff), acc)
            if acc is None:
                return None
        # the aggregate crossed the wire whether or not it hashes
        # clean — honest accounting charges it either way
        self.repair_ingress_bytes += acc.nbytes
        return acc.tobytes()

    def _repair_via_fragments(self, hashes, row: int,
                              present: tuple[int, ...],
                              holders: dict[int, "MinerAgent"],
                              cfg: PipelineConfig) -> bytes:
        """Whole-fragment dispatch: ingress k survivor rows — from
        whichever k holders ``restore_fragment`` found, in row order —
        and reconstruct (engine repair queue when attached, direct
        codec otherwise). The program is the warmed shape's whatever
        the helper set; the set picks the matrix it is called with. The
        engine takes the rows as they lie in the holders' stores (views
        of their ``bytes``): it puts each on the device from there and
        stacks them there, so no host copy of the k fragments is made."""
        survivors = [np.frombuffer(holders[j].store[hashes[j]],
                                   dtype=np.uint8) for j in present]
        self.repair_ingress_bytes += sum(s.nbytes for s in survivors)
        if self.engine is not None and self.engine.codec is not None:
            rec = self.engine.reconstruct(survivors, present, (row,),
                                          tenant=self.account)
        else:
            from ..ops.rs import make_codec

            codec_ = make_codec(cfg.k, cfg.m, backend="auto")
            rec = codec_.reconstruct(np.stack(survivors), present,
                                     (row,))
        return np.asarray(rec)[0].tobytes()

    def try_repair(self, frag_hash: bytes, peers: list["MinerAgent"],
                   gateways: list[OssGateway] | None = None) -> bool:
        """Claim + repair a broken fragment from peer-held rows, then
        report completion: the chain's side of a restoral. The order,
        its file and the segment that holds the fragment are read from
        ``file_bank``; the repair itself — holders, dispatch, hash
        check, store, both extrinsics — is ``restore_fragment``."""
        rt = self.node.runtime
        order = rt.file_bank.restoral_order(frag_hash)
        if order is None:
            return False
        f = rt.file_bank.file(order.file_hash)
        if f is None:
            return False
        seg = next(s for s in f.segments if frag_hash in s.fragment_hashes)
        row = seg.fragment_hashes.index(frag_hash)
        with trace.span("offchain.repair", sys="offchain",
                        miner=self.account, row=row,
                        mode=self.repair_mode):
            return self.restore_fragment(seg.fragment_hashes, row, peers,
                                         gateways)

    def restore_fragment(self, hashes, row: int,
                         peers: list["MinerAgent"],
                         gateways: list[OssGateway] | None = None
                         ) -> bool:
        """THE miner's repair entry point: one lost fragment rebuilt.
        ``hashes`` are the segment's ``k + m`` fragment hashes in row
        order (their on-chain ids), ``row`` the lost one, ``peers`` the
        miners to ask -> True once the fragment is stored and reported.
        ``try_repair`` calls it after its chain lookups.

        The helpers are the first k peers, in row order, that hold
        their row. ``repair_mode`` picks the dispatch: "fragments"
        ingresses k whole survivor rows; "symbols" walks the
        regenerating repair-symbol chain (ops/regen.py) and ingresses
        one fragment-sized aggregate, falling back to the
        whole-fragment path when a helper refuses or the aggregate
        fails its hash (counted in ``repair_fallbacks`` and noted to
        the flight recorder). EITHER WAY the repaired bytes must
        re-hash to the on-chain identity before they are stored — a
        bad decode is a failed repair, never poisoned storage. Then
        ``file_bank.claim_restoral_order`` and
        ``file_bank.restoral_order_complete`` are submitted.

        A profiler trace holds ``cess:miner.repair`` with ``.holders``,
        ``.chain`` (symbols) or ``.fragments``, ``.hash``, ``.store``
        and ``.report`` inside it (a fallback: ``.chain``, ``.hash``,
        then ``.fragments`` and ``.hash`` again); ``counters()`` has
        each one's count and seconds."""
        sink: dict = {}
        try:
            with trace.stage("miner.repair", sink, sys="offchain",
                             miner=self.account, row=row):
                return self._restore(hashes, row, peers, gateways, sink)
        finally:
            self._merge_stages(sink)

    def _restore(self, hashes, row: int, peers, gateways,
                 sink: dict) -> bool:
        """``restore_fragment`` inside its stage."""
        frag_hash = hashes[row]
        cfg = self.pipeline.config
        with trace.stage("miner.repair.holders", sink):
            holders: dict[int, MinerAgent] = {}
            for j, h in enumerate(hashes):
                if j == row:
                    continue
                for peer in peers:
                    if h in peer.store:
                        holders[j] = peer
                        break
                if len(holders) == cfg.k:
                    break
        if len(holders) < cfg.k:
            return False
        present = tuple(holders)
        via_symbols = False
        ingress0 = self.repair_ingress_bytes
        blob = None
        if self.repair_mode == "symbols":
            with trace.stage("miner.repair.chain", sink):
                blob = self._repair_via_symbols(hashes, row, present,
                                                holders, cfg)
            with trace.stage("miner.repair.hash", sink):
                via_symbols = blob is not None \
                    and fragment_hash(blob) == frag_hash
            if not via_symbols:
                self.repair_fallbacks += 1
                _flight.note("repair", "fallback",
                             miner=self.account, row=row,
                             reason="broken-chain" if blob is None
                             else "bad-hash")
        if not via_symbols:
            with trace.stage("miner.repair.fragments", sink):
                blob = self._repair_via_fragments(hashes, row, present,
                                                  holders, cfg)
            with trace.stage("miner.repair.hash", sink):
                if fragment_hash(blob) != frag_hash:
                    return False
        with trace.stage("miner.repair.store", sink):
            self.store[frag_hash] = blob
            self.repair_recovered_bytes += len(blob)
            if via_symbols:
                self.repair_symbol_repairs += 1
            else:
                self.repair_whole_repairs += 1
            for peer in peers:
                if frag_hash in peer.tags:
                    self.tags[frag_hash] = peer.tags[frag_hash]
                    break
            else:
                for gw in (gateways or self.gateways):
                    if frag_hash in gw.tag_store:
                        self.tags[frag_hash] = gw.tag_store[frag_hash]
                        break
        with trace.stage("miner.repair.report", sink):
            self.node.submit_extrinsic(self.account,
                                       "file_bank.claim_restoral_order",
                                       frag_hash)
            self.node.submit_extrinsic(self.account,
                                       "file_bank.restoral_order_complete",
                                       frag_hash)
            # custody restoral: the fragment's custodian is this miner
            # now (the ledger clears the loss and re-scores the margin)
            _flight.note("custody", "repair", miner=self.account,
                         frag=frag_hash,
                         mode="symbols" if via_symbols else "fragments",
                         ingress=self.repair_ingress_bytes - ingress0)
        return True

    def _merge_stages(self, sink: dict) -> None:
        """One call's stage sink into this miner's totals."""
        with self._stages_mu:
            _merge_sinks(self._stages, sink)

    def counters(self) -> dict:
        """Totals over this miner's restorals: ``repairs`` (fragments
        stored: ``repair_symbol_repairs`` + ``repair_whole_repairs``),
        ``repair_ingress_bytes`` (what crossed the wire INTO this miner
        for them, the aggregates that failed their hash included),
        ``repair_recovered_bytes``, ``repair_fallbacks`` (chains that
        ended in a whole-fragment repair) and, by stage name,
        ``stage_count`` and ``stage_seconds`` (raw, unrounded) of
        ``miner.repair`` and its children and of the hops this miner
        served as a helper (``miner.symbol.hop``)."""
        with self._stages_mu:
            stages = {k: tuple(v) for k, v in self._stages.items()}
        out = {name: getattr(self, name) for name in (
            "repair_ingress_bytes", "repair_recovered_bytes",
            "repair_symbol_repairs", "repair_whole_repairs",
            "repair_fallbacks")}
        out["repairs"] = out["repair_symbol_repairs"] \
            + out["repair_whole_repairs"]
        out["stage_count"] = {k: v[0] for k, v in stages.items()}
        out["stage_seconds"] = {k: v[1] for k, v in stages.items()}
        return out

    def metrics(self) -> dict[str, float]:
        """``counters()`` as ``cess_miner_*_total`` series and, a
        stage, ``cess_miner_stage_<name>_seconds`` / ``_count``
        (``<name>``: the stage's without its first part, dots as
        underscores: ``repair``, ``repair_chain``, ``symbol_hop``):
        merged into GET /metrics when the node carries ``node.miner =
        agent`` (node/metrics.py collect())."""
        return _series("miner", self.counters())


@codec.register
@dataclasses.dataclass(frozen=True)
class Proof:
    """The aggregated PoDR2 proof: ONE (mu, sigma) folded over every
    owed fragment with PRF coefficients (podr2.aggregate_coeffs). The
    chain sees only the codec-encoded bytes and caps the REAL wire
    size at SIGMA_MAX (runtime/src/lib.rs:992). Sizing is stated
    authoritatively ONCE, at podr2.PROOF_BYTES: raw payload 1032 B at
    the defaults, plus this codec framing's constant overhead
    (proof_wire_bytes() below computes the framed total — 1058 B at
    the defaults), constant in the number of fragments.

    Both fields are FIXED-WIDTH uint32 ndarrays. sigma used to be a
    tuple of Python ints, whose varint encoding shrank whenever a limb
    value happened to be small — so the wire size depended on the
    (F-dependent) fold values and test_aggregate_proof_wire_size_constant
    caught a 1-byte drift between F=1 and F=50. An ndarray encodes as
    dtype + shape + raw bytes: byte-for-byte constant in F."""
    mu: np.ndarray              # [sectors] uint32
    sigma: np.ndarray           # [limbs] uint32 F_p^limbs element


def proof_wire_bytes(limbs: int | None = None,
                     sectors: int = podr2.SECTORS) -> int:
    """The exact framed wire size of an aggregated proof: the raw
    payload (podr2.PROOF_BYTES — the ONE authoritative size statement)
    plus this codec framing's constant overhead, computed from an
    actual encode so it can never drift from the codec."""
    if limbs is None:
        limbs = podr2.LIMBS
    return len(codec.encode(Proof(
        mu=np.zeros((sectors,), np.uint32),
        sigma=np.zeros((limbs,), np.uint32))))


def build_proof(seed: bytes, owed: list[bytes],
                store: dict[bytes, bytes],
                tags: dict[bytes, np.ndarray],
                limbs: int | None = None, engine=None,
                tenant: str | None = None) -> bytes:
    """Miner-side: aggregated proof over the owed set, as wire bytes —
    ``MinerAgent.prove_round`` over the two dicts handed in (a
    ``MinerAgent.custodian`` of them). ``limbs`` is the deployment's
    limb width (None: the tags' own, else the module default);
    ``tenant`` tags the engine submit (the proving miner's account)
    for per-tenant accounting."""
    return MinerAgent.custodian(store, tags, limbs=limbs, engine=engine,
                                account=tenant).prove_round(seed, owed)


class TeeAgent:
    """Holds the PoDR2 secret; certifies fillers and verifies queued
    proofs on device.

    A round's verify missions are judged TOGETHER (``on_block`` ->
    ``judge_round`` -> ``verify_round``, the one entry point): the owed
    sets' fragment hashes become ids in one vectorised pass, and what
    goes to the device — through the engine's verify class where an
    engine is configured — is FLAT: one row an owed fragment (its id
    and its mission's index), the round's challenge and its two
    aggregation key words, and, once the folds are out and the wire
    bytes decoded under them and held to the deployment's widths, the
    proofs [missions, sectors + limbs]. One compiled program a mission
    bucket (8, 64, 512 up to the protocol's VerifyMissionMax) folds
    the rows a fixed number at a time and derives r itself;
    ``warm_verify`` loads them, after which a round of any sizes
    compiles nothing. What the verifier holds is held per mission."""

    def __init__(self, node: Node, controller: str, key: podr2.Podr2Key,
                 blocks_per_fragment: int, bls_seed: bytes | None = None,
                 engine=None):
        self.node = node
        self.controller = controller
        self.key = key
        self.blocks = blocks_per_fragment
        # optional submission engine (cess_tpu/serve): aggregated-proof
        # checks route through its verify queue — the highest-priority
        # class, so audit verification preempts bulk encode/tag work.
        # The engine's AuditBackend must hold THIS TEE's key.
        self.engine = engine
        if engine is not None and engine.audit is not None \
                and not podr2.keys_equal(engine.audit.key, key):
            raise ValueError("engine AuditBackend key is not this "
                             "TEE's PoDR2 key")
        self.account_key = node.spec.account_key(controller)
        self._submitted: set[tuple[str, int]] = set()
        # BLS verdict master key: registered on chain (with a PoP) so
        # every submit_verify_result is publicly re-verifiable
        if bls_seed is not None:
            self.bls_sk, self.bls_pk = bls12381.keygen(bls_seed)
        else:
            self.bls_sk, self.bls_pk = None, b""

    def bls_registration(self) -> tuple[bytes, bytes]:
        """(bls_pk, proof-of-possession) for tee_worker.register."""
        if self.bls_sk is None:
            return b"", b""
        return self.bls_pk, bls12381.prove_possession(self.bls_sk,
                                                      self.bls_pk)

    # -- filler certification -------------------------------------------------
    def certify_fillers(self, miner: str, indices: list[int],
                        blobs: list[bytes]):
        """Check each blob IS the canonical full-size PRF stream for
        (miner, index), tag it, and sign the hash batch bound to the
        miner's on-chain cert nonce — the attestation
        file_bank.upload_filler verifies (and consumes) on chain."""
        expected_size = self.blocks * podr2.BLOCK_BYTES
        if len(indices) != len(blobs) or len(set(indices)) != len(indices):
            raise ValueError("indices/blobs mismatch")
        for i, blob in zip(indices, blobs):
            if len(blob) != expected_size \
                    or blob != filler_bytes(miner, i, expected_size):
                raise ValueError(f"filler {i} content not canonical")
        return self._tag_and_sign(miner, blobs)

    def certify_pois_fillers(self, miner: str, secret: bytes,
                             indices: list[int],
                             work: int = SLOW_FILLER_WORK):
        """PoIS-direction variant (see slow_filler_bytes): the miner
        hands its filler seed to the ENCLAVE; the TEE checks it against
        the miner's on-chain commitment, derives the secret-seeded
        sequential content itself, tags and signs the batch through
        the SAME cert flow. Returns (hashes, tags, sig, blobs) — the
        derived blobs, so callers need not re-plot."""
        commitment = self.node.runtime.sminer.filler_seed_commitment_of(
            miner)
        if commitment is None \
                or filler_seed_commitment(secret) != commitment:
            raise ValueError("filler seed does not match the miner's "
                             "on-chain commitment")
        if len(set(indices)) != len(indices):
            raise ValueError("duplicate filler indices")
        expected_size = self.blocks * podr2.BLOCK_BYTES
        blobs = [slow_filler_bytes(secret, i, expected_size, work)
                 for i in indices]
        hashes, tags, sig = self._tag_and_sign(miner, blobs)
        return hashes, tags, sig, blobs

    def _tag_and_sign(self, miner: str, blobs: list[bytes]):
        from ..chain.file_bank import FileBank

        hashes = [fragment_hash(b) for b in blobs]
        ids = np.stack([podr2.fragment_id_from_hash(h) for h in hashes])
        tags = np.asarray(podr2.tag_fragments(
            self.key, jnp.asarray(ids),
            jnp.asarray(np.stack([np.frombuffer(b, dtype=np.uint8)
                                  for b in blobs]))))
        nonce = self.node.runtime.file_bank.filler_cert_nonce(miner)
        sig = self.account_key.sign(
            FileBank.FILLER_CERT_CONTEXT
            + codec.encode((miner, tuple(hashes), nonce)))
        return hashes, tags, sig

    # -- proof verification ----------------------------------------------------
    def on_block(self, node: Node) -> None:
        rt = node.runtime
        missions = rt.state.get("audit", "unverify", self.controller,
                                default=())
        ch = rt.audit.challenge()
        if not missions or ch is None:
            return
        # a result already queued, not yet applied, is not judged again
        todo = [m for m in missions
                if (m.miner, ch.start) not in self._submitted]
        if todo:
            self.judge_round(node, todo, b"".join(ch.net.randoms),
                             ch.start)

    def judge_round(self, node: Node, missions, seed: bytes,
                    round_start: int) -> None:
        """Judge a round's missions together (``verify_round``: every
        service proof and every idle proof in one call, against the
        owed sets frozen at round start) and submit one sealed
        ``audit.submit_verify_result`` a mission."""
        count = len(missions)
        verdicts = self.verify_round(
            [m.service_proof for m in missions]
            + [m.idle_proof for m in missions],
            [m.snapshot.service_frags for m in missions]
            + [m.snapshot.fillers for m in missions], seed)
        for mission, service_ok, idle_ok in zip(
                missions, verdicts[:count], verdicts[count:]):
            with trace.span("offchain.verify", sys="offchain",
                            tee=self.controller, miner=mission.miner,
                            round=round_start, service_ok=service_ok,
                            idle_ok=idle_ok):
                self._submitted.add((mission.miner, round_start))
                bls_sig = b""
                if self.bls_sk is not None:
                    from ..chain import audit as audit_mod
                    bls_sig = bls12381.sign(
                        self.bls_sk, audit_mod.verdict_message(
                            self.controller,
                            audit_mod.mission_digest(mission),
                            idle_ok, service_ok))
                node.submit_extrinsic(self.controller,
                                      "audit.submit_verify_result",
                                      mission.miner, idle_ok, service_ok,
                                      bls_sig)
                # custody verdict: the frozen owed set is exactly the
                # fragment list the audit outcome covers
                _flight.note("custody", "verdict", miner=mission.miner,
                             round=round_start, service=service_ok,
                             idle=idle_ok,
                             frags=mission.snapshot.service_frags)

    def warm_verify(self, missions: int = constants.VERIFY_MISSION_MAX
                    ) -> None:
        """Load every program a round of up to ``missions`` missions can
        meet (the engine's, where one is configured), so that a round
        of any sizes compiles nothing."""
        count = len(podr2.gen_challenge(b"", self.blocks)[0])
        engine = getattr(self, "engine", None)
        if engine is not None and engine.audit is not None:
            engine.warm_verify(count, missions)
            return
        key_ops = podr2.key_operands(self.key)
        for bucket in podr2.mission_buckets(missions):
            jax.block_until_ready(podr2.warm_round(key_ops, count, bucket))

    def _decode_proof(self, blob) -> "Proof | None":
        """The (untrusted) aggregated proof bytes as a well-shaped
        Proof of this deployment's widths, or None: malformed bytes
        are a failed audit, never an exception."""
        try:
            proof = codec.decode(blob)
        except (codec.CodecError, TypeError, ValueError):
            return None
        if isinstance(proof, Proof) and isinstance(proof.mu, np.ndarray) \
                and proof.mu.shape == (podr2.SECTORS,) \
                and proof.mu.dtype == np.uint32 \
                and isinstance(proof.sigma, np.ndarray) \
                and proof.sigma.shape == (self.key.limbs,) \
                and proof.sigma.dtype == np.uint32 \
                and bool((proof.sigma < pf.P).all()):
            return proof
        return None

    def _decode_round(self, proofs) -> list:
        """A round's wire proofs decoded (``_decode_proof``), in order.
        Equal bytes decode once a call (every fillerless miner sends
        the same all-zero idle proof); nothing is kept from one call to
        the next."""
        once: dict = {}
        decoded = []
        for blob in proofs:
            if type(blob) is not bytes:
                decoded.append(self._decode_proof(blob))
                continue
            if blob not in once:
                once[blob] = self._decode_proof(blob)
            decoded.append(once[blob])
        return decoded

    def _stacked(self, decoded, live) -> tuple:
        """(mu [len(live), sectors], sigma [len(live), limbs]) of the
        missions ``live`` as the close takes them; an undecodable proof
        goes as the zero proof (its verdict is forced afterwards)."""
        sectors, limbs = self.key.alpha.shape
        mu = np.zeros((len(live), sectors), np.uint32)
        sigma = np.zeros((len(live), limbs), np.uint32)
        for row, i in enumerate(live):
            if decoded[i] is not None:
                mu[row], sigma[row] = decoded[i].mu, decoded[i].sigma
        return mu, sigma

    def verify_round(self, proofs, owed_sets, seed: bytes,
                     challenge=None) -> list[bool]:
        """THE verifier's entry point: a round's missions judged
        together. ``proofs[i]`` are mission i's aggregated proof as
        wire bytes, ``owed_sets[i]`` the fragment hashes the chain says
        it owes, ``seed`` the round's randomness -> one verdict a
        mission. The miner proves exactly its obligations, or fails.

        Held per mission, never for the round: undecodable or
        mis-shaped bytes and ``sigma >= p`` are a failed audit, not an
        exception; an empty owed set passes only under the all-zero
        proof; a bad mission does not fail its neighbours. No verdict
        is remembered: every call computes every mission's equation,
        both limbs, from the bytes handed in.

        The missions' owed fragments go to the device FLAT (ids in one
        vectorised pass, a row a fragment with its mission's index) and
        one program a mission bucket folds them, r derived there from
        the round's aggregation key words: through the engine's verify
        class where one is configured (``submit_verify_round``), else
        by the same programs directly (``podr2.round_folds`` /
        ``round_verdicts``). The folds read nothing of the proofs, so
        the order is ids -> challenge -> submit -> decode -> close ->
        verdicts: the folds are on the device before a proof is
        decoded, the wire bytes are decoded while it folds (after the
        engine has said that the folds are out, so that the decode's
        Python does not hold the interpreter against the batcher's
        dispatch), and (mu, sigma) reach the program only at the close.
        Which missions' rows are folded is decided by the owed sets
        alone (non-empty); what the decode finds decides the verdict
        afterwards, as above. A mission whose proof turns out
        undecodable has therefore had its rows folded for nothing: it
        closes against the zero proof and its verdict is forced False.
        That is device work a malformed proof did not cost before; a
        well-formed wrong proof has always cost it, so nothing new is
        exposed. A profiler trace holds ``cess:tee.round`` with
        ``.ids``, ``.challenge``, ``.submit`` (to the folds' enqueue),
        ``.decode``, ``.close`` and ``.gather`` inside it."""
        with trace.stage("tee.round"):
            verdicts = [False] * len(proofs)
            live = [i for i, owed in zip(range(len(proofs)), owed_sets)
                    if len(owed)]
            late = None
            if live:
                with trace.stage("tee.round.ids"):
                    sizes = [len(owed_sets[i]) for i in live]
                    ids = np.concatenate([podr2.fragment_ids_from_hashes(
                        owed_sets[i]) for i in live])
                    words = podr2.aggregate_words(seed)
                with trace.stage("tee.round.challenge"):
                    # challenge derivation is round-constant: one a round
                    idx, nu = challenge if challenge is not None \
                        else podr2.gen_challenge(seed, self.blocks)
                    idx, nu = np.asarray(idx), np.asarray(nu)
                with trace.stage("tee.round.submit"):
                    # getattr: tests construct partial TeeAgents via __new__
                    engine = getattr(self, "engine", None)
                    if engine is not None and engine.audit is not None:
                        from ..serve.engine import LateProofs

                        late = LateProofs()
                        pending = engine.submit_verify_round(
                            ids, sizes, self.blocks, idx, nu, words,
                            tenant=self.controller, proofs=late)
                        late.folds_out()
                    else:
                        key_ops = podr2.key_operands(self.key)
                        acc = podr2.round_folds(
                            key_ops, podr2.round_rows(ids, sizes), idx, nu,
                            words)
            try:
                with trace.stage("tee.round.decode"):
                    decoded = self._decode_round(proofs)
                for i, (proof, owed) in enumerate(zip(decoded, owed_sets)):
                    if proof is not None and not len(owed):
                        verdicts[i] = not proof.sigma.any() \
                            and not proof.mu.any()
                if not live:
                    return verdicts
                with trace.stage("tee.round.close"):
                    mu, sigma = self._stacked(decoded, live)
                    if late is not None:
                        late.put(mu, sigma)
                    else:
                        pending = podr2.round_verdicts(key_ops, acc, mu,
                                                       sigma)
            except BaseException as e:
                # the batch waits for these proofs: it must not wait
                # out the request's timeout for a caller that is gone
                if late is not None:
                    late.fail(e)
                raise
            with trace.stage("tee.round.gather"):
                ok = pending.result() if late is not None \
                    else np.asarray(pending)
            for i, good in zip(live, ok):
                verdicts[i] = bool(good) and decoded[i] is not None
            return verdicts

    def _verify(self, blob, owed: list[bytes], seed: bytes,
                idx, nu) -> bool:
        """One mission through ``verify_round`` (idx, nu: the round's
        challenge, derived by the caller)."""
        return self.verify_round([blob], [owed], seed, (idx, nu))[0]


class ValidatorOcw:
    """The audit offchain worker (audit lib.rs:347-369). Holds the
    validator's session SIGNING key: proposals carry an ed25519
    signature over the snapshot digest, verified on chain against the
    session-key registry (the reference's validate_unsigned,
    lib.rs:739-772)."""

    def __init__(self, account: str, session_key):
        self.account = account
        self.session_key = session_key
        self._proposed_at: int = -1
        self._mined_era: int = -1

    def on_block(self, node: Node) -> None:
        self._maybe_propose_challenge(node)
        self._maybe_mine_election(node)

    def _maybe_propose_challenge(self, node: Node) -> None:
        from ..chain.audit import SESSION_SIGNING_CONTEXT, Audit

        rt = node.runtime
        if self.account not in rt.audit.keys():
            return
        if rt.audit.challenge() is not None:
            return
        if rt.state.block == self._proposed_at:
            return
        net, miners = rt.audit.generation_challenge()
        if not miners:
            return
        digest = Audit.snapshot_digest(net, miners)
        sig = self.session_key.sign(SESSION_SIGNING_CONTEXT + digest)
        node.submit_extrinsic(self.account, "audit.save_challenge_info",
                              net, miners, sig)
        self._proposed_at = rt.state.block

    def _maybe_mine_election(self, node: Node) -> None:
        """The reference's unsigned election phase (lib.rs:834-863):
        during the OCW window each validator mines a solution locally
        and submits it feeless; on-chain admission verifies the
        session signature and the exact score (election.py)."""
        from .consensus import elect_validators

        rt = node.runtime
        el = rt.election
        era = rt.state.block // el.era_blocks
        if not el.in_unsigned_phase() or era == self._mined_era:
            return
        if self.account not in rt.staking.validators():
            return
        # mine over the SAME stake-bounded snapshot admission verifies
        # against (election._candidates) — the full roster would pick
        # out-of-snapshot validators and every submission would bounce
        # (review-caught)
        stakes = el._candidates()
        credits = rt.credit.credits()
        maxv = el.max_validators or rt.config.max_validators
        solution = elect_validators(stakes, credits, maxv)
        if not solution:
            return
        from ..chain.election import score_of

        score = score_of(solution, stakes, credits)
        queued = rt.state.get("election", "best_unsigned", default=None)
        if queued is not None and queued[2] >= score:
            self._mined_era = era       # someone already queued as good
            return
        sig = self.session_key.sign(
            el.unsigned_payload(tuple(solution), score, self.account))
        try:
            node.submit_extrinsic(self.account,
                                  "election.submit_unsigned",
                                  tuple(solution), score, sig)
        except DispatchError:
            pass   # raced by a peer's equal solution: fine
        self._mined_era = era
