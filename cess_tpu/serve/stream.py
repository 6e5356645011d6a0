"""Double-buffered host->device streaming driver for the flagship
encode+tag workload.

Every BASELINE metric is measured device-resident, but the real
OSS-gateway workload (SURVEY.md §3.2) ingests a STREAM of 16 MiB
segments from the host. Round-tripping each batch through the host
between encode and tag, and serializing transfer against compute,
throws away exactly the throughput the kernels won — erasure-coding
pipelines live or die on transfer/compute overlap once the kernel is
fast (PAPERS: "Accelerating XOR-based Erasure Coding using Program
Optimization Techniques"), and ragged batched TPU streams need
dedicated staging to keep the chip busy (PAPERS: "Ragged Paged
Attention ... for TPU").

:class:`StreamingIngest` drives the pipeline's FUSED encode+tag
program (models/pipeline.py ``fused_program``: one jitted call) over
a host byte stream:

- each batch is staged ONCE with ``jax.device_put`` (one H2D copy from
  host bytes to device tags — the fused program never materializes an
  intermediate on the host), and crosses the link in a LINEAR layout:
  the put seam is handed the staged chunk as its 1-D ``uint8`` row views
  (models/pipeline.py ``linear_rows``: no host copy) and the program
  stacks them to ``u8[B, k, n]`` on the device — a 2-D ``uint8`` array
  would be packed four rows to a word on the host first, which capped
  every one-chip stream at 5.1-5.7 GiB/s whatever the program cost
  (PERF.md, PR 43);
- dispatch is asynchronous, so staging batch i+1 overlaps the device
  computing batch i (double buffering falls out of async dispatch +
  a bounded in-flight window: at most ``depth`` batches' programs are
  enqueued before the driver blocks on the oldest). The next batch's
  put is asked for BEFORE that wait, as soon as the put two before it
  has arrived: two puts are on the link at any time, which is how it
  carries most (tools/link_probe.py ``stream``, "2 in flight"), and it
  never stands still while the host waits for a result. That keeps one
  more batch's rows on the device than the window's ``depth``; no
  order of the three calls has the one without the other (PERF.md,
  PR 50);
- the ragged final batch is padded with zero segments to the SAME
  program shape (no tail recompile; every pipeline op is
  row-independent, so the pad rows are sliced off bit-exactly);
- every stage is counted in :class:`~cess_tpu.serve.stats.StreamStats`
  (enqueue time of the staging and of the program, stall time, pad
  waste; each stage's distribution on the program's ladder, and the
  waits over ``obs.trace.LONG_WAIT_S`` with what was in flight) and
  exported through the engine's ``cess_engine_stream_*`` metrics when
  attached (SubmissionEngine.attach_stream); the same extents are
  ``cess:stream.stage`` / ``.gate`` / ``.put`` / ``.dispatch`` /
  ``.stall`` in any profiler trace taken meanwhile (obs.trace.stage),
  the five of one batch under one ``seq``.

Results are bit-identical to the direct per-step path
(``encode_step`` -> ``tag_step``) — tests/test_stream.py pins this on
both MAC limb widths, including the ragged tail.

For multi-chip meshes, cess_tpu/parallel/mesh.py ``stream_entry``
builds the (program, put, put_ids) triple that shards each staged
batch over (seg, byte); the driver is topology-agnostic.
"""
from __future__ import annotations

import collections
import os
import resource
import time
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from ..models.pipeline import linear_rows
from ..obs import flight as _flight
from ..obs import trace
from ..resilience import faults
from .engine import _pad_axis0
from .stats import StreamStats


# /proc/pressure/<resource>, opened once and kept: a reading is one
# pread a file. None until the first reading; absent files are left out.
_PRESSURE: list | None = None

# A reading serves as the "before" of every wait that begins within this
# long of it. In a tight loop a reading is 7 us, but between two stages
# of a streamed batch on a many-core host it read 40 (the kernel sums a
# pressure file over every CPU's counters, cold; PERF.md, PR 54), twice
# a batch; a wait worth a record lasts LONG_WAIT_S, and counters a
# fifth of that older say the same of it. A constant, not an option.
_COUNTERS_FRESH_S = 0.05


def _host_counters() -> dict:
    """What tells a starved host from a runtime that held a ready
    result, read before a wait (at most ``_COUNTERS_FRESH_S`` before:
    ``StreamingIngest._run``) and again after one that ran long: the
    process's context switches (``nvcsw`` it gave the CPU up, ``nivcsw``
    it was taken off it), minor faults and system seconds
    (``getrusage``), and the machine's ``some`` stall totals in
    microseconds (``psi_<resource>_us``) where the kernel keeps them."""
    global _PRESSURE
    if _PRESSURE is None:
        found = []
        for name in ("cpu", "memory", "io"):
            try:
                found.append((f"psi_{name}_us", os.open(
                    f"/proc/pressure/{name}", os.O_RDONLY)))
            except OSError:
                pass
        _PRESSURE = found
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out = {"nvcsw": ru.ru_nvcsw, "nivcsw": ru.ru_nivcsw,
           "minflt": ru.ru_minflt, "stime_s": ru.ru_stime}
    for key, fd in _PRESSURE:
        try:    # "some avg10=.. avg60=.. avg300=.. total=<us>" first
            out[key] = int(os.pread(fd, 256, 0)
                           .split(b"total=", 2)[1].split()[0])
        except (OSError, IndexError, ValueError):
            pass
    return out


def _as_host_array(source):
    """Coerce a whole-source 2-D array-like (jax.Array included) to a
    host ndarray in ONE fetch; anything else (a chunk iterable) passes
    through untouched. Shared by run()'s validation and _rebatch so
    the two paths can never accept different source types — and so a
    device-resident source is never iterated row-by-row (one blocking
    D2H per segment)."""
    if not isinstance(source, np.ndarray) \
            and getattr(source, "ndim", None) == 2:
        return np.asarray(source)
    return source


def _rebatch(source, batch: int) -> Iterator[np.ndarray]:
    """Yield [<=batch, seg] host chunks from an array or an iterable of
    row chunks (a network receive loop hands arbitrary-sized pieces)."""
    source = _as_host_array(source)
    if isinstance(source, np.ndarray):
        for start in range(0, source.shape[0], batch):
            yield source[start:start + batch]
        return
    pending: list[np.ndarray] = []
    rows = 0
    for piece in source:
        piece = np.asarray(piece)
        if piece.ndim == 1:
            piece = piece[None]
        pending.append(piece)
        rows += piece.shape[0]
        while rows >= batch:
            buf = np.concatenate(pending, axis=0) if len(pending) > 1 \
                else pending[0]
            yield buf[:batch]
            rest = buf[batch:]
            pending = [rest] if rest.shape[0] else []
            rows = rest.shape[0]
    if rows:
        yield np.concatenate(pending, axis=0) if len(pending) > 1 \
            else pending[0]


class StreamingIngest:
    """See module doc. One instance per stream source; safe to reuse
    for consecutive runs (counters accumulate across runs).

    pipeline: the StoragePipeline whose fused program to drive.
    batch:    segments per device batch (the compiled shape).
    depth:    in-flight window — batches whose program is enqueued
              before the driver blocks on the oldest (2 = classic double
              buffering: one computing, one staged). It bounds device
              memory: the results of ``depth`` batches beside what the
              caller holds, and the rows of ``depth + 1`` (one more
              batch's put is asked for ahead of the wait; a batch's
              rows go with its result).
    program:  override the device program (fn(staged, ids) -> dict
              with "fragments"/"tags"; ``staged`` is what ``put``
              returned) — the mesh entry passes its shard_map'd step
              here.
    put / put_ids: override staging (default jax.device_put) — the
              mesh entry passes sharded placements. ``put`` receives
              the batch as the tuple of its ``batch * k`` linear rows
              (1-D uint8 views of the staged chunk).
    pool:     optional DevicePool (serve/pool.py) — device-aware
              placement: the driver derives its (program, put,
              put_ids) from ``pool.stream_entry``'s mesh over the
              pool's lanes, so each staged batch's sharded
              ``device_put`` fans the segment axis across every lane
              in one transfer. Explicit program/put overrides win;
              ``batch`` must be divisible by the lane count.
    engine:   optional SubmissionEngine to export stats through.
    tenant:   optional per-tenant accounting tag (obs/slo.py): with an
              attached engine carrying an SLO board, each staged batch
              is charged to this tenant under the ``stream`` class —
              the gateway ingest path's contribution to the same
              accounting its engine submits carry.
    """

    def __init__(self, pipeline, batch: int, *, depth: int = 2,
                 program=None, put=None, put_ids=None, stats=None,
                 engine=None, tenant: str | None = None, pool=None):
        if batch < 1 or depth < 1:
            raise ValueError(f"bad stream shape: batch={batch}, "
                             f"depth={depth}")
        if pool is not None and program is None:
            # device-aware placement: shard the staged batches over
            # the pool's lanes (the single-device default otherwise)
            entry = pool.stream_entry(pipeline, batch)
            program = entry["program"]
            put = put or entry["put"]
            put_ids = put_ids or entry["put_ids"]
        self.pipeline = pipeline
        self.batch = batch
        self.depth = depth
        self.stats = stats or StreamStats()
        self.tenant = tenant
        self._program = program
        self._put = put or jax.device_put
        self._put_ids = put_ids or self._put
        self._engine = engine
        if engine is not None:
            engine.attach_stream(self.stats)

    def detach(self) -> None:
        """Stop contributing to the attached engine's merged
        cess_engine_stream_* gauges (call when this stream source is
        done; idempotent, no-op without an engine). Construct ONE
        driver per long-lived source rather than one per request —
        attachments are summed, not replaced."""
        if self._engine is not None:
            self._engine.detach_stream(self.stats)
            self._engine = None

    # ------------------------------------------------------------------
    def run(self, segments, fragment_ids=None) -> Iterator[dict]:
        """Stream host segments through the device; yield per-batch
        ``{"fragments", "tags", "rows"}`` dicts of DEVICE arrays
        (ragged tail already sliced to its real rows). Each yielded
        batch is complete on device (the in-flight throttle blocks
        before yielding), so consumers never observe partial results.

        segments: [N, segment_size] uint8 host array, or an iterable
        of row chunks (rebatched internally). fragment_ids: optional
        [N, k+m] or [N, k+m, 2] array (requires an array source); None
        uses the bench/demo arange over the global row index — exactly
        the default the direct path would use over the whole array.

        Input validation happens HERE, at call time (run() is a plain
        method delegating to an inner generator), so a bad call fails
        at its own site rather than at the consumer's first next().
        """
        if fragment_ids is not None:
            segments = _as_host_array(segments)
            if not isinstance(segments, np.ndarray) \
                    or segments.ndim != 2:
                # a generator/chunked source cannot be lined up with a
                # pre-shaped id array — reject loudly instead of the
                # opaque shape errors np coercion would produce
                raise ValueError(
                    "fragment_ids requires an [N, segment_size] array "
                    "segment source, not a chunked/iterator source")
            fragment_ids = np.asarray(fragment_ids)
            if fragment_ids.shape[0] != segments.shape[0]:
                raise ValueError("fragment_ids rows != segments rows")
        return self._run(segments, fragment_ids)

    def _tracer_now(self):
        """Tracer serving this run: the attached engine's pinned one,
        else the process-armed tracer (obs.trace), else None."""
        if self._engine is not None and self._engine.tracer is not None:
            return self._engine.tracer
        return trace.armed_tracer()

    def _escaped(self, e, bspan, bt0: float, rows: int) -> None:
        """A staging/dispatch failure (fault injection, OOM) must still
        land the batch span in the ring, error attached — a traced
        chaos run shows WHICH batch died, not a silent hole in the
        export — and burn the stream SLO's error budget like any engine
        failure (_observe_failure): a stream that died must not scrape
        as a clean SLO."""
        if bspan is not trace.NOOP_SPAN:
            bspan.set(error=repr(e)).finish()
        eng = self._engine
        if eng is not None and eng.slo is not None:
            eng.slo.observe("stream", time.perf_counter() - bt0,
                            ok=False, tenant=self.tenant, rows=rows)
        # black-box journal: the exception is about to escape the
        # stream driver — an incident trigger
        _flight.note("stream", "escape", error=repr(e))

    def _run(self, segments, fragment_ids) -> Iterator[dict]:
        cfg = self.pipeline.config
        rows = cfg.k + cfg.m
        program = self._program or self.pipeline.fused_program()
        st = self.stats
        t_run = time.perf_counter()
        # (result, real rows, the batch's rows on the device, its seq):
        # a batch's rows are let go of with its result, never while its
        # program may still be pending
        inflight: collections.deque = collections.deque()
        run_span = bspan = trace.NOOP_SPAN
        batches = stalls = 0
        # the stages of one turn of the loop, merged once a batch; and
        # what a wait had in flight: puts asked for, programs enqueued,
        # results handed out
        sink: dict = {}
        n_put = n_run = n_out = 0

        read_at, reading = -1.0, None

        def counters() -> dict:
            """The host's counters as a wait's "before": the last
            reading, a new one once that is ``_COUNTERS_FRESH_S`` old."""
            nonlocal read_at, reading
            now = time.perf_counter()
            if now - read_at > _COUNTERS_FRESH_S:
                read_at, reading = now, _host_counters()
            return reading

        def waited(seq, before) -> dict:
            """A long wait's context (built after it, and only then):
            the counters' change over the wait and the at most
            ``_COUNTERS_FRESH_S`` between the reading and its start."""
            nonlocal read_at, reading
            read_at, reading = time.perf_counter(), _host_counters()
            return {"seq": seq, "results_in_flight": n_run - n_out,
                    "puts_in_flight": n_put - n_out,
                    **{k: round(reading[k] - v, 6)
                       for k, v in before.items() if k in reading}}

        def drain_one():
            nonlocal stalls, n_out
            out, real, _, seq = inflight.popleft()
            before = counters()
            with trace.stage("stream.stall", sink, parent=run_span,
                             seq=seq) as stalled:
                jax.block_until_ready(out["tags"])
            stall = stalled.seconds
            st.stall_s += stall
            st.long_waits.observe("stream.stall", stalled.t0, stall,
                                  waited, seq, before)
            n_out += 1
            stalls += 1
            if run_span is not trace.NOOP_SPAN:
                run_span.event("stall", s=round(stall, 6))
            if real < self.batch:
                out = {k: v[:real] for k, v in out.items()}
            st.bytes_out += out["fragments"].nbytes + out["tags"].nbytes
            out["rows"] = real
            return out

        def handed():
            """The oldest batch, waited for and handed to the consumer:
            while the driver stands at the yield the time is the
            consumer's (``consumer_s``), also when it never comes back."""
            out = drain_one()
            t_yield = time.perf_counter()
            try:
                yield out
            finally:
                st.consumer_s += time.perf_counter() - t_yield

        try:
            tracer = self._tracer_now()
            if tracer is not None:
                run_span = tracer.start("stream.run", sys="stream",
                                        batch=self.batch,
                                        depth=self.depth)
            seg_off = 0
            chunks = _rebatch(segments, self.batch)
            while True:
                # a batch's number in this driver's life: the ``seq`` of
                # its five stages, in a trace and under a tracer
                seq = st.batches
                # host-side staging of the next batch: the source's
                # next rows, contiguous, padded, with their ids
                with trace.stage("stream.stage", sink, parent=run_span,
                                 seq=seq) as staging:
                    chunk = next(chunks, None)
                    if chunk is not None:
                        chunk = np.ascontiguousarray(chunk,
                                                     dtype=np.uint8)
                        real = chunk.shape[0]
                        pad = 0
                        if real < self.batch:  # ragged tail: pad, reuse
                            chunk = _pad_axis0(chunk, self.batch)
                            pad = self.batch - real
                            st.padded_segments += pad
                        if fragment_ids is None:
                            ids = np.arange(seg_off * rows,
                                            (seg_off + self.batch) * rows,
                                            dtype=np.int32)
                        else:
                            ids = _pad_axis0(
                                fragment_ids[seg_off:seg_off + real],
                                self.batch)
                st.stage_s += staging.seconds
                if chunk is None:
                    break
                # the gate: the put is asked for BEFORE the wait for the
                # oldest result, once the put two before it has arrived:
                # two puts are on the link at any time, as many as cross
                # it fastest (tools/link_probe.py), and it never stands
                # still while the host waits for a result. Asked for
                # after that wait, one put crossed at a time and the
                # RS(2,1) ingest's device idled a third of every batch
                # (PERF.md, PR 50). One runtime call for all the put's
                # rows: its last row alone is not the last to arrive.
                # (At depth 1 the put two before is out with its result:
                # nothing to wait for.)
                if len(inflight) >= 2:
                    before = counters()
                    with trace.stage("stream.gate", sink, parent=run_span,
                                     seq=seq) as gate:
                        jax.block_until_ready(inflight[-2][2])
                    st.gate_s += gate.seconds
                    st.long_waits.observe("stream.gate", gate.t0,
                                          gate.seconds, waited, seq, before)
                bspan = trace.NOOP_SPAN if tracer is None \
                    else tracer.start("stream.batch", sys="stream",
                                      parent=run_span, rows=real,
                                      pad=pad, seq=seq)
                bt0 = time.perf_counter()
                try:
                    with trace.stage("stream.put", sink, parent=bspan,
                                     seq=seq) as put:
                        faults.inject("stream.h2d")   # chaos: staging
                        rows_up = linear_rows(chunk, cfg.k)
                        dev = self._put(rows_up)
                        ids_dev = self._put_ids(ids)
                except BaseException as e:
                    self._escaped(e, bspan, bt0, real)
                    raise
                n_put += 1
                # the window, enforced before the program is enqueued
                # and its result allocated: ``depth`` programs' batches
                # at most (depth=2 = one computing + one staged), and
                # this batch's rows ahead of them. A program is
                # enqueued before any later put is asked for: behind the
                # next batch's put (a result fewer on the device for the
                # rows more) every cell ran as under the old order
                # (PERF.md, PR 50)
                while len(inflight) >= self.depth:
                    yield from handed()
                try:
                    with trace.stage("stream.dispatch", sink,
                                     parent=bspan, seq=seq) as launch:
                        faults.inject("stream.dispatch")  # chaos: launch
                        out = program(dev, ids_dev)
                except BaseException as e:
                    self._escaped(e, bspan, bt0, real)
                    raise
                n_run += 1
                h2d, dispatch = put.seconds, launch.seconds
                # everything a batch counts, it counts here, with
                # ``batches``: no snapshot sees a put without its batch
                st.h2d_s += h2d
                st.linear_puts += 1
                st.put_arrays += len(rows_up)
                st.direct_rows += bool(getattr(
                    program, "direct_rows", lambda staged: False)(dev))
                # gauge: the devices this batch was placed over
                st.lanes = len(jax.tree.leaves(dev)[0]
                               .sharding.device_set)
                st.dispatch_s += dispatch
                # what the batch cost this thread: its gate, its put,
                # the stall that let its program in, its dispatch (the
                # staging is the source's; an enqueue alone says nothing)
                st.hist.observe(sum(
                    acc[1] for name, acc in sink.items()
                    if name != "stream.stage"))
                st.add_stages(sink)
                # SLO/tenant feed (obs/slo.py): streamed batches ride
                # the attached engine's board under the "stream" class
                # (targetable like any op class); one attribute chain
                # + None check when no board is configured
                eng = self._engine
                if eng is not None and eng.slo is not None:
                    eng.slo.observe("stream", h2d + dispatch,
                                    tenant=self.tenant, rows=real)
                # continuous-profiling feed (obs/profile.py): the
                # ragged tail's pad rides the SAME PadLedger as the
                # engine's bucket padding — one end-to-end pad bill
                if eng is not None and eng.profile is not None:
                    eng.profile.on_stream(
                        batch=self.batch, rows=real,
                        nbytes=real * cfg.segment_size,
                        h2d_s=h2d, dispatch_s=dispatch)
                bspan.finish(h2d_s=round(h2d, 6),
                             dispatch_s=round(dispatch, 6))
                st.batches += 1
                batches += 1
                st.segments += real
                st.bytes_in += real * cfg.segment_size
                seg_off += self.batch
                inflight.append((out, real, dev, seq))
            while inflight:
                yield from handed()
                st.add_stages(sink)      # a stall of the final drain
        finally:
            # a consumer that stopped between a batch's put and its
            # program: the batch's span still lands (no-op otherwise)
            bspan.finish()
            # what the last turns left in the sink: the staging that
            # found the source dry, the final drain's stalls, a turn
            # that was cut short
            st.add_stages(sink)
            st.wall_s += time.perf_counter() - t_run
            if run_span is not trace.NOOP_SPAN:
                run_span.finish(batches=batches, stalls=stalls)

    def ingest(self, segments, fragment_ids=None) -> dict:
        """Run the whole stream and concatenate the per-batch device
        results — the convenience form for callers that want the full
        ``forward``-shaped output without managing the generator."""
        outs = list(self.run(segments, fragment_ids))
        if not outs:
            raise ValueError("empty segment stream")
        return {"fragments": jnp.concatenate([o["fragments"]
                                              for o in outs], axis=0),
                "tags": jnp.concatenate([o["tags"] for o in outs],
                                        axis=0)}
