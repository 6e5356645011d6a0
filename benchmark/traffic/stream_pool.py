"""Traffic kind ``stream_pool``: the ``stream`` kind's closed loop of one
producer, driven through a DevicePool: ``StreamingIngest(pipe, batch,
depth=depth, pool=DevicePool(n=lanes))``. Each staged batch goes to the
pool's lanes in one sharded ``device_put`` and through one ``shard_map``
program, ``batch / lanes`` segments a lane. An operation is "the next
batch came out complete on every lane"; its work is the user bytes of
the whole batch.

Parameters (workloads/<cell>.json, ``traffic``): those of ``stream`` plus
``lanes`` (devices in the pool; ``batch`` divides by it). The data, the
batches kept for the check, the check itself and the controls are
``traffic/stream.py``'s, loaded from its file as a copy of its own.
"""
from __future__ import annotations

import importlib.util
import os

# a private copy of the stream driver: its loop (warm, op, drain) calls
# its module's ``_run``, which is pointed at the pooled one below, so the
# keep-for-check logic and the check stay the one-chip cell's own
_spec = importlib.util.spec_from_file_location(
    "bench_traffic_stream_for_pool",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "stream.py"))
_stream = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_stream)

counters, check, close, CONTROLS = (_stream.counters, _stream.check,
                                    _stream.close, _stream.CONTROLS)
warm, op, drain = _stream.warm, _stream.op, _stream.drain


def setup(ctx) -> None:
    import jax

    _stream.setup(ctx)
    lanes = ctx.traffic["lanes"]
    if ctx.rehearse:
        # run.py reaches JAX before this file is loaded, so a rehearsal
        # has the devices it was started with (one, unless XLA_FLAGS
        # forces more); on the chip run.py has refused fewer than `chips`
        lanes = min(lanes, len(jax.devices()))
    ctx.lanes = lanes


def _run(ctx):
    if ctx.ingest is None:
        from cess_tpu.serve.pool import DevicePool
        from cess_tpu.serve.stream import StreamingIngest

        t = ctx.traffic
        pool = DevicePool(n=ctx.lanes)
        seams = {}
        if ctx.fault is not None or ctx.spans.enabled:
            # StreamingIngest ignores pool= once program= is given: the
            # spans and the controls go around the pool's own triple, so
            # traced and untraced runs drive the same program
            seams = pool.stream_entry(ctx.pipe, t["batch"])
            if ctx.fault is not None:
                seams["program"] = ctx.fault(seams["program"])
            if ctx.spans.enabled:
                seams["program"] = ctx.spans.wrap("stream.dispatch",
                                                  seams["program"])
                seams["put"] = ctx.spans.wrap("stream.device_put",
                                              seams["put"])
        ctx.ingest = StreamingIngest(ctx.pipe, batch=t["batch"],
                                     depth=t["depth"], pool=pool, **seams)
    return ctx.ingest.run(_stream._source(ctx))


_stream._run = _run
