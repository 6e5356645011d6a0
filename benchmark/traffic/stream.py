"""Traffic kind ``stream``: a closed loop of one producer that cycles a
seeded host corpus through StreamingIngest (the fused encode+tag
program). An operation is "the next batch came out complete on the
device"; its work is the user bytes of that batch.

Parameters (workloads/<cell>.json, ``traffic``): corpus_segments, batch,
depth, check_segments (fragments of how many segments are compared),
check_tags (tags of how many fragments are compared).
"""
from __future__ import annotations

import time

import numpy as np

import bench_lib
from reference import podr2_ref, rs_ref


def setup(ctx) -> None:
    from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
    from cess_tpu.ops import podr2, target

    c, t = ctx.config, ctx.traffic
    if ctx.on_chip and target.interpret():
        raise RuntimeError("pallas kernels would be interpreted")
    ctx.key_seed = bench_lib.key_seed(ctx)
    with ctx.spans.span("make_corpus"):
        ctx.corpus = bench_lib.seeded_bytes(
            bench_lib.sub_seed(ctx.seed, 2),
            t["corpus_segments"] * c["segment_size"]).reshape(
                t["corpus_segments"], c["segment_size"])
    cfg = PipelineConfig(k=c["k"], m=c["m"], segment_size=c["segment_size"])
    ctx.pipe = StoragePipeline(cfg,
                               podr2_key=podr2.Podr2Key.generate(ctx.key_seed))
    ctx.ingest = None          # built at first use, after any control
    ctx.fault = None
    ctx.gen = None
    ctx.n_out = 0
    # which finished batches are kept (on the device) for the check: the
    # first of the window and a later one drawn from the seed
    ctx.keep_at = {0, 1 + bench_lib.sub_seed(ctx.seed, 3) % 64}
    ctx.kept = []


def _source(ctx):
    """Corpus batches, cycled until the window's deadline."""
    b = ctx.traffic["batch"]
    n = ctx.corpus.shape[0] // b
    j = 0
    while ctx.deadline is None or time.perf_counter() < ctx.deadline:
        yield ctx.corpus[(j % n) * b:(j % n + 1) * b]
        j += 1
        if ctx.deadline is None and j >= ctx.traffic["depth"] + 1:
            return


def _run(ctx):
    if ctx.ingest is None:
        import jax

        from cess_tpu.serve.stream import StreamingIngest

        t = ctx.traffic
        program = put = None          # the driver's own defaults
        if ctx.fault is not None:
            program = ctx.fault(ctx.pipe.fused_program())
        if ctx.spans.enabled:
            # the driver's own seams (program=, put=) carry the spans
            program = ctx.spans.wrap(
                "stream.dispatch", program or ctx.pipe.fused_program())
            put = ctx.spans.wrap("stream.device_put", jax.device_put)
        ctx.ingest = StreamingIngest(ctx.pipe, batch=t["batch"],
                                     depth=t["depth"], program=program,
                                     put=put)
    return ctx.ingest.run(_source(ctx))


def warm(ctx) -> None:
    import jax

    with ctx.spans.span("warm"):
        for out in _run(ctx):                 # depth + 1 batches, no deadline
            jax.block_until_ready(out["fragments"])


def op(ctx):
    if ctx.gen is None:
        ctx.gen = _run(ctx)
    t0 = time.perf_counter()
    with ctx.spans.span("stream.next_batch"):
        out = next(ctx.gen, None)
    if out is None:
        return None
    rec = bench_lib.op_record(
        t0, user_bytes=out["rows"] * ctx.config["segment_size"],
        index=ctx.n_out)
    if ctx.n_out in ctx.keep_at or not ctx.kept:
        ctx.kept.append((ctx.n_out, out))
    ctx.n_out += 1
    return rec


def drain(ctx) -> list:
    recs = []
    while (rec := op(ctx)) is not None:
        recs.append(rec)
    return recs


def counters(ctx) -> dict:
    return {"stream": ctx.ingest.stats.raw()}      # after warm: it exists


def check(ctx, ops) -> list[dict]:
    """Kept batches of the window against the plain reference: every
    fragment of ``check_segments`` segments (NumPy RS), the tags of
    ``check_tags`` fragments (jnp on the CPU device), drawn from the seed;
    and that the systematic rows are the user's bytes."""
    c, t = ctx.config, ctx.traffic
    k, rows = c["k"], c["k"] + c["m"]
    b = t["batch"]
    n_corpus = ctx.corpus.shape[0] // b
    kept = ctx.kept
    rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 4))
    frag_diff = tag_diff = sys_diff = 0
    n_frag = n_tag = 0
    codec = rs_ref.ReferenceCodec(k, c["m"])
    with podr2_ref.on_cpu():
        key = podr2_ref.generate_key(ctx.key_seed)
    for index, out in kept:
        frags = np.asarray(out["fragments"])            # [b, rows, n]
        tags = np.asarray(out["tags"])
        src = ctx.corpus[(index % n_corpus) * b:(index % n_corpus + 1) * b]
        sys_diff += bench_lib.n_differ(
            frags[:, :k].reshape(b, -1), src)
        for s in rng.choice(b, min(t["check_segments"], b), replace=False):
            want = codec.encode(src[s].reshape(k, -1))
            frag_diff += bench_lib.n_differ(frags[s], want)
            n_frag += rows
        for _ in range(t["check_tags"]):
            s, row = int(rng.integers(b)), int(rng.integers(rows))
            # the stream's default ids: the global row index
            fid = np.int32((index * b + s) * rows + row)
            with podr2_ref.on_cpu():
                want = podr2_ref.tag_fragment(key, fid, frags[s, row])
            tag_diff += bench_lib.n_differ(tags[s, row], want)
            n_tag += 1
    ctx.say(info="check", batches_kept=[i for i, _ in kept],
            fragments_compared=n_frag, tags_compared=n_tag)
    return [
        {"what": "batches of the window kept for the check (missing)",
         "value": 0 if kept else 1, "limit": 0},
        {"what": "systematic rows differ from the user's bytes (bytes)",
         "value": sys_diff, "limit": 0},
        {"what": "fragments differ from reference RS (bytes)",
         "value": frag_diff, "limit": 0},
        {"what": "tags differ from reference PoDR2 (words)",
         "value": tag_diff, "limit": 0}]


def close(ctx) -> None:
    pass


# -- tests only: the timed path broken underneath ------------------------
def _flip_parity(program):
    """Every parity fragment comes out with its first byte altered."""
    def broken(dev, ids):
        out = dict(program(dev, ids))
        f = out["fragments"]
        out["fragments"] = f.at[:, -1, 0].set(f[:, -1, 0] ^ 1)
        return out
    return broken


def _stale_tags(program):
    """The degraded guarantee: tags left at the PRF's value for the last
    block of every fragment (a shortened tag pass)."""
    def broken(dev, ids):
        out = dict(program(dev, ids))
        t = out["tags"]
        out["tags"] = t.at[:, :, -1, :].set(0)
        return out
    return broken


def _install(fault):
    def install(ctx):
        ctx.fault = fault
    return install


CONTROLS = {"flip_parity": _install(_flip_parity),
            "stale_tags": _install(_stale_tags)}
