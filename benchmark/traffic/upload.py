"""Traffic kind ``upload``: a closed loop of one client that uploads whole
files through the real ``OssGateway.upload(owner, bucket, name, data)`` with
``StoragePipeline(cfg, podr2_key=key, engine=eng)``: encode through the
engine, fetch, SHA-256 of each fragment, tag through the engine, fetch, the
declaration (taken by the recording stand-in). After each upload the
gateway's stores are set aside and replaced by empty ones; after the window
every stored fragment is hashed against its key, every declaration is read
back, and a sample's fragments and tags are compared with the reference.

Parameters: pool_files, file_segments, check_files (uploads compared with
the reference), check_tags (tags compared in each of them).
"""
from __future__ import annotations

import importlib.util
import os
import time

import numpy as np

import bench_lib
from reference import podr2_ref, rs_ref

_HERE = os.path.dirname(os.path.abspath(__file__))


def _recording_node():
    spec = importlib.util.spec_from_file_location(
        "bench_recording_node", os.path.join(_HERE, "_recording_node.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.RecordingNode()


def setup(ctx) -> None:
    from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
    from cess_tpu.node.offchain import OssGateway
    from cess_tpu.ops import podr2

    c, t = ctx.config, ctx.traffic
    ctx.key_seed = bench_lib.key_seed(ctx)
    size = t["file_segments"] * c["segment_size"]
    raw = bench_lib.seeded_bytes(bench_lib.sub_seed(ctx.seed, 2),
                                 t["pool_files"] * size)
    ctx.files = [raw[i * size:(i + 1) * size].tobytes()
                 for i in range(t["pool_files"])]
    key = podr2.Podr2Key.generate(ctx.key_seed)
    ctx.engine = bench_lib.make_engine(ctx, key)
    cfg = PipelineConfig(k=c["k"], m=c["m"], segment_size=c["segment_size"])
    ctx.pipe = StoragePipeline(cfg, podr2_key=key, engine=ctx.engine)
    if ctx.spans.enabled:
        # timing shims on the pipeline handed to the gateway
        for name in ("encode_step", "tag_step"):
            setattr(ctx.pipe, name, ctx.spans.wrap(
                "pipeline." + name, getattr(ctx.pipe, name)))
    ctx.node = _recording_node()
    ctx.gateway = OssGateway(ctx.node, "gw", ctx.pipe)
    ctx.rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 3))
    ctx.uploads = []          # (file index, file hash, fragments, tags)
    ctx.fault = None


def _upload(ctx, i: int) -> dict:
    gw = ctx.gateway
    t0 = time.perf_counter()
    with ctx.spans.span("gateway.upload"):
        fh = gw.upload("alice", "bench", f"file-{i}", ctx.files[i])
    rec = bench_lib.op_record(t0, user_bytes=len(ctx.files[i]),
                              index=len(ctx.uploads))
    frags, tags = gw.fragment_store, gw.tag_store
    gw.fragment_store, gw.tag_store = {}, {}     # host memory stays flat
    if ctx.fault is not None:
        ctx.fault(frags, tags)
    ctx.uploads.append((i, fh, frags, tags))
    return rec


def warm(ctx) -> None:
    with ctx.spans.span("warm"):
        for _ in range(2):
            _upload(ctx, 0)
    ctx.uploads.clear()
    ctx.node.extrinsics.clear()


def op(ctx):
    return _upload(ctx, int(ctx.rng.integers(len(ctx.files))))


def drain(ctx) -> list:
    return []


def counters(ctx) -> dict:
    return {"engine": bench_lib.engine_counters(ctx.engine)}


def check(ctx, ops) -> list[dict]:
    c, t = ctx.config, ctx.traffic
    k, rows = c["k"], c["k"] + c["m"]
    bad_hash = bad_decl = frag_diff = tag_diff = n_hashed = 0
    for (i, fh, frags, tags), (_, call, args) in zip(ctx.uploads,
                                                     ctx.node.extrinsics):
        for h, data in frags.items():
            bad_hash += bench_lib.sha256(data) != h
            n_hashed += 1
        named = [h for _, hs in args[1] for h in hs]
        want = bench_lib.sha256(b"".join(named))
        bad_decl += not (call == "file_bank.upload_declaration"
                         and args[0] == fh == want
                         and set(named) == set(frags) == set(tags)
                         and len(named) == t["file_segments"] * rows
                         and args[3] == len(ctx.files[i]))
    bad_decl += abs(len(ctx.uploads) - len(ctx.node.extrinsics))
    # a sample against the plain reference, the window's last upload in it
    done = ctx.uploads
    sample = bench_lib.draw_sample(ctx.seed, len(done), t["check_files"],
                                   len(done) - 1)
    codec = rs_ref.ReferenceCodec(k, c["m"])
    rng = np.random.default_rng(bench_lib.sub_seed(ctx.seed, 4))
    with podr2_ref.on_cpu():
        key = podr2_ref.generate_key(ctx.key_seed)
    n_tag = 0
    for j in sample:
        i, fh, frags, tags = done[j]
        segs = np.frombuffer(ctx.files[i], dtype=np.uint8).reshape(
            t["file_segments"], k, -1)
        want = codec.encode(segs)                      # [S, rows, n]
        hashes = [[bench_lib.sha256(want[s, r]) for r in range(rows)]
                  for s in range(t["file_segments"])]
        for s in range(t["file_segments"]):
            for r in range(rows):
                got = frags.get(hashes[s][r])
                frag_diff += want[s, r].size if got is None else \
                    bench_lib.n_differ(np.frombuffer(got, np.uint8),
                                       want[s, r])
        for _ in range(t["check_tags"]):
            s, r = int(rng.integers(t["file_segments"])), \
                int(rng.integers(rows))
            h = hashes[s][r]
            with podr2_ref.on_cpu():
                ref = podr2_ref.tag_fragment(
                    key, podr2_ref.fragment_id_from_hash(h), want[s, r])
            tag_diff += ref.size if h not in tags else \
                bench_lib.n_differ(tags[h], ref)
            n_tag += 1
    ctx.say(info="check", uploads=len(ctx.uploads),
            fragments_hashed=n_hashed, uploads_compared=sample,
            tags_compared=n_tag)
    return [{"what": "stored fragments that do not hash to their key",
             "value": bad_hash, "limit": 0},
            {"what": "uploads whose recorded declaration does not name "
                     "the stored fragments", "value": bad_decl, "limit": 0},
            {"what": "uploads compared with the reference (none: 1)",
             "value": 0 if sample else 1, "limit": 0},
            {"what": "fragments differ from reference RS (bytes)",
             "value": frag_diff, "limit": 0},
            {"what": "tags differ from reference PoDR2 (words)",
             "value": tag_diff, "limit": 0},
            *bench_lib.engine_comparisons(ctx.engine)]


def close(ctx) -> None:
    if getattr(ctx, "engine", None) is not None:
        ctx.engine.close()


# -- tests only ------------------------------------------------------------
def _flip_stored(ctx):
    """One byte of one stored fragment of every upload altered."""
    def fault(frags, tags):
        h = next(iter(frags))
        frags[h] = frags[h][:-1] + bytes([frags[h][-1] ^ 1])
    ctx.fault = fault


def _stale_tags(ctx):
    """The degraded guarantee: every fragment's last tag zeroed (a
    shortened tag pass)."""
    def fault(frags, tags):
        for h in tags:
            tags[h] = np.array(tags[h])
            tags[h][-1] = 0
    ctx.fault = fault


CONTROLS = {"flip_stored": _flip_stored, "stale_tags": _stale_tags}
