"""OssGateway.upload treats the systematic rows as host data (ISSUE 30):
data rows and segments are hashed, and the data rows stored, from the bytes
the user handed in, on the gateway's worker threads, while the device
encodes; only the parity rows are fetched; each fragment's ``bytes`` are
made once.

What must not change is the result. Every case compares an upload's stores,
file hash and recorded declaration with a plain serial reference written
here (NumPy RS, hashlib, the direct ``tag_step``): the parent's upload,
step by step.
"""
import hashlib
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from cess_tpu import obs
from cess_tpu.chain.file_bank import UserBrief
from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
from cess_tpu.node import offchain
from cess_tpu.node.offchain import OssGateway
from cess_tpu.ops import podr2
from cess_tpu.ops.rs_ref import ReferenceCodec
from cess_tpu.serve import AdmissionPolicy, make_engine

FRAG = 1024               # bytes per fragment -> 2 PoDR2 blocks
GEOMETRIES = [(2, 1), (4, 8), (3, 3)]
# file sizes in segments: one, several, and two that need zero-padding
SIZES = {"one": 1.0, "several": 3.0, "padded": 1.5, "ragged": 0.01}
JOIN_S = 120              # bounds a hang; nothing here waits that long


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    obs.disarm()


@pytest.fixture(scope="module")
def pkey():
    return podr2.Podr2Key.generate(30)


@pytest.fixture(scope="module")
def pipes(pkey):
    """(k, m, engine?) -> pipeline; engines are built once a geometry
    and closed with the module."""
    engines, built = [], {}

    def get(k, m, engine):
        if (k, m, engine) not in built:
            cfg = PipelineConfig(k=k, m=m, segment_size=k * FRAG)
            eng = None
            if engine:
                eng = make_engine(k, m, rs_backend="jax", podr2_key=pkey,
                                  policy=AdmissionPolicy(max_delay=0.002))
                engines.append(eng)
            built[k, m, engine] = StoragePipeline(cfg, podr2_key=pkey,
                                                  engine=eng)
        return built[k, m, engine]

    yield get
    for eng in engines:
        eng.close()


class _Node:
    def __init__(self):
        self.extrinsics = []

    def submit_extrinsic(self, *call):
        self.extrinsics.append(call)


@pytest.fixture()
def gateway(pipes):
    made = []

    def make(k, m, engine):
        made.append(OssGateway(_Node(), "gw", pipes(k, m, engine)))
        return made[-1]

    yield make
    for gw in made:
        gw.close()


def _file(k, size, seed=0):
    n = max(1, int(size * k * FRAG)) + (7 if size % 1 else 0)
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _sha(data) -> bytes:
    return hashlib.sha256(data).digest()


def _reference(pipes, pkey, k, m, owner, bucket, name, data):
    """The upload, serially and plainly: (fragment_store, tag_store,
    file hash, declaration, tag bytes), stores in the order the gateway
    fills them."""
    seg = k * FRAG
    padded = data + bytes(-len(data) % seg)
    segs = np.frombuffer(padded, np.uint8).reshape(-1, k, FRAG)
    coded = ReferenceCodec(k, m).encode(segs)
    hashes = [[_sha(row.tobytes()) for row in s] for s in coded]
    ids = np.array([[podr2.fragment_id_from_hash(h) for h in hs]
                    for hs in hashes], dtype=np.uint32)
    tags = np.asarray(pipes(k, m, False).tag_step(jnp.asarray(coded),
                                                  jnp.asarray(ids)))
    frag_store, tag_store = {}, {}
    for i, hs in enumerate(hashes):
        for j, h in enumerate(hs):
            frag_store[h] = coded[i, j].tobytes()
            tag_store[h] = tags[i, j]
    seg_list = [(_sha(padded[i * seg:(i + 1) * seg]), tuple(hs))
                for i, hs in enumerate(hashes)]
    file_hash = _sha(b"".join(h for hs in hashes for h in hs))
    declaration = ("gw", "file_bank.upload_declaration", file_hash,
                   seg_list, UserBrief(owner, name, bucket), len(data))
    return frag_store, tag_store, file_hash, declaration, tags.nbytes


def _same_stores(gw, frag_store, tag_store):
    assert list(gw.fragment_store.items()) == list(frag_store.items())
    assert all(type(v) is bytes for v in gw.fragment_store.values())
    assert list(gw.tag_store) == list(tag_store)
    for h, tag in tag_store.items():
        np.testing.assert_array_equal(gw.tag_store[h], tag)


@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("engine", [False, True],
                         ids=["direct", "engine"])
@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_upload_equals_the_serial_reference(pipes, pkey, gateway, k, m,
                                            engine, size):
    data = _file(k, SIZES[size], seed=k + m)
    gw = gateway(k, m, engine)
    got = gw.upload("alice", "photos", "f.bin", data)
    frag_store, tag_store, file_hash, declaration, tag_bytes = _reference(
        pipes, pkey, k, m, "alice", "photos", "f.bin", data)
    assert got == file_hash
    assert gw.node.extrinsics == [declaration]
    _same_stores(gw, frag_store, tag_store)
    # the mechanism engaged: parity and tags came down, no data row did
    segs = len(declaration[3])
    counters = gw.counters()
    stage_count = counters.pop("stage_count")
    stage_seconds = counters.pop("stage_seconds")
    assert counters == {
        "uploads": 1, "rows_from_host": k * segs, "rows_fetched": m * segs,
        "bytes_fetched": m * segs * FRAG + tag_bytes,
        "hash_jobs": segs * (k + m + 1)}
    # every stage of the upload is timed: once an upload on its thread
    # (gateway.fetch twice), once a job on the workers'
    assert stage_count == {
        "offchain.upload": 1, "gateway.encode": 1, "gateway.encode.jobs": 1,
        "gateway.encode.put": 1, "gateway.encode.step": 1,
        "gateway.fetch": 2, "gateway.hash": 1, "gateway.tag": 1,
        "gateway.store": 1, "gateway.declare": 1,
        "gateway.worker.copy": segs * (k + m),
        "gateway.worker.hash": segs * (k + m + 1)}
    assert set(stage_seconds) == set(stage_count)
    assert all(s >= 0.0 for s in stage_seconds.values())


@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_data_rows_never_come_from_the_device(pipes, pkey, gateway, k, m,
                                              monkeypatch):
    """With every data row inverted in the encode result on the device,
    the stored fragments and their keys are still the user's bytes."""
    pipe = pipes(k, m, False)
    gw = gateway(k, m, False)
    encode = pipe.encode_step

    def poisoned(segments, tenant=None):
        out = encode(segments, tenant=tenant)
        return out.at[:, :k].set(~out[:, :k])

    monkeypatch.setattr(pipe, "encode_step", poisoned)
    data = _file(k, 3.0, seed=9)
    gw.upload("alice", "photos", "f.bin", data)
    frag_store, _, _, declaration, _ = _reference(
        pipes, pkey, k, m, "alice", "photos", "f.bin", data)
    assert list(gw.fragment_store.items()) == list(frag_store.items())
    assert gw.node.extrinsics == [declaration]


@pytest.mark.parametrize("failing", ["first", "middle", "last"])
def test_a_failed_hash_fails_the_upload_and_stores_nothing(
        pipes, pkey, gateway, monkeypatch, failing):
    k, m = 2, 1
    gw = gateway(k, m, True)
    data = _file(k, 3.0, seed=3)
    jobs = 3 * (k + m + 1)
    at = {"first": 0, "middle": jobs // 2, "last": jobs - 1}[failing]
    seen, lock = [], threading.Lock()

    def failing_hash(blob):
        with lock:
            seen.append(threading.current_thread().name)
            mine = len(seen) - 1
        if mine == at:
            raise OSError("hash worker lost its memory")
        return _sha(blob)

    monkeypatch.setattr(offchain, "fragment_hash", failing_hash)
    with pytest.raises(OSError, match="lost its memory"):
        gw.upload("alice", "photos", "f.bin", data)
    assert all(name.startswith("gateway-hash-gw") for name in seen)
    assert gw.fragment_store == {} and gw.tag_store == {}
    assert gw.node.extrinsics == []
    assert gw.counters()["uploads"] == 0
    # and the gateway is whole: the next upload is the reference's
    monkeypatch.undo()
    frag_store, tag_store, file_hash, declaration, _ = _reference(
        pipes, pkey, k, m, "alice", "photos", "f.bin", data)
    assert gw.upload("alice", "photos", "f.bin", data) == file_hash
    assert gw.node.extrinsics == [declaration]
    _same_stores(gw, frag_store, tag_store)


@pytest.mark.parametrize("engine", [False, True],
                         ids=["direct", "engine"])
def test_two_uploads_from_two_threads_do_not_mix_their_rows(
        pipes, pkey, gateway, engine):
    k, m = 2, 1
    gw = gateway(k, m, engine)
    files = {name: _file(k, 3.0, seed=seed)
             for name, seed in (("a.bin", 11), ("b.bin", 12))}
    start = threading.Barrier(len(files))
    got, errors = {}, []

    def client(name):
        try:
            start.wait(JOIN_S)
            for _ in range(4):
                got[name] = gw.upload(name, "photos", name, files[name])
        except BaseException as e:     # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(name,))
               for name in files]
    for t in threads:
        t.start()
    for t in threads:
        t.join(JOIN_S)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    want_frags, want_tags, want_calls = {}, {}, set()
    for name, data in files.items():
        frag_store, tag_store, file_hash, declaration, _ = _reference(
            pipes, pkey, k, m, name, "photos", name, data)
        assert got[name] == file_hash
        want_frags.update(frag_store)
        want_tags.update(tag_store)
        want_calls.add(repr(declaration))
    assert gw.fragment_store == want_frags
    assert set(gw.tag_store) == set(want_tags)
    for h, tag in want_tags.items():
        np.testing.assert_array_equal(gw.tag_store[h], tag)
    assert len(gw.node.extrinsics) == 8
    assert {repr(call) for call in gw.node.extrinsics} == want_calls
    assert gw.counters()["uploads"] == 8
    assert gw.counters()["rows_fetched"] == 8 * 3 * m


def test_the_stages_stay_on_the_uploads_thread(pipes, gateway):
    """``gateway.fetch`` twice (the parity rows, with what came down,
    then the tags), ``gateway.hash`` once, whatever the workers do."""
    k, m = 2, 1
    gw = gateway(k, m, True)
    tracer = obs.Tracer()
    with obs.armed(tracer):
        gw.upload("alice", "photos", "f.bin", _file(k, 3.0))
    spans = tracer.finished()
    (upload,) = [s for s in spans if s["name"] == "offchain.upload"]
    workers = [s for s in spans if s["name"].startswith("gateway.worker.")]
    stages = [s for s in spans if s["name"].startswith("gateway.")
              and s["parent_id"] == upload["span_id"] and s not in workers]
    assert [s["name"] for s in stages] == [
        "gateway.encode", "gateway.fetch", "gateway.hash", "gateway.tag",
        "gateway.fetch", "gateway.store", "gateway.declare"]
    assert {s["tid"] for s in stages} == {upload["tid"]}
    # gateway.encode is made of three, in order, on the same thread
    (encode,) = [s for s in stages if s["name"] == "gateway.encode"]
    inner = [s for s in spans if s["parent_id"] == encode["span_id"]
             and s["name"].startswith("gateway.")]
    assert [s["name"] for s in inner] == [
        "gateway.encode.jobs", "gateway.encode.put", "gateway.encode.step"]
    assert {s["tid"] for s in inner} == {upload["tid"]}
    # the workers' jobs are children of the upload, none on its thread:
    # a copy and a hash a fragment, a hash a segment
    assert {s["parent_id"] for s in workers} == {upload["span_id"]}
    assert upload["tid"] not in {s["tid"] for s in workers}
    names = [s["name"] for s in workers]
    assert names.count("gateway.worker.copy") == 3 * (k + m)
    assert names.count("gateway.worker.hash") == 3 * (k + m + 1)
    parity, tags = [s for s in stages if s["name"] == "gateway.fetch"]
    assert parity["attrs"] == {"rows": 3 * m, "bytes": 3 * m * FRAG}
    assert tags["attrs"] == {}


def test_counters_ride_the_nodes_exposition(pipes, gateway):
    from cess_tpu.node.chain_spec import dev_spec
    from cess_tpu.node.metrics import collect, render_metrics
    from cess_tpu.node.network import Node

    k, m = 2, 1
    gw = gateway(k, m, False)
    gw.upload("alice", "photos", "f.bin", _file(k, 3.0))
    node = Node(dev_spec(), "gateway-node", {})
    assert not any(name.startswith("cess_gateway_")
                   for name in collect(node))
    node.gateway = gw
    series = collect(node)
    assert series["cess_gateway_uploads_total"] == 1.0
    assert series["cess_gateway_rows_from_host_total"] == 3.0 * k
    assert series["cess_gateway_rows_fetched_total"] == 3.0 * m
    assert series["cess_gateway_hash_jobs_total"] == 3.0 * (k + m + 1)
    assert series["cess_gateway_bytes_fetched_total"] \
        == gw.counters()["bytes_fetched"]
    # every stage's seconds and count, the workers' once a job; the
    # process's challenge derivations ride along
    seconds = gw.counters()["stage_seconds"]
    assert series["cess_gateway_stage_upload_count"] == 1.0
    assert series["cess_gateway_stage_upload_seconds"] \
        == seconds["offchain.upload"]
    assert series["cess_gateway_stage_encode_put_seconds"] \
        == seconds["gateway.encode.put"]
    assert series["cess_gateway_stage_fetch_count"] == 2.0
    assert series["cess_gateway_stage_worker_copy_count"] == 3.0 * (k + m)
    assert series["cess_gateway_stage_worker_hash_count"] \
        == 3.0 * (k + m + 1)
    assert series["cess_gateway_stage_worker_copy_seconds"] \
        == seconds["gateway.worker.copy"]
    assert {"cess_podr2_challenge_seconds", "cess_podr2_challenge_count",
            "cess_podr2_challenge_programs", "cess_podr2_coeffs_seconds",
            "cess_podr2_coeffs_count", "cess_podr2_coeffs_programs"} \
        <= set(series)
    assert "# TYPE cess_gateway_rows_fetched_total counter" \
        in render_metrics(node)


def test_close_stops_the_workers(pipes):
    gw = OssGateway(_Node(), "closing", pipes(2, 1, False))
    gw.upload("alice", "photos", "f.bin", _file(2, 1.0))

    def workers():
        return [t for t in threading.enumerate()
                if t.name.startswith("gateway-hash-closing")]

    assert workers()
    gw.close()
    assert workers() == []
    with pytest.raises(RuntimeError):
        gw.upload("alice", "photos", "f.bin", _file(2, 1.0))
    assert gw.node.extrinsics and len(gw.node.extrinsics) == 1
