"""What the traffic drivers share: data from the seed, the engine as the
cells build it, its failure counters, and the comparisons' arithmetic."""
from __future__ import annotations

import concurrent.futures
import hashlib
import time

import numpy as np

_PARTS = 8          # fixed: the bytes depend on the seed alone


def sub_seed(seed: int, *salt: int) -> int:
    """A 31-bit seed of its own for one consumer (keys, draws, data);
    ``--seed`` may exceed 32 signed bits."""
    return int(np.random.SeedSequence([seed, *salt]).generate_state(1)[0]
               & 0x7FFFFFFF)


def key_seed(ctx) -> int:
    """The PoDR2 key's seed. The key is the network's (one TEE key for a
    deployment), so it comes from the configuration and not from
    ``--seed``: the fused encode+tag program bakes the key's weights in
    as constants, and a key from the run's seed made every run compile
    that program anew (4.5 s of set-up, my chip runs, PR 24). Data,
    draws and round seeds come from ``--seed``."""
    return int(ctx.config["podr2_key_seed"])


def seeded_bytes(seed: int, nbytes: int) -> np.ndarray:
    """``nbytes`` (a multiple of 64) of uint8 from the seed, made in bulk
    by a few threads (NumPy fills outside the interpreter lock)."""
    buf = np.empty(nbytes // 8, dtype=np.uint64)
    chunks = np.array_split(buf, _PARTS)
    seeds = np.random.SeedSequence(seed).spawn(_PARTS)

    def fill(job):
        ss, out = job
        out[:] = np.random.Generator(np.random.SFC64(ss)).integers(
            0, 2 ** 64 - 1, out.shape[0], dtype=np.uint64, endpoint=True)

    with concurrent.futures.ThreadPoolExecutor(_PARTS) as pool:
        list(pool.map(fill, zip(seeds, chunks)))
    return buf.view(np.uint8)


def sha256(data) -> bytes:
    return hashlib.sha256(data).digest()


def n_differ(a, b) -> int:
    """How many elements differ (shape mismatch: all of them)."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return int(max(a.size, b.size, 1))
    return int(np.count_nonzero(a != b))


def make_engine(ctx, key=None):
    """The submission engine as every engine-driven cell builds it (no
    key: codec classes only):
    device codec, device audit backend, resilience off (a device failure
    fails the run instead of degrading to the CPU codec)."""
    from cess_tpu.serve import make_engine as make

    return make(ctx.config["k"], ctx.config["m"],
                rs_backend="tpu" if ctx.on_chip else "jax",
                podr2_key=key,
                audit_backend="tpu" if ctx.on_chip else "cpu")


ENGINE_FAILURES = ("failed", "timeouts", "saturated", "shed")


def engine_counters(eng) -> dict:
    """stats_snapshot() as the layer readers take it, with the summed
    failure counters that must stay 0."""
    snap = eng.stats_snapshot()
    snap["failures"] = sum(c[k] for c in snap["classes"].values()
                           for k in ENGINE_FAILURES)
    snap["resilience_on"] = "resilience" in snap
    return snap


def engine_comparisons(eng) -> list[dict]:
    snap = engine_counters(eng)
    return [{"what": "engine failed+timeouts+saturated+shed",
             "value": snap["failures"], "limit": 0},
            {"what": "engine resilience configured (must be off)",
             "value": int(snap["resilience_on"]), "limit": 0}]


def op_record(t0: float, ok: bool = True, **work) -> dict:
    """One finished operation: its clock stops here."""
    t1 = time.perf_counter()
    return {"t_start": t0, "t_end": t1, "latency_s": t1 - t0, "ok": ok,
            **work}


def draw_sample(seed: int, n: int, k: int, *must) -> list[int]:
    """``k`` of ``range(n)``, drawn from the seed, with ``must`` in it."""
    if n <= 0:
        return []
    rng = np.random.default_rng(sub_seed(seed, 0x5A))
    picked = {m % n for m in must}
    for i in rng.permutation(n):
        if len(picked) >= min(k, n):
            break
        picked.add(int(i))
    return sorted(picked)


def percentile_ms(view, q: float):
    """Percentile of the latencies of the operations completed in the
    window (host clock), in ms; the count goes on an earlier line."""
    xs = sorted(o["latency_s"] for o in view.ops if o["ok"])
    if not xs:
        return None
    view.say(info="latency sample", count=len(xs), q=q)
    return 1e3 * xs[min(len(xs) - 1, int(q * len(xs)))]
