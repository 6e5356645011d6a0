#!/usr/bin/env python
"""Link probe: what one 8 MiB fragment costs to cross the device <-> host
link in each shape the engine could hold it in (PERF.md section 5, PR 28).

    chiprun -- python tools/link_probe.py

d2h: median of REPS ``np.asarray`` calls, each on a fresh array that
``block_until_ready`` has declared complete (a ``jax.Array`` keeps its host
copy: a second fetch would time nothing). "as rows": the same bytes as that
many ``u8[N]``. "flatten + fetch": the engine's path, ``[rows, r, N]``
through one jitted program that returns its rows 1-D, then those fetched.
h2d: ``jax.device_put`` + ``block_until_ready``. "+ stack": a repair's
survivors as the engine sends them up since PR 32, q linear ``u8[N]`` rows
put one by one and stacked into the kernel's ``u8[1, q, N]`` operand by a
jitted program on the device (``ops/rs.py _stack_rows``' form), against the
same bytes put as one ``u8[1, q, N]`` host array. A CPU run says nothing.

    chiprun -- python tools/link_probe.py stream

The stream table (PR 43): one streamed batch of the one-chip stream cells
(8 segments of 16 MiB, 128 MiB) in every form the stream driver could put
it in, as views of ONE C-contiguous host chunk (no host copy): the packed
``u8[8, 16 MiB]`` the driver put until PR 43, the fragment-major
``u8[8, k, n]`` the pool's entry puts, and the linear forms ``u8[128 MiB]``,
``8 x u8[16 MiB]`` and ``8k x u8[n]``; each alone, "+ stack" (the put, then
one jitted program that makes the ``u8[8, k, n]`` the fused step reads, in
``split_rows``' slice-and-stack style) and "stack alone" (the same program
over rows already on the device), at RS(4,8) (k = 4) and RS(2,1) (k = 2),
and the archival tier's RS(10,4) batch (8 segments of 80 MiB, 640 MiB) as
its 80 rows. "2 in flight" (PR 50): the interval of a put when the next one
is asked for before the last is waited for, as the stream driver asks since
then (serve/stream.py ``_run``'s gate): what the link carries with two puts
on it, against "put", one put waited for.
"""
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

N = 8 << 20          # one RS(2,1) fragment
REPS = 20


def _fresh(shape):
    """A jitted maker of distinct u8 arrays of ``shape`` (elementwise over
    the final shape: no reshape the TPU compiler would have to relayout)."""
    def make(i):
        x = jax.lax.broadcasted_iota(jnp.uint32, shape, len(shape) - 1)
        for d in range(len(shape) - 1):
            x = x + 7919 * jax.lax.broadcasted_iota(jnp.uint32, shape, d)
        return ((x * jnp.uint32(2654435761) + i) >> 13).astype(jnp.uint8)
    return jax.jit(make)


def _median_ms(prepare, timed):
    out = []
    for i in range(REPS + 2):
        arg = jax.block_until_ready(prepare(i))
        t0 = time.perf_counter()
        timed(arg)
        out.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(out[2:]), min(out[2:])   # two calls warm up


def _fetch_all(rows):
    return [np.asarray(a) for a in rows]


def whole(*shape):
    return _median_ms(_fresh(shape), np.asarray)


def as_rows(count):
    one = _fresh((N,))
    make = jax.jit(lambda i: tuple(one(i + j) for j in range(count)))
    return _median_ms(make, _fetch_all)


def flat(*shape):
    """The flatten: ``[rows, r, N]`` -> rows * r arrays ``u8[N]``."""
    rows = jax.jit(lambda a: tuple(a[i, j].reshape(-1)
                                   for i in range(shape[0])
                                   for j in range(shape[1])))
    return _median_ms(_fresh(shape),
                      lambda a: _fetch_all(jax.block_until_ready(rows(a))))


def put(*shapes):
    rng = np.random.default_rng(28)
    host = [rng.integers(0, 256, s, dtype=np.uint8) for s in shapes]
    return _median_ms(lambda i: host, lambda hs: jax.block_until_ready(
        [jax.device_put(h) for h in hs]))


def put_stacked(q):
    """q host rows -> q device rows -> ``u8[1, q, N]`` on the device."""
    stack = jax.jit(lambda *rows: jnp.stack([jnp.stack(rows)]))
    rng = np.random.default_rng(32)
    host = [rng.integers(0, 256, (N,), dtype=np.uint8) for _ in range(q)]
    return _median_ms(lambda i: host, lambda hs: jax.block_until_ready(
        stack(*jax.device_put(hs))))


BATCH = 8            # the one-chip stream cells' batch
# (k, segment bytes) of the stream cells: RS(4,8), RS(2,1), archival RS(10,4)
GEOMETRIES = ((4, 16 << 20), (2, 16 << 20), (10, 80 << 20))


def _stackers(k, segment):
    """form -> (host views of a chunk, jitted stack to ``u8[BATCH, k, n]``
    or None where the put already has that shape)."""
    n = segment // k

    def split(x):                        # models/pipeline.py split_rows
        return jnp.stack([x[:, j * n:(j + 1) * n] for j in range(k)],
                         axis=1)

    def from_flat(x):
        return jnp.stack([jnp.stack(
            [x[i * segment + j * n:i * segment + (j + 1) * n]
             for j in range(k)]) for i in range(BATCH)])

    def from_segments(*segs):
        return jnp.stack([jnp.stack([s[j * n:(j + 1) * n]
                                     for j in range(k)]) for s in segs])

    def from_rows(*rows):                # ops/rs.py _stack_rows
        return jnp.stack([jnp.stack(rows[i:i + k])
                          for i in range(0, len(rows), k)])

    return {
        f"u8[{BATCH},S]": (lambda c: [c], split),
        f"u8[{BATCH},{k},S/{k}]": (
            lambda c: [c.reshape(BATCH, k, n)], None),
        f"u8[{BATCH}*S]": (lambda c: [c.reshape(-1)], from_flat),
        f"{BATCH} x u8[S]": (list, from_segments),
        f"{BATCH * k} x u8[S/{k}]": (
            lambda c: list(c.reshape(BATCH * k, n)), from_rows),
    }


def _in_flight_ms(hosts):
    """Median interval of a put when the next is asked for before the last
    one is waited for (two in flight)."""
    out, flying = [], jax.device_put(hosts[0])
    for i in range(1, REPS + 3):
        t0 = time.perf_counter()
        nxt = jax.device_put(hosts[i % 2])
        jax.block_until_ready(flying)
        flying = nxt
        out.append((time.perf_counter() - t0) * 1e3)
    jax.block_until_ready(flying)
    return statistics.median(out[2:])


def stream_table():
    rng = np.random.default_rng(43)
    print(f"one batch = {BATCH} x S; median (min) of {REPS}, ms")
    print(f"{'k':>2} {'S MiB':>5} {'what':<16}{'put':>9}{'(min)':>9}"
          f"{'GiB/s':>7}{'2 in flight':>12}{'+ stack':>9}{'(min)':>9}"
          f"{'GiB/s':>7}{'stack alone':>13}{'compile s':>11}")
    for k, segment in GEOMETRIES:
        chunks = [rng.integers(0, 256, (BATCH, segment), dtype=np.uint8)
                  for _ in range(2)]     # alternated: no put of a warm page
        gib = BATCH * segment / 2**30
        for what, (views, stack) in _stackers(k, segment).items():
            if k == 10 and " x " not in what:
                continue     # the archival batch as rows alone: the
                             # one-array forms left the driver in PR 43
            hosts = [views(c) for c in chunks]
            assert all(np.shares_memory(v, c)      # views, not copies
                       for h, c in zip(hosts, chunks) for v in h)
            put_ms, put_min = _median_ms(
                lambda i: hosts[i % 2],
                lambda hs: jax.block_until_ready(jax.device_put(hs)))
            line = (f"{k:>2} {segment >> 20:>5} {what:<16}{put_ms:>9.3f}"
                    f"{put_min:>9.3f}{gib / (put_ms / 1e3):>7.2f}"
                    f"{_in_flight_ms(hosts):>12.3f}")
            if stack is not None:
                jitted = jax.jit(stack)
                t0 = time.perf_counter()
                jax.block_until_ready(jitted(*jax.device_put(hosts[0])))
                compile_s = time.perf_counter() - t0
                both, both_min = _median_ms(
                    lambda i: hosts[i % 2],
                    lambda hs: jax.block_until_ready(
                        jitted(*jax.device_put(hs))))
                alone, _ = _median_ms(
                    lambda i: jax.device_put(hosts[i % 2]),
                    lambda ds: jax.block_until_ready(jitted(*ds)))
                line += (f"{both:>9.3f}{both_min:>9.3f}"
                         f"{gib / (both / 1e3):>7.2f}{alone:>13.3f}"
                         f"{compile_s:>11.2f}")
            print(line, flush=True)


def main():
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}")
    if "stream" in sys.argv[1:]:
        return stream_table()
    print(f"N = {N}; median (min) of {REPS}, ms")
    table = [("d2h u8[1,1,N]", 1, whole(1, 1, N)),
             ("d2h u8[1,N]", 1, whole(1, N)),
             ("d2h u8[N]", 1, whole(N)),
             ("d2h u8[32,N/32]", 1, whole(32, N // 32)),
             ("d2h u8[1,1,N] flatten + fetch", 1, flat(1, 1, N)),
             ("d2h u8[2,1,N]", 2, whole(2, 1, N)),
             ("d2h 2 x u8[N] as rows", 2, as_rows(2)),
             ("d2h u8[2,1,N] flatten + fetch", 2, flat(2, 1, N)),
             ("d2h u8[4,3,N]", 12, whole(4, 3, N)),
             ("d2h 12 x u8[N] as rows", 12, as_rows(12)),
             ("d2h u8[4,3,N] flatten + fetch", 12, flat(4, 3, N)),
             ("h2d u8[1,2,N]", 2, put((1, 2, N))),
             ("h2d 2 x u8[N]", 2, put((N,), (N,))),
             ("h2d 2 x u8[N] + stack u8[1,2,N]", 2, put_stacked(2)),
             ("h2d u8[1,10,N]", 10, put((1, 10, N))),
             ("h2d 10 x u8[N]", 10, put(*[(N,)] * 10)),
             ("h2d 10 x u8[N] + stack u8[1,10,N]", 10, put_stacked(10))]
    print(f"{'what':<34}{'MiB':>5}{'median':>10}{'min':>10}{'GiB/s':>8}")
    for what, frags, (med, low) in table:
        print(f"{what:<34}{frags * N >> 20:>5}{med:>10.3f}{low:>10.3f}"
              f"{frags * N / 2**30 / (med / 1e3):>8.2f}")


if __name__ == "__main__":
    main()
