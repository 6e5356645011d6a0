#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --workload <cell> --rehearse     tiny sizes, any backend

The cell is an entry of BENCHMARK.json's ``workloads``; everything that
belongs to it is found by name: ``workloads/<cell>.json`` (traffic kind
and parameters), the configuration's file, ``traffic/<kind>.py`` (the
driver), ``end_to_end/<metric>.py`` and ``layer_metrics/<metric>.py``
(one reader each). This file knows none of them by name.

Order of a run: set-up (data from the seed, the system, warm-up of the
cell's own shapes) -> the measured window (operations start for
``--seconds`` seconds; the window closes when the last of them is done)
-> the output check against
``reference/`` (outside the window and outside ``setup_s``) -> one JSON
object as the last line. ``--trace 1`` takes a profiler trace of a short
steady part of the window and reports the per-layer metrics instead of
the end-to-end ones. Without a TPU only ``--rehearse`` runs, and it
prints no device metric and never the last line.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()      # set-up is counted from here

import argparse
import importlib.util
import json
import os
import shutil
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
TRACE_START_S = 1.0        # into the window, so the loop is steady
TRACE_SECONDS = 4.0


class Refused(Exception):
    """The run cannot be made; nothing is printed as a result."""


def load_by_path(kind: str, name: str):
    """The module ``<kind>/<name>.py`` (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise Refused(f"no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> types.SimpleNamespace:
    bench = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise Refused(f"BENCHMARK.json has no workload {name!r}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return types.SimpleNamespace(
        name=name, chips=entry["chips"],
        run_seconds=float(bench["run_seconds"]),
        config=load_json(ROOT, conf["file"]),
        workload=load_json(HERE, "workloads", name + ".json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name)])


def rehearsal_sizes(cell) -> None:
    """--rehearse: the files' own ``rehearse`` overrides, nothing else."""
    cell.config.update(cell.config.get("rehearse", {}))
    cell.workload["traffic"].update(cell.workload.get("rehearse", {}))


class Tracing:
    """A profiler trace of ``TRACE_SECONDS`` of the window, started and
    stopped between operations."""

    def __init__(self, cell_name: str, window_t0: float, seconds: float):
        self.dir = os.path.join(TRACE_DIR, cell_name)
        shutil.rmtree(self.dir, ignore_errors=True)
        start = min(TRACE_START_S, seconds / 4)
        self.t_start = window_t0 + start
        self.t_stop = self.t_start + min(TRACE_SECONDS, seconds / 2)
        self.state = "before"

    def tick(self, now: float) -> None:
        import jax

        if self.state == "before" and now >= self.t_start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # the bench: spans suffice
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.state = "on"
        elif self.state == "on" and now >= self.t_stop:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state == "on":
            jax.profiler.stop_trace()
            self.state = "done"

    def summary(self, n_devices: int, allow_host: bool):
        import trace_reduce

        if self.state != "done":
            return None
        return trace_reduce.reduce_dir(self.dir, n_devices, allow_host)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever JAX finds; no device "
                         "metric, never the last line")
    ap.add_argument("--control", default=None,
                    help="tests only: break the timed path underneath "
                         "(a name from the driver's CONTROLS)")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    cell = load_cell(args.workload)
    if args.rehearse:
        rehearsal_sizes(cell)
    seconds = args.seconds if args.seconds is not None else (
        1.0 if args.rehearse else cell.run_seconds)

    try:
        import cess_tpu                                    # noqa: F401
    except ImportError as e:
        raise Refused(f"the program is not in this checkout: {e}")
    import jax

    from cess_tpu import jaxcache

    import compile_clock
    import spans as spans_mod

    cache_dir = jaxcache.enable()          # before the first compile
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        raise Refused(f"no chip found: JAX reports platform "
                      f"{dev.platform!r}, not 'tpu' (--rehearse runs the "
                      f"control flow at tiny sizes)")
    if len(devices) < cell.chips and not args.rehearse:
        raise Refused(f"the cell needs {cell.chips} chips, JAX reports "
                      f"{len(devices)}")
    on = f"{dev.platform}/{dev.device_kind} x{len(devices)}"

    def say(**line) -> None:
        line["on"] = on
        print(json.dumps(line, default=str), flush=True)

    clock = compile_clock.CompileClock()
    stages = {"imports_and_chip_s": time.perf_counter() - T_PROCESS}
    driver = load_by_path("traffic", cell.workload["traffic"]["kind"])
    ctx = types.SimpleNamespace(
        cell=cell.name, seed=args.seed, config=cell.config,
        traffic=cell.workload["traffic"], chips=cell.chips,
        rehearse=args.rehearse, on_chip=dev.platform == "tpu",
        device_kind=dev.device_kind,
        spans=spans_mod.Spans(enabled=bool(args.trace)),
        say=say, deadline=None, setup_s=None)
    try:
        t_stage = time.perf_counter()
        driver.setup(ctx)
        if args.control is not None:
            driver.CONTROLS[args.control](ctx)
        stages["driver_setup_s"] = time.perf_counter() - t_stage
        t_stage = time.perf_counter()
        driver.warm(ctx)
        stages["driver_warm_s"] = time.perf_counter() - t_stage
        compiled_setup = clock.snapshot()

        # ---- the measured window -------------------------------------
        before = driver.counters(ctx)
        t0 = time.perf_counter()
        ctx.setup_s = t0 - T_PROCESS
        ctx.window_t0 = t0
        ctx.deadline = t_end = t0 + seconds
        tracing = Tracing(cell.name, t0, seconds) if args.trace else None
        ops = []
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if tracing is not None:
                tracing.tick(now)
            rec = driver.op(ctx)
            if rec is None:
                break
            ops.append(rec)
        if tracing is not None:
            tracing.stop()
        # nothing new starts after --seconds; what is in flight is
        # finished, and the window is all that time: every operation and
        # every second count, so a rate has no whole-operation steps in it
        ops.extend(driver.drain(ctx))
        window_s = time.perf_counter() - t0
        after = driver.counters(ctx)
        compiled_window = clock.snapshot()["programs"] \
            - compiled_setup["programs"]

        # ---- output check: outside the window and outside setup_s ----
        t_check = time.perf_counter()
        comparisons = driver.check(ctx, ops)
        comparisons.append({"what": "compilations inside the window",
                            "value": compiled_window, "limit": 0})
        check_s = time.perf_counter() - t_check
        for c in comparisons:
            c["ok"] = bool(c["value"] <= c["limit"])
            say(compare=c["what"], value=c["value"], limit=c["limit"],
                ok=c["ok"])
        failed = sum(1 for o in ops if not o["ok"])
        correct = all(c["ok"] for c in comparisons) and failed == 0

        thirds = [[o["latency_s"] for o in ops
                   if i <= 3 * (o["t_start"] - t0) / window_s < i + 1]
                  for i in range(3)]
        say(info="run", workload=cell.name, seed=args.seed,
            seconds=seconds, window_s=window_s, trace=args.trace,
            attempted=len(ops), failed=failed,
            # a first third slower than the last: something still warms
            # up inside the window
            median_op_s_by_third=[sorted(x)[len(x) // 2] if x else None
                                  for x in thirds],
            setup_s=ctx.setup_s, **stages, check_s=check_s,
            compile_or_load_s=compiled_setup["seconds"],
            programs=compiled_setup["programs"],
            cache_hits=clock.cache_hits, cache_writes=clock.cache_writes,
            compiled_in_window=compiled_window, cache_dir=cache_dir)

        view = types.SimpleNamespace(
            ctx=ctx, ops=ops, window_s=window_s, spans=ctx.spans,
            counters_before=before, counters_after=after, trace=None,
            say=say)
        metrics = {}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": max(
                      (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices[:cell.chips])}
        result = {"correct": correct, "attempted": len(ops),
                  "failed": failed, "metrics": metrics, "device": device}
        if args.trace:
            view.trace = tracing.summary(cell.chips, args.rehearse)
            if view.trace is not None:
                device["busy_s"] = view.trace["busy_s"]
                device["window_s"] = view.trace["window_s"]
                result["breakdown"] = {
                    "device_ops": view.trace["device_ops"][:10],
                    "idle_gaps": view.trace["idle_gaps"][:10]}
                say(info="trace", planes=view.trace["planes"],
                    events=view.trace["n_events"],
                    idle_share=1 - view.trace["busy_s"]
                    / view.trace["window_s"])
            wanted, kind = cell.per_layer, "layer_metrics"
        else:
            wanted, kind = cell.end_to_end, "end_to_end"
        for m in wanted:
            value = load_by_path(kind, m["name"]).read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    finally:
        driver.close(ctx)

    if args.rehearse:
        # counts and control flow only: a CPU number never goes under a
        # device metric's name
        say(rehearsal="passed" if correct else "FAILED",
            metrics_read=sorted(metrics), correct=correct)
        return 0 if correct else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Refused as e:
        print(f"benchmark/run.py: {e}. Nothing was run.", file=sys.stderr)
        sys.exit(3)
