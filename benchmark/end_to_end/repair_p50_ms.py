"""Median over all repairs completed in the window: host survivors
handed to engine.reconstruct -> the repaired fragment's bytes on the host."""
import bench_lib


def read(view):
    return bench_lib.percentile_ms(view, 0.50)
