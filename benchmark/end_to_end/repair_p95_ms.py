"""95th percentile of the same sample as repair_p50_ms."""
import bench_lib


def read(view):
    return bench_lib.percentile_ms(view, 0.95)
