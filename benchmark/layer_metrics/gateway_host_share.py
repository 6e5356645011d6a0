"""Off-chain agents (node/offchain.py): the share of OssGateway.upload's
wall time spent outside the pipeline's two calls (encode_step, tag_step):
hashing, copies, the declaration. From the benchmark's timing shims."""


def read(view):
    whole = view.spans.total("gateway.upload", view.ctx.window_t0)
    if whole <= 0:
        return None
    inside = view.spans.total("pipeline.encode_step", view.ctx.window_t0) \
        + view.spans.total("pipeline.tag_step", view.ctx.window_t0)
    return 100.0 * (1.0 - inside / whole)
