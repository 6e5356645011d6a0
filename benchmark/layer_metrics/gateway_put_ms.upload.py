"""Off-chain agents (node/offchain.py): the upload's host -> device copy as
the upload's thread sees it — the mean ``cess:gateway.encode.put`` span of
the trace (``jnp.asarray(segments)``), with ``gateway.encode``'s other two
parts printed beside it. A program without the span: nothing to read."""
import program_spans


def read(view):
    put = program_spans.total(view, "gateway.encode.put")
    if put is None:
        return None
    parts = {}
    for name in ("gateway.encode", "gateway.encode.jobs",
                 "gateway.encode.put", "gateway.encode.step"):
        got = program_spans.total(view, name)
        if got is not None:
            parts[name] = 1e3 * got[0] / got[1]
    view.say(info="gateway.encode by part", mean_ms=parts, count=put[1])
    return 1e3 * put[0] / put[1]
