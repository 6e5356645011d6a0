"""Off-chain agents (node/offchain.py): the share of OssGateway.upload
spent fetching device results to the host (``cess:gateway.fetch`` over
``cess:offchain.upload`` spans of the trace): ``np.asarray`` of the
fragments and of the tags."""
import program_spans


def read(view):
    return program_spans.span_share(view, "gateway.fetch",
                                    "offchain.upload")
