"""Off-chain agents (node/offchain.py): the share of OssGateway.upload
spent hashing (``cess:gateway.hash`` over ``cess:offchain.upload`` spans of
the trace): ``tobytes`` + SHA-256 of every fragment and segment, and the
fragment ids."""
import program_spans


def read(view):
    return program_spans.span_share(view, "gateway.hash", "offchain.upload")
