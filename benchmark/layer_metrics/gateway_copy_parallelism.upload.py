"""Off-chain agents (node/offchain.py): how many of the gateway's workers
are inside a copy at once — the summed seconds of the
``cess:gateway.worker.copy`` spans of the trace over the seconds their union
covers. 1.0: one span at a time, whatever the number of workers. An upper
bound on copies MADE at once: a span runs from its worker's stage entry to
its exit, so it holds that thread's waits for the GIL on either side of
``bytes(row)``, which itself holds the GIL. A program whose workers emit no
span: nothing to read."""
import caller_accounts
import program_spans


def read(view):
    summed = program_spans.total(view, "gateway.worker.copy")
    covered = caller_accounts.union_seconds(view, "gateway.worker.copy")
    if summed is None or not covered:
        return None
    return summed[0] / covered
