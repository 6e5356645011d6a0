"""Off-chain agents, the miner's restoral: mean milliseconds of a
``cess:miner.repair.hash`` span of the trace — the rebuilder's SHA-256 of
the repaired fragment against its on-chain id, before anything is stored
(8 MiB; once a repair, twice where a chain fell back). A program without
the span: nothing to read."""
import program_spans


def read(view):
    hashed = program_spans.total(view, "miner.repair.hash")
    if hashed is None:
        return None
    return 1e3 * hashed[0] / hashed[1]
