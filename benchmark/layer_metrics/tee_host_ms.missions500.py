"""Off-chain agents, the TEE's round: host milliseconds a round inside
``TeeAgent.verify_round`` that are not the wait for the verdicts — the
``cess:tee.round`` spans of the trace less their ``cess:tee.round.gather``
(decoding the proofs, the ids of the owed sets, the round's challenge, the
submit). The gather holds the engine's queue, assemble, dispatch, wait and
fetch, which the engine's own stages split. A program without the spans:
nothing to read."""
import program_spans


def read(view):
    whole = program_spans.total(view, "tee.round")
    if whole is None:
        return None
    gather = program_spans.total(view, "tee.round.gather") or (0.0, 0)
    return 1e3 * (whole[0] - gather[0]) / whole[1]
