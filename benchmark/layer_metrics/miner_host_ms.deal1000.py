"""Off-chain agents, the miner's round: host milliseconds a round inside
``MinerAgent.prove_round`` that are not the wait for the engine — the
``cess:miner.round`` spans of the trace less the ``cess:engine.prove.result``
spans inside them (the caller blocked on the prove class: its queue, the
gathers, the steps' calls, the wait, the fetch, which the engine's own
stages and counters split). What is left is the miner's own: the held
set's views and ids, the challenge, r, the submit, the encode. A round is
one that reached its submit (the idle proof over an empty set does not). A
program without the spans: nothing to read."""
import program_spans


def read(view):
    whole = program_spans.total(view, "miner.round")
    rounds = program_spans.total(view, "miner.round.submit")
    if whole is None or rounds is None:
        return None
    waited = program_spans.inside(view, "engine.prove.result", "miner.round")
    blocked = waited[0][0] if waited is not None else 0.0
    return 1e3 * (whole[0] - blocked) / rounds[1]
