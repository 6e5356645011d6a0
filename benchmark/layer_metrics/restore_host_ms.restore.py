"""Off-chain agents, the miner's restoral: host milliseconds a repair
inside ``MinerAgent.restore_fragment`` that are not the wait for the
engine — the ``cess:miner.repair`` spans of the trace less the
``cess:engine.repair.result`` spans inside them (the caller blocked on the
repair class: its queue, the put, the program, the wait, the fetch, which
the engine's own stages split; ten of them a repair in mode ``symbols``,
one in mode ``fragments``). What is left is the agent's own: finding the
holders, the coefficients, each hop's views and zero row, the engine's
``submit``, ``tobytes``, the SHA-256, the store, both extrinsics. A
program without the spans: nothing to read."""
import program_spans


def read(view):
    whole = program_spans.total(view, "miner.repair")
    if whole is None:
        return None
    waited = program_spans.inside(view, "engine.repair.result",
                                  "miner.repair")
    blocked = waited[0][0] if waited is not None else 0.0
    return 1e3 * (whole[0] - blocked) / whole[1]
