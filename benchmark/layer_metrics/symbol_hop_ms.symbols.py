"""Off-chain agents, a helper's side of a chained repair: mean
milliseconds of a ``cess:miner.symbol.hop`` span of the trace — one call
of ``MinerAgent.repair_symbol``: the view of the held bytes, the first
hop's zero row, the fold as one request of the engine's repair class (its
``cess:engine.repair.result`` lies inside) and the way out. Ten a repair.
A program without the span: nothing to read."""
import program_spans


def read(view):
    hops = program_spans.total(view, "miner.symbol.hop")
    if hops is None:
        return None
    return 1e3 * hops[0] / hops[1]
