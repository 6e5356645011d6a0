"""Off-chain agents, the miner's restoral: MiB that crossed into the
rebuilder a repaired fragment — the agent's ``repair_ingress_bytes``
counter over its ``repairs``, differenced over the window. One fragment's
worth (8.0) down a chain of helpers that fold computed symbols, k
fragments' worth (80.0) where whole rows are pulled: the regenerating
plane's point, and what the README's test of it asks to be measured. The
link the bytes would cross is not in any cell's time. A program without
the counters: nothing to read."""


def read(view):
    try:
        a, b = view.counters_before["miner"], view.counters_after["miner"]
        came_in = b["repair_ingress_bytes"] - a["repair_ingress_bytes"]
        repairs = b["repairs"] - a["repairs"]
    except (KeyError, TypeError):
        return None
    if repairs <= 0:
        return None
    return came_in / repairs / 2 ** 20
