"""Fragments proven by the miner side and accepted by the verifier side
per second of window."""


def read(view):
    return sum(o["frags"] for o in view.ops if o["ok"]) / view.window_s
