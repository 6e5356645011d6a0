"""The decode path end to end, RS(4,8) with four rows lost: GiB of rebuilt
rows handed back as host ``bytes`` per second a burst is in flight — Σ
``rebuilt_bytes`` / Σ ``latency_s`` of the window's correct bursts / 2^30
(host clock; the hashing after each burst's clock is in neither). The
number to set beside BASELINE's "RS 4-erasure batched decode >= 8 GiB/s
per chip"."""


def read(view):
    done = [o for o in view.ops if o["ok"] and "rebuilt_bytes" in o]
    seconds = sum(o["latency_s"] for o in done)
    if seconds <= 0:
        return None
    rebuilt = sum(o["rebuilt_bytes"] for o in done)
    view.say(info="decode rate", bursts=len(done), rebuilt_bytes=rebuilt,
             in_flight_s=seconds, window_s=view.window_s)
    return rebuilt / seconds / 2 ** 30
