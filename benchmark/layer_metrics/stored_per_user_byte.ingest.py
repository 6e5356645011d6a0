"""Stream driver (serve/stream.py): fragment bytes the window's finished
batches hold over the user bytes staged for them — the code's stored
bytes per user byte as a count: StreamStats ``bytes_out`` (fragments plus
tags of every finished batch) less the tags' bytes, over ``bytes_in``,
differenced over the window. (k + m) / k exactly — 3.0, 1.5, 1.4 — where
the program writes every row of every segment and no pad is counted; the
tags' bytes are reckoned from the configuration (a tag word a limb a
block of every fragment). A program without ``bytes_out`` (before PR 47)
gives nothing to read."""


def read(view):
    a, b = view.counters_before["stream"], view.counters_after["stream"]
    if "bytes_out" not in b:
        return None
    user = b["bytes_in"] - a["bytes_in"]
    if user <= 0:
        return None
    c = view.ctx.config
    tags = (b["segments"] - a["segments"]) * (c["k"] + c["m"]) \
        * c["blocks_per_fragment"] * c["podr2_limbs"] * 4
    out = b["bytes_out"] - a["bytes_out"]
    view.say(info="stored bytes", bytes_in=user, bytes_out=out,
             tag_bytes=tags)
    return (out - tags) / user
