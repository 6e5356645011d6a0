"""GiB of user bytes handed in as host memory for which fragments and
tags were complete, over the window's seconds (the window closes when
the last operation started inside ``--seconds`` is done)."""


def read(view):
    done = sum(o["user_bytes"] for o in view.ops if o["ok"])
    return done / 2 ** 30 / view.window_s
