"""Submission engine, repair class: the part of a request's queue wait that
no policy asked for — from the instant the drain trigger tripped until the
batch starts to run (the batcher's thread asleep past its timeout, busy
with another batch or waiting for the GIL), the program's ``queue.wake``
counter over the requests completed in the window. A program without the
counter: nothing to read."""
import caller_accounts


def read(view):
    return caller_accounts.per_request_ms(
        view, "wake", "repair", calls=("engine.reconstruct",))
