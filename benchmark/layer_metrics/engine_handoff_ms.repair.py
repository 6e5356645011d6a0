"""Submission engine, repair class: how long the caller stays blocked in
``result()`` after the batcher has resolved its future (the program's
``caller.handoff`` counter over the requests completed in the window): the
wake-up of the caller's thread and its wait for the GIL. A program without
the counter: nothing to read."""
import caller_accounts


def read(view):
    return caller_accounts.per_request_ms(
        view, "handoff", "repair", calls=("engine.reconstruct",))
