"""Submission engine, prove class: mean time the caller's thread spends in
``submit_prove_aggregate`` before its request is queued (the program's
``caller.submit`` counter over the requests completed in the window):
normalising the arguments, ``_check_round`` and ``_round_digest`` on the
round's ``idx`` / ``nu``, admission, the enqueue. The time of a prove call
that lies before the six stages start. A program without the counter:
nothing to read."""
import caller_accounts


def read(view):
    return caller_accounts.per_request_ms(
        view, "submit", "prove", calls=("engine.prove_aggregate",))
