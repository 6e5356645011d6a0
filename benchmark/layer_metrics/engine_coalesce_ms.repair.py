"""Submission engine, repair class: the part of a request's queue wait that
policy asked for — from its enqueue to the instant the batch's drain
trigger tripped (a lone client: ``AdmissionPolicy.max_delay`` after the
enqueue), the program's ``queue.coalesce`` counter over the requests
completed in the window. With ``engine_wake_ms.repair`` it is
``engine_queue_ms.repair``, exactly. A program without the counter: nothing
to read."""
import caller_accounts


def read(view):
    return caller_accounts.per_request_ms(
        view, "coalesce", "repair", calls=("engine.reconstruct",))
