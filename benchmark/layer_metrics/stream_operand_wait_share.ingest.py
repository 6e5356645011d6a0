"""Stream driver: of the traced window's device-idle seconds, the share
during which a batch's program had been enqueued (its
``cess:stream.dispatch`` had ended) and had not started — the device
waiting for its operands: on one chip, for the link. The rest of the idle
time had nothing enqueued: the host was late. ``stream_stall_share``
cannot tell the two apart (a host blocked on a transfer stalls as one
blocked on a kernel does), and the ledger's ``idle_gaps`` lay idle time to
whatever span the host thread happened to be inside. A batch's dispatch
and its program's run on the device are paired by the ``seq`` the
program's stream spans carry since PR 54 (stream_pairing.py: by the
runtime's ``run_id`` where the trace has it on both sides, else by the
order anchored at the stalls and gates that waited); the line prints both kinds of
idle seconds and how the pairs were made. A transfer is still no device
event: what crosses the link when is not in this number. A program
without ``seq`` in its spans (the parent): nothing to read."""
import stream_pairing


def read(view):
    got = stream_pairing.operand_wait(view)
    if got is None:
        return None
    view.say(info="device idle by cause", **got)
    return 100.0 * got["operand_wait_s"] / got["idle_s"]
