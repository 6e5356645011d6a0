"""Submission engine, prove class: MiB of operands handed to the device
program per prove batch in the window (the ``operand_bytes`` counter of
``stats_snapshot()["classes"]["prove"]`` over its ``batches``). The miner's
set is 256 MiB of host fragments + 4 MiB of tags; a round reads the 753
challenged blocks of each fragment, 12.3 MiB. None on a program without
the counter."""

MIB = float(1 << 20)


def read(view):
    try:
        a = view.counters_before["engine"]["classes"]["prove"]
        b = view.counters_after["engine"]["classes"]["prove"]
        nbytes = b["operand_bytes"] - a["operand_bytes"]
        batches = b["batches"] - a["batches"]
    except (KeyError, TypeError):
        return None
    if batches <= 0:
        return None
    view.say(info="prove operands", batches=batches, operand_bytes=nbytes)
    return nbytes / batches / MIB
