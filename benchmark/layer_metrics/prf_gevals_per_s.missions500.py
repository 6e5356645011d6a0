"""PoDR2 programs, the verifier's PRF: evaluations of f_k(id, block) that
missions owed (real rows x challenged blocks; two threefry-2x32 blocks
each) per second the device was busy, in 10^9. The evaluations of the
traced window are the window's mean a verify batch (the program's
``prf_evals`` counter less the pad rows' share, differenced over the whole
window) times the verify batches that started inside the trace; the busy
seconds are the trace's. A batch cut by either edge of the trace is
counted whole or not at all, an error of about one batch in thirty. A
program without the counter or the spans: nothing to read."""
import program_spans


def read(view):
    try:
        a = view.counters_before["engine"]["classes"]["verify"]
        b = view.counters_after["engine"]["classes"]["verify"]
        issued = b["prf_evals"] - a["prf_evals"]
        real = b["rows"] - a["rows"]
        pad = b["padded_rows"] - a["padded_rows"]
        batches = b["batches"] - a["batches"]
    except (KeyError, TypeError):
        return None
    traced = program_spans.total(view, "engine.verify.batch")
    if traced is None or batches <= 0 or real <= 0 \
            or view.trace["busy_s"] <= 0:
        return None
    owed = issued * real / (real + pad) / batches * traced[1]
    view.say(info="prf evaluations", per_batch=issued / batches,
             real_share=real / (real + pad), traced_batches=traced[1],
             busy_s=view.trace["busy_s"])
    return owed / view.trace["busy_s"] / 1e9
