"""Off-chain agents (node/offchain.py): worker milliseconds an upload spent
copying rows into the store's ``bytes`` (the ``cess:gateway.worker.copy``
spans inside the ``cess:offchain.upload`` spans of the trace, over those
uploads). Worker-seconds: they add over the workers and may pass the
upload's own wall time. A program whose workers emit no span: nothing to
read."""
import program_spans


def read(view):
    got = program_spans.inside(view, "gateway.worker.copy",
                               "offchain.upload")
    if got is None or got[0][1] == 0:
        return None
    (copy_s, copies), (upload_s, uploads) = got
    hashed = program_spans.inside(view, "gateway.worker.hash",
                                  "offchain.upload")
    view.say(info="gateway workers", uploads=uploads, upload_s=upload_s,
             copies=copies, copy_s=copy_s, hashes=hashed[0][1],
             hash_s=hashed[0][0])
    return 1e3 * copy_s / uploads
