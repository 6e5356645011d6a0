"""PoDR2 programs (ops/podr2.py): host milliseconds a round spent deriving
the round from its seed — the ``cess:podr2.challenge`` spans of the trace
(``gen_challenge``) and the ``cess:podr2.coeffs`` spans
(``aggregate_coeffs``; a verifier that derives r on the device has none),
over the challenges. The calls as the host sees them: both issue their
operations one by one, un-jitted, on the caller's thread. A program without
the spans: nothing to read."""
import program_spans


def read(view):
    challenge = program_spans.total(view, "podr2.challenge")
    if challenge is None:
        return None
    coeffs = program_spans.total(view, "podr2.coeffs") or (0.0, 0)
    view.say(info="podr2 round derivation", challenges=challenge[1],
             challenge_s=challenge[0], coeffs=coeffs[1],
             coeffs_s=coeffs[0])
    return 1e3 * (challenge[0] + coeffs[0]) / challenge[1]
