"""Device pool (parallel/mesh.py ``stream_entry``): host milliseconds a
batch inside the pooled put's ``slice`` call —
building the list of the ``slots x devices`` row views (views of views of the staged chunk: no bytes move).
The sum of the trace's ``cess:stream.put.slice`` spans over its
``cess:stream.put`` spans (one a batch): with its two siblings, where the
29 of a pooled batch's 30.6 ms go (``stream_put_ms.pool4`` is the whole
call from the counters). A program without the span: nothing to read."""
import program_spans


def read(view):
    part = program_spans.total(view, "stream.put.slice")
    puts = program_spans.total(view, "stream.put")
    if part is None or puts is None:
        return None
    return 1e3 * part[0] / puts[1]
