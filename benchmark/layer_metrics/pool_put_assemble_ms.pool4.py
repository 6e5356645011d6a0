"""Device pool (parallel/mesh.py ``stream_entry``): host milliseconds a
batch inside the pooled put's ``assemble`` call —
the ``slots`` calls of ``jax.make_array_from_single_device_arrays`` that make one global array a row slot.
The sum of the trace's ``cess:stream.put.assemble`` spans over its
``cess:stream.put`` spans (one a batch): with its two siblings, where the
29 of a pooled batch's 30.6 ms go (``stream_put_ms.pool4`` is the whole
call from the counters). A program without the span: nothing to read."""
import program_spans


def read(view):
    part = program_spans.total(view, "stream.put.assemble")
    puts = program_spans.total(view, "stream.put")
    if part is None or puts is None:
        return None
    return 1e3 * part[0] / puts[1]
