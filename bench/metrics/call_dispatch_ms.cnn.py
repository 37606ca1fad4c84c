"""Host time of ``repro.call.dispatch`` per call: the compiled program
looked up, the batch padded to its bucket, and the program called up
to its being enqueued (the runtime's argument handling over the
weights and the input included), summed over the traced window and
divided by the window's calls.  Spans under 20 us are dropped by the
trace loader and count as nothing."""

from bench import program_spans


def read(run):
    if run.trace is None:
        return None
    return program_spans.mean_ms(run.trace, "repro.call.dispatch",
                                 run.counters.get("traced_calls", 0))
