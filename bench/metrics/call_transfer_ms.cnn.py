"""Host time of ``repro.call.transfer`` per call: the inputs of each
``Executable.__call__`` handed to the runtime as device arrays, summed
over the traced window and divided by the window's calls.  It covers
the enqueue of each input's copy only: ``jnp.asarray`` returns before
the runtime's relayout (``XlaLinearize``) and copy to the device, which
run on the runtime's own threads outside every program span.  Spans
under 20 us are dropped by the trace loader and count as nothing."""

from bench import program_spans


def read(run):
    if run.trace is None:
        return None
    return program_spans.mean_ms(run.trace, "repro.call.transfer",
                                 run.counters.get("traced_calls", 0))
