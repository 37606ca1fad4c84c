"""Host time of ``repro.step.sample`` per decoding step: each active
slot's logits sliced, its token sampled and read back to the host, and
finished requests retired, summed over the traced window and divided
by the window's steps that decoded.  Spans under 20 us are dropped by
the trace loader and count as nothing."""

from bench import program_spans


def read(run):
    if run.trace is None:
        return None
    steps = sum(bool(s.decode_contexts) for s in run.window_steps())
    return program_spans.mean_ms(run.trace, "repro.step.sample", steps)
