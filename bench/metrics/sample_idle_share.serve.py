"""Share of the traced window in which the first device ran no
operation while the host was inside ``repro.step.sample`` (the
per-slot sampling loop and its host reads), the device's gaps moved
onto the host's clock as in ``call_idle_share.cnn``: the part of
``idle_share.serve`` that sampling accounts for."""

from bench import program_spans


def read(run):
    if run.trace is None:
        return None
    share = program_spans.idle_share_under(run.trace, "repro.step.sample")
    return None if share is None else 100.0 * share
