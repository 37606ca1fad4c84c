"""Share of the traced window in which the first device ran no
operation while the host was inside one of the ``repro.call.*`` spans
of ``Executable.__call__``: the device's idle gaps, moved onto the
host's clock by the lag between each program and its enqueue
(``program_spans.device_lag_ns``), intersected with the call path's
spans; the part of ``idle_share.cnn`` that the program's call path
accounts for."""

from bench import program_spans


def read(run):
    if run.trace is None:
        return None
    share = program_spans.idle_share_under(run.trace, program_spans.CALL)
    return None if share is None else 100.0 * share
