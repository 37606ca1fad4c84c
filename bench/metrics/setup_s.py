"""Set-up time: from the start of the process to the start of the
window (imports, device, weights, compilation or cache loads, warm-up),
on the host's clock."""


def read(run):
    return run.setup_s
