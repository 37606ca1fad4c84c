"""Mean time of a call as the one closed-loop caller sees it: the
window's length over the calls it completed (host clock; each call from
``Executable.__call__`` on host inputs to its outputs being ready)."""


def read(run):
    if not run.calls:
        return None
    return 1e3 * run.window_s / len(run.calls)
