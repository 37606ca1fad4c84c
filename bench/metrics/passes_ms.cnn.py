"""Time the compiler's passes took (PassManager ``time_ms`` summed over
``cost_summary()["passes"]``): the paper's compilation time without
the backend compile."""


def read(run):
    return run.counters.get("passes_ms")
