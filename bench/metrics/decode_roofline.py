"""The decode program against its roofline: over the window's decode
steps, the larger of their operations over the bf16 peak and their
bytes (bf16 weights once, the live K/V rows, the float32 logits of
every slot) over the HBM bandwidth, over the decode programs' device
time in the trace (XLA modules of the scheduler's decode step)."""

from bench import flops

#: Trace names of the decode program's executions.
PROGRAM = r"decode_body"


def read(run):
    if run.trace is None:
        return None
    from bench.trace_reduce import MODULES_LINE
    t, n = run.trace.time_s(PROGRAM, line=MODULES_LINE)
    steps = [s for s in run.window_steps() if s.decode_contexts]
    if n == 0 or t <= 0 or not steps:
        return None
    slots = run.config["serve"]["slots"]
    bound = 0.0
    for s in steps:
        f, b = flops.lm_decode_cost(run.config, s.decode_contexts, slots)
        bound += max(f / run.peaks["bf16_flops_per_s"],
                     b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / t
