"""The decode program of a DeepSeek-V3-style model against its
roofline: ``decode_roofline``'s reading with this model's count
(``bench/flops_moe.decode_cost``): latent attention, the dense prefix,
the shared experts, the router and the head once, the held experts
that the live slots' tokens hit at their expected share (the routing
of empty slots is no work the step requires), the live latent rows
and the float32 logits of every slot, over the decode programs' device
time in the trace."""

from bench import flops_moe

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
        f, b = flops_moe.decode_cost(run.config, s.decode_contexts, slots)
        bound += max(f / run.peaks["bf16_flops_per_s"],
                     b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / t
