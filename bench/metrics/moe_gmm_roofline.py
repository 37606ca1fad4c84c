"""The grouped matmul of the expert layers (``kernels/grouped_matmul``,
kernel name ``moe_gmm``) against its roofline: over the window's decode
steps and prefill chunks, the larger of the operations of the
token-choices routed to the experts held here over the bf16 peak and
their bytes (each hit expert's weights once, each row in and out) over
the HBM bandwidth, from the program's expert counters
(``bench/expert_counts.py``), over the kernels' device time in the
trace.  The counters count the rows the kernel was handed, the choices
of a decode step's empty slots among them: this judges the kernel on
its work, not the step on what it needs.  Nothing without a trace, the
kernel or the counters."""

from bench import expert_counts, flops_moe

KERNEL = r"moe_gmm"


def read(run):
    if run.trace is None:
        return None
    t, n = run.trace.time_s(KERNEL)
    counts = expert_counts.window_totals(run)
    if n == 0 or t <= 0 or counts is None:
        return None
    f, b = flops_moe.gmm_cost(run.config, counts["held_choices"],
                              counts["experts_hit"])
    bound = max(f / run.peaks["bf16_flops_per_s"],
                b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / t
