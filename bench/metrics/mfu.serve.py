"""Whole-step share of the chip's bf16 peak while the scheduler works:
the model operations of every decode step (each slot at its live
context) and every prompt whose prefill finished, in the window's
steps, over the summed wall time of those steps and the peak.  A
prompt's prefill is counted in the step that gave its first token."""

from bench import flops


def read(run):
    steps = run.window_steps()
    busy = sum(s.end - s.start for s in steps)
    if busy <= 0 or not run.peaks:
        return None
    done = sum(s.prefill_flops + sum(flops.lm_token_flops(
        run.config, c, head=True) for c in s.decode_contexts) for s in steps)
    return 100.0 * done / busy / run.peaks["bf16_flops_per_s"]
