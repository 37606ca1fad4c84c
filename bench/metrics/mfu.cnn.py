"""Whole-call share of the chip's bf16 peak: the network's operations
(two per multiply-add, from its shapes) times the calls completed,
over the window and the peak."""

from bench import flops


def read(run):
    if not run.calls or not run.peaks:
        return None
    done = flops.cnn_flops(run.config, run.batch) * len(run.calls)
    return 100.0 * done / run.window_s / run.peaks["bf16_flops_per_s"]
