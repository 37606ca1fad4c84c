"""Whole-step share of the chip's bf16 peak while the scheduler works,
for a DeepSeek-V3-style model: the model operations of every decode
step (each live slot at its context) and of every prompt whose prefill
finished in the window's steps (of the requests due in the window),
counted from the requests' prompt lengths, over the summed wall time of those steps and the peak.  Routed
experts count only those held here, at their expected share of each
token's choices (``bench/flops_moe.py``)."""

from bench import flops_moe


def read(run):
    steps = run.window_steps()
    busy = sum(s.end - s.start for s in steps)
    if busy <= 0 or not run.peaks:
        return None
    cfg = run.config
    done = sum(flops_moe.token_flops(cfg, c, head=True)
               for s in steps for c in s.decode_contexts)
    lo, hi = steps[0].start, steps[-1].end
    done += sum(flops_moe.prefill_flops(cfg, r.prompt_tokens)
                for r in run.requests
                if r.first_token is not None and lo <= r.first_token <= hi)
    return 100.0 * done / busy / run.peaks["bf16_flops_per_s"]
