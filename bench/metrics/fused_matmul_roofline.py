"""The dense Pallas kernel (``kernels/fused_matmul``) against its
roofline: for every call in the traced window, the larger of the dense
layers' operations over the bf16 peak and their bytes (float32
weights, bias, input and output, each once) over the HBM bandwidth,
over the kernel's device time in the trace.

The trace does not name Pallas kernels: each is a ``tpu_custom_call``
operation named after the program around it.  So the reader takes the
``tpu_custom_call`` operations, and reads nothing unless they number
exactly the calls times the dense nodes the selector gave
``pallas.fused_matmul`` (another kernel on the path would be counted
as this one)."""

from bench import flops

KERNEL = r'custom_call_target="tpu_custom_call"'


def read(run):
    if run.trace is None or not run.calls:
        return None
    dense = run.counters.get("dense_kernels_per_call", 0)
    t, n = run.trace.time_s(KERNEL)
    if dense == 0 or t <= 0 or n != dense * run.counters["traced_calls"]:
        return None
    f, b = flops.dense_cost(run.config, run.batch)
    bound = max(f / run.peaks["bf16_flops_per_s"],
                b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * bound * run.counters["traced_calls"] / t
