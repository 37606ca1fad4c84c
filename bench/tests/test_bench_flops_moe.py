"""DeepSeek-V3's operation and byte counts (``bench/flops_moe.py``)
against the published sizes, and the decode step's count of the routed
experts from the live slots alone."""

import pytest

from bench import flops_moe
from bench.tests import tiny

CFG = tiny._read("bench/configs/deepseek-v3.json")


def test_parameter_counts_match_the_sizing():
    # the configuration's sizing: MLA 187.1 M a layer, a routed expert
    # 44.04 M, the dense MLP 396.4 M
    assert flops_moe.mla_params(CFG) == pytest.approx(187.1e6, rel=1e-3)
    assert flops_moe.expert_params(CFG) == 3 * 7168 * 2048
    assert flops_moe.expert_params(CFG) == pytest.approx(44.04e6, rel=1e-3)
    dense_mlp = (flops_moe.token_params(CFG) - 5 * flops_moe.mla_params(CFG)
                 - 4 * (7168 * 256 + flops_moe.expert_params(CFG)))
    assert dense_mlp == 3 * 7168 * 18432


@pytest.mark.parametrize("live", [1, 12, 32])
def test_experts_hit_follow_the_live_tokens(live):
    """Each held expert is hit with probability 1 - (31/32)^live in each
    of the 4 expert layers."""
    want = 4 * 8 * (1 - (1 - 8 / 256) ** live)
    assert flops_moe.expected_experts_hit(CFG, live) == pytest.approx(want)
    assert flops_moe.expected_experts_hit(CFG, live) < 4 * 8


def test_decode_cost_counts_live_slots_only():
    """A second live slot adds its token's work and the held experts it
    is expected to hit; the program's empty slots add only their
    float32 logits, counted once for every slot."""
    f1, b1 = flops_moe.decode_cost(CFG, [100], slots=32)
    f2, b2 = flops_moe.decode_cost(CFG, [100, 300], slots=32)
    assert f2 - f1 == pytest.approx(
        flops_moe.token_flops(CFG, 300, head=True))
    hit = (flops_moe.expected_experts_hit(CFG, 2)
           - flops_moe.expected_experts_hit(CFG, 1))
    row = 5 * (512 + 64) * 2
    assert b2 - b1 == pytest.approx(
        2 * hit * flops_moe.expert_params(CFG) + row * 301)
    _, b_more_slots = flops_moe.decode_cost(CFG, [100], slots=64)
    assert b_more_slots - b1 == 4.0 * 32 * 129280


class _Trace:
    """Stands in for a trace: ``seconds`` of device time in each of
    ``count`` matching operations or programs."""

    def __init__(self, seconds, count=1):
        self.seconds, self.count = seconds, count

    def time_s(self, pattern, line=None):
        return self.seconds, self.count


def _run(steps, trace=None):
    from bench import device, record
    run = record.Run(config=CFG, traffic={}, window_start=0.0,
                     window_end=10.0, steps=steps, trace=trace)
    run.peaks = device.peaks_for("TPU v5 lite", device.load_peaks())
    return run


def _reader(name):
    import importlib.util
    path = f"{tiny.ROOT}/bench/metrics/{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_decode_roofline_reads_the_live_slots_bound_over_the_trace():
    from bench import record
    steps = [record.Step(1.0, 1.1, [500] * 12, 0.0, 12),
             record.Step(2.0, 2.1, [], 0.0, 0)]
    run = _run(steps, _Trace(0.01))
    f, b = flops_moe.decode_cost(CFG, [500] * 12, 32)
    want = 100 * max(f / run.peaks["bf16_flops_per_s"],
                     b / run.peaks["hbm_bytes_per_s"]) / 0.01
    assert _reader("decode_roofline.dsv3")(run) == pytest.approx(want)
    assert _reader("decode_roofline.dsv3")(_run(steps)) is None


def test_gmm_roofline_reads_the_counters_in_the_window():
    from bench import expert_counts, record
    steps = [record.Step(1.0, 1.1, [500], 0.0, 1)]
    saved = list(expert_counts.EVENTS)
    try:
        expert_counts.EVENTS[:] = [(1.05, "held_choices", 6.0),
                                   (1.05, "experts_hit", 5.0),
                                   (1.5, "held_choices", 99.0)]
        run = _run(steps, _Trace(1e-3, 8))
        assert expert_counts.window_totals(run) == {
            "held_choices": 6.0, "experts_hit": 5.0}
        f, b = flops_moe.gmm_cost(CFG, 6.0, 5.0)
        want = 100 * max(f / run.peaks["bf16_flops_per_s"],
                         b / run.peaks["hbm_bytes_per_s"]) / 1e-3
        assert _reader("moe_gmm_roofline")(run) == pytest.approx(want)
        expert_counts.EVENTS[:] = []
        assert _reader("moe_gmm_roofline")(run) is None
    finally:
        expert_counts.EVENTS[:] = saved
