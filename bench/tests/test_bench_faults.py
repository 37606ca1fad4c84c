"""Each fault a cell can have, planted under the timed path of a run
at a CPU-sized configuration (the look for a chip skipped), turns
``correct`` false; the same run unbroken stays true.  The exchange
between chips has no fault to plant: every cell runs on one chip."""

import jax.numpy as jnp
import pytest

from bench.tests import tiny

B = 4
CNN_BATCH = {"batch": B, "pool": 2}


class Wrapped:
    """An executable whose outputs pass through ``edit``."""

    def __init__(self, exe, edit):
        self.exe, self.edit = exe, edit

    def __call__(self, **kw):
        return {k: self.edit(v) for k, v in self.exe(**kw).items()}

    def cost_summary(self):
        return self.exe.cost_summary()


def _altered(exe):
    return Wrapped(exe, lambda v: v.at[..., 0].add(0.01))


def _half_batch(exe):
    # the second half of the batch left out: its rows are the mean of
    # the rows that were computed
    def edit(v):
        kept = v[: v.shape[0] // 2]
        return v.at[v.shape[0] // 2:].set(kept.mean(axis=0))
    return Wrapped(exe, edit)


@pytest.mark.parametrize("broken,correct", [
    (None, True), (_altered, False), (_half_batch, False)])
def test_compile_path_faults(broken, correct):
    cell = tiny.cell("vgg19.b32", tiny.TINY_CNN, CNN_BATCH)
    run = tiny.drive(cell, seed=5, seconds=0.3, broken=broken)
    assert run.correct is correct, run.checks


def _token_altered(exe):
    def sampler(logits, temperature, *, uid, index):
        tok = int(jnp.argmax(logits, axis=-1)[0])
        return (tok + 1) % logits.shape[-1] if index % 3 == 2 else tok
    return exe, {"sampler": sampler}


def _set(model, name, fn):
    object.__setattr__(model, name, fn)      # the model is frozen


def _state_unchanged(exe):
    step = exe.model.decode_step
    _set(exe.model, "decode_step", lambda p, c, t: (step(p, c, t)[0], c))
    return exe, {}


def _half_slots(exe):
    step = exe.model.decode_step

    def broken(p, c, t):
        logits, c = step(p, c, t)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:half].mean(axis=0)), c
    _set(exe.model, "decode_step", broken)
    return exe, {}


@pytest.mark.parametrize("broken,correct", [
    (None, True), (_token_altered, False), (_state_unchanged, False),
    (_half_slots, False)])
def test_serving_path_faults(broken, correct):
    cell = tiny.cell("qwen14b.chat", tiny.TINY_LM, tiny.TINY_CHAT)
    run = tiny.drive(cell, seed=9, seconds=0.5, broken=broken)
    assert run.correct is correct, run.checks
    assert run.checks and run.checks[0]["name"] == "max_logit_gap"


def test_serving_control_reads_above_the_program():
    """The int8 control in the program's place reads a wider gap than
    the program on the same requests (on the chip, at the cell's size,
    ``bench/control.py`` gives the readings the limit is set from)."""
    cell = tiny.cell("qwen14b.chat", tiny.TINY_LM, tiny.TINY_CHAT)
    run = tiny.drive(cell, seed=11, seconds=0.5)
    program = run.checks[0]["value"]
    assert run.control_reading() > program


def test_serving_control_fails_the_comparison():
    """``bench/control.py`` judges the control in the program's place by
    the run's own comparison: it comes out not correct where the program
    comes out correct.  At this width the int8 control does not separate
    from the program at the cell's limit (both read 0.003-0.09 here), so
    the limit is set at 0.02, between this seed's readings: the program
    0.0034, the control 0.0865."""
    from bench import control
    chk = dict(tiny._read("bench/configs/qwen2.5-14b.json")["check"],
               limit=0.02)
    cell = tiny.cell("qwen14b.chat", dict(tiny.TINY_LM, check=chk),
                     tiny.TINY_CHAT)
    got = control.readings(tiny.drive(cell, seed=9, seconds=0.5))
    assert got["correct"] is True and got["program"] <= got["limit"]
    assert got["control_correct"] is False, got
