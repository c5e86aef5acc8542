"""A run whose timed path is broken underneath comes out not correct, and
so does the control: the reference at one HyperLogLog bit less than the
configuration states, put in the program's place."""
import pytest

import faults
import run as harness
from cells import run_tiny, tiny
from reference import compare

CELLS = ("bsbm_dump.ntriples", "bsbm_dump.encoded", "bsbm_update.changesets")


@pytest.fixture
def planted():
    undo = []
    yield lambda name: undo.append(faults.plant(name))
    for u in undo:
        u()


@pytest.mark.parametrize("name", CELLS)
def test_half_of_the_batch_left_out(planted, name):
    planted("half")
    out = run_tiny(name)
    assert not out["correct"]
    assert out["checks"]["counts_gap"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_an_answer_altered_where_it_is_produced(planted, name):
    planted("altered")
    out = run_tiny(name)
    assert not out["correct"]
    assert out["checks"]["counts_gap"]["value"] > 0


def test_a_step_that_returns_its_state_unchanged(planted):
    planted("stale")
    out = run_tiny("bsbm_update.changesets")
    assert not out["correct"]


def test_a_planted_fault_is_removed_again():
    from repro.core.evaluator import QualityEvaluator
    real = QualityEvaluator.__dict__["materialize_chunk"]
    faults.plant("altered")()
    assert QualityEvaluator.__dict__["materialize_chunk"] is real
    with pytest.raises(ValueError):
        faults.plant("nothing")


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct_and_the_program_is(name, tmp_path):
    _, _, config, traffic = tiny(name)
    runner = harness.load_module("runners", traffic["runner"]).Runner(
        config, traffic, 2**31 + 9, str(tmp_path))
    try:
        runner.setup()
        steps = [runner.step() for _ in range(2)]
        sound = compare.worst(runner.gaps(steps))
        control = compare.worst(runner.gaps(steps, control=True))
    finally:
        runner.close()
    assert compare.within(sound), sound
    assert not compare.within(control), control
    assert control["registers_gap"] == 2 * 4096
