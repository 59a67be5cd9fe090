"""What decides ``correct``: the references agree with the program at
a small size, the control (the reference one precision down, put in
the program's place) comes out as not correct, and so does a run
whose timed path is broken underneath.  The sizes are the rehearsal's
(benchmark/testdata/rehearse); the limits there were set, as the
cells' own, between the program's readings and the control's."""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import correct, train  # noqa: E402

# ----------------------------------------------------------- training
@pytest.fixture(scope="module")
def lm_readings(harness):
    """The program's and the reference's first steps of the toy LM."""
    import jax

    import incubator_mxnet_tpu as mx
    os.environ["MXTPU_FLASH"] = "1"
    cell = harness.cell("tiny-lm.train")
    fam, block, step, batches = train.build(harness, cell, 5, mx,
                                            jax.devices()[0])
    shapes = fam.param_shapes(cell.config)
    prog = train.first_steps(step, batches, cell.config, shapes, 5,
                             block.prefix, 3)
    ref = correct.reference_training(fam, cell.config, 5, batches[:3])
    return cell, fam, batches[:3], prog, ref


def test_lm_reference_agrees_with_the_program(lm_readings):
    cell, _, _, prog, ref = lm_readings
    ok, table = correct.verdict(correct.compare_training(prog, ref),
                                cell.limits)
    assert ok, table
    assert table["loss1"]["value"] < 1e-4
    assert table["grad1"]["value"] < 0.03
    assert set(prog["grad1"]) == set(ref["grad1"])


def test_lm_control_one_precision_down_is_not_correct(lm_readings):
    cell, fam, batches, _, ref = lm_readings
    assert cell.config["train"]["control_precision"] == "fp8"
    low = correct.reference_training(fam, cell.config, 5, batches,
                                     mode="fp8")
    ok, table = correct.verdict(correct.compare_training(low, ref),
                                cell.limits)
    assert not ok, table


def test_half_the_batch_left_out_is_not_correct(lm_readings):
    cell, fam, batches, _, ref = lm_readings
    cut = correct.reference_training(fam, cell.config, 5, batches,
                                     rows=1)
    ok, table = correct.verdict(correct.compare_training(cut, ref),
                                cell.limits)
    assert not ok and table["grad1"]["value"] > 0.1, table


def test_a_sound_run_of_the_train_cell_is_correct(measure):
    result = measure("tiny-lm.train", seconds=0.1)
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "train_step_ms"}
    assert list(result)[-1] == "compared"


def _broken_step(kind):
    """The program's step with a fault planted underneath."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel import ShardedTrainStep
    sound = ShardedTrainStep.__call__

    def unchanged(self, x, y, rng=None):
        keep = jax.tree_util.tree_map(
            jnp.copy, (self.params, self.states, self.opt_state,
                       self.step_count))
        loss = sound(self, x, y, rng)
        (self.params, self.states, self.opt_state,
         self.step_count) = keep
        return loss

    def half_batch(self, x, y, rng=None):
        half = x.shape[0] // 2
        return sound(self, x[:half], y[:half], rng)

    return {"unchanged": unchanged, "half_batch": half_batch}[kind]


@pytest.mark.parametrize("fault,caught_by", [
    ("unchanged", "change3"), ("half_batch", "grad1")])
def test_a_broken_train_step_comes_out_not_correct(monkeypatch, measure,
                                                   fault, caught_by):
    from incubator_mxnet_tpu.parallel import ShardedTrainStep
    monkeypatch.setattr(ShardedTrainStep, "__call__",
                        _broken_step(fault))
    result = measure("tiny-lm.train", seconds=0.1)
    assert not result["correct"], result["compared"]
    row = result["compared"][caught_by]
    assert row["value"] > row["limit"]
    if fault == "unchanged":    # nothing moved: the measure reads 1
        assert row["value"] == pytest.approx(1.0, abs=1e-6)


# ------------------------------------- the chip's readings, kept as rows
def _chip_rows():
    from benchmark.harness import HERE
    folder = os.path.join(HERE, "readings")
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name)) as f:
            for line in f:
                row = json.loads(line)
                yield pytest.param(
                    name[:-len(".jsonl")], row,
                    id=f"{name[:-6]}-{row['who']}-{row['seed']}")


@pytest.mark.parametrize("cell,row", list(_chip_rows()))
def test_chip_readings_under_the_committed_limits(cell, row):
    """readings/<cell>.jsonl keeps what calibrate.py and the sets read
    on the chip at the cell's own size: under the limits as committed
    every sound run of the program is correct, every control and
    planted fault is not.  But for a serve cell's control on a seed
    whose text is too quiet (``..._text_too_quiet``: wide margins, so
    that the control's yardstick lies under ``yardstick_flips``): its
    tokens are the reference's own there, and it passes as any
    program would."""
    from benchmark.harness import Harness
    limits = Harness().cell(cell).limits
    numbers = {k: (row[k], None) for k in limits if k in row}
    assert numbers
    ok, table = correct.verdict(numbers, limits)
    assert ok == (row["who"] == "program"
                  or row["who"].endswith("_text_too_quiet")), table
