"""The control of `correct`: the plain reference computed in fp8, the
nearest precision below the configurations' bfloat16, put in the program's
place.  It has to come out NOT correct, and the program in bfloat16 amp
correct, at a size a test run can hold: under the tiny cells' own limits
(see test_faults.py), by `loss0_rms`, the root mean square of the frozen
call's loss gaps, which is the number that fails it in both real cells on
the chip (perfbench/calibrate.py puts the control through the same
`compare.verdict` there, under the real cells' limits)."""

import pytest

import compare
import helpers
import registry
import traffic_gen

SEEDS = [7, 2 ** 31 + 8, 3000000021]


@pytest.mark.parametrize("which", ["transformer", "bert"])
def test_fp8_control_fails_and_bf16_program_passes(which):
    cell = helpers.tiny_cell(which, limits=helpers.tiny_limits(which))
    train = registry.load_driver("train")
    tc = train.TrainCell(cell)
    for seed in SEEDS:
        feeds = traffic_gen.train_feeds(cell.traffic, cell.cfg, seed)
        ref = tc.reference(seed, feeds)
        ctl = tc.reference(seed, feeds, mode=cell.cfg["control_precision"])
        numbers = train.numbers_of(ctl, ref)[0]
        ok, compared = compare.verdict(numbers, cell.limits)
        assert not ok, (seed, compared)
        assert numbers["loss0_rms"] > cell.limits["loss0_rms"], (seed, numbers)
        numbers = train.numbers_of(tc.first_calls(seed, feeds), ref)[0]
        ok, compared = compare.verdict(numbers, cell.limits)
        assert ok, (seed, compared)
