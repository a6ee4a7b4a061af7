"""The plain references against the program on the CPU at the tiny
presets, dropout 0, float32, no amp: so that a disagreement on the chip
points at the chip path and not at the reference."""

import pytest

import helpers
import registry
import traffic_gen


@pytest.mark.parametrize("which", ["transformer", "bert"])
def test_reference_follows_the_program_in_float32(which):
    cell = helpers.tiny_cell(which, amp=False)
    train = registry.load_driver("train")
    tc = train.TrainCell(cell)
    seed = 2 ** 31 + 11
    feeds = traffic_gen.train_feeds(cell.traffic, cell.cfg, seed)
    obs = tc.first_calls(seed, feeds)
    ref = tc.reference(seed, feeds)
    numbers, where = train.numbers_of(obs, ref)
    assert numbers["loss_gap"] < 1e-5, numbers
    assert numbers["loss0_gap"] < 1e-5, numbers
    assert numbers["grad_diff"] < 1e-3, (numbers, where)
    assert numbers["frozen_moved"] == 0.0, numbers
    assert numbers["grad_gap"] < 1e-4, (numbers, where)
    assert numbers["step_gap"] < 1e-3, (numbers, where)
    assert where["left_out"] == []


def test_feeds_repeat_by_seed_and_rows_differ():
    cell = helpers.tiny_cell("transformer")
    a = traffic_gen.train_feeds(cell.traffic, cell.cfg, 3000000019)
    b = traffic_gen.train_feeds(cell.traffic, cell.cfg, 3000000019)
    c = traffic_gen.train_feeds(cell.traffic, cell.cfg, 3000000020)
    assert all((a[i][k] == b[i][k]).all() for i in range(len(a)) for k in a[i])
    assert not (a[0]["src_word"] == c[0]["src_word"]).all()
    rows = a[0]["trg_word"].reshape(-1, a[0]["trg_word"].shape[2])
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert {k: v.shape for k, v in a[0].items()} == {
        k: v.shape for k, v in c[1].items()}
