"""The rest of a run with the timed path broken underneath: `correct` has
to come out false, once for each fault a one-chip training cell can have.
The tiny cells have limits of their own (data/limits_tiny_*.json, set from
CPU readings by the rule the real cells' limits were set by: a loss over
256 tokens is noisier than one over 16,384).  (There is no exchange between
chips to leave out, and no token is produced.)  The harness's look for a
chip is skipped: the driver is called as run.py calls it, with a `TrainCell`
whose timed entry, `call`, is broken."""

import pytest

import helpers
import registry


def _one_leaf(tc):
    return tc.trainable[len(tc.trainable) // 2]


def _broken(train, fault):
    """A `TrainCell` with `fault` planted under its timed entry."""

    class Broken(train.TrainCell):
        def _kept(self):
            if fault == "one_leaf_unchanged":  # the parameter; Adam's
                return [self.param_of[_one_leaf(self)]]  # moments go on
            return list(self.param_of.values()) + list(
                self.moment_of.values())

        def call(self, feed):
            if fault == "half_batch":
                return super().call(
                    train.half_batch(feed, self.ref_mod.WEIGHTS_FIELD))
            keep = {n: self.jax.numpy.array(self.scope.find_var(n), copy=True)
                    for n in self._kept()}
            losses = super().call(feed)
            for n, x in keep.items():
                self.scope.set_var(n, x)
            return losses

    return Broken


def _run(which, fault, tmp_path):
    cell = helpers.tiny_cell(which, limits=helpers.tiny_limits(which),
                             out_dir=str(tmp_path))
    train = registry.load_driver("train")
    return train.run(cell, 2 ** 31 + 5, 0.5, False, compile_cache=False,
                     cell_class=_broken(train, fault) if fault
                     else train.TrainCell)


@pytest.mark.parametrize("which", ["transformer", "bert"])
def test_sound_run_is_correct(which, tmp_path):
    r = _run(which, None, tmp_path)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] >= 3
    assert r["train_tokens_per_s"] > 0


@pytest.mark.parametrize("which", ["transformer", "bert"])
@pytest.mark.parametrize("fault", ["state_unchanged", "one_leaf_unchanged",
                                   "half_batch"])
def test_fault_under_the_timed_path_is_not_correct(which, fault, tmp_path):
    r = _run(which, fault, tmp_path)
    assert not r["correct"], r["compared"]
    c = r["compared"]
    over = {n for n, x in c.items() if not x["value"] <= x["limit"]}
    if fault == "state_unchanged":
        # by the measure of norms an unmoved leaf reads 1 (the median leaf
        # a little less: a leaf under the median norm is held against it)
        assert c["step_gap_median"]["value"] > 0.5
        assert c["step_gap"]["value"] == pytest.approx(1.0)
        assert c["grad_gap"]["value"] == pytest.approx(1.0)
        assert c["grad_diff"]["value"] == pytest.approx(1.0)
    elif fault == "one_leaf_unchanged":
        # only the worst leaf's change sees one parameter left unmoved: the
        # median leaf's does not, and the moments gather all the same
        assert over == {"step_gap"}, c
        assert c["step_gap"]["value"] > 0.5
        assert r["where"]["step_gap"] is not None
    else:
        assert "grad_diff" in over
