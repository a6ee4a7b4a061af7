"""The comparison that decides `correct` for a training cell: what the
timed `run_steps` entry left after its first call, against the plain
reference that followed the same steps from the same weights and rows.

A call is `steps_per_call` steps in one scan, so the program's state is
visible only after a whole call, and over a whole call at the cell's
learning rate Adam's sign-like updates carry two sound trajectories apart
by more than any precision does (PERF.md, section 6).  So set-up drives the
same compiled entry through its first call twice, each time from fresh
state:

- FROZEN, with the learning rate (a variable of the scope) at nought: the
  weights stay, and Adam's first moment after the call is a weighted sum of
  the call's gradients, all taken at the initial weights, as the optimizer
  got them.  Compared by the norm of the DIFFERENCE, which is first-order
  in the precision of the arithmetic:
  `grad_diff`, `grad_diff_median`: by the worst and by the median leaf,
  |m_program - m_reference| against the reference's norm of that leaf or of
  the median leaf, whichever is larger;
  `loss0_gap`: the widest relative gap of the call's losses (every step's
  loss is then a forward pass at the initial weights);
  `loss0_rms`: the root mean square of the same gaps.  A step's gap is
  rounding noise of either sign, in the control too, so the call's steps
  together say more of its size than the largest of them does.
- TRAINED, as the cell states it; the window goes on from this state.
  Compared by the gap between NORMS, which survives the sign-like updates:
  `loss_gap`: the widest relative gap of the per-step losses;
  `grad_gap`, `grad_gap_median`: the gap between the norms of the first
  moment, by the worst and the median leaf, same denominator;
  `step_gap`, `step_gap_median`: the same for each parameter's change over
  the call.  Leaves whose reference gradient is under a thousandth of the
  median leaf's move by round-off alone under Adam and are left out.

`train_numbers` works out all of them; a cell's limits file
(perfbench/limits/) names the ones it is held to, each with its limit."""

import statistics

DEAD_GRADIENT = 1e-3


def _by_leaf(err, ref, names):
    """({leaf: err / max(ref, median ref)}, worst value, worst leaf)."""
    floor = statistics.median(ref[k] for k in names)
    rel = {k: err[k] / max(ref[k], floor, 1e-30) for k in names}
    worst, at = 0.0, None
    for k in names:
        if not rel[k] <= worst:  # a NaN is the worst there is
            worst, at = rel[k], k
    return rel, float(worst), at


def _loss_gap(prog, ref):
    if len(prog) != len(ref):
        return float("nan")
    return float(max(abs(p - r) / abs(r) for p, r in zip(prog, ref)))


def _loss_rms(prog, ref):
    if len(prog) != len(ref):
        return float("nan")
    return float(statistics.fmean(((p - r) / r) ** 2
                                  for p, r in zip(prog, ref)) ** 0.5)


def train_numbers(prog, ref):
    """`prog` and `ref`: {"frozen": .., "trained": ..}, each {"losses":
    [..], "m_norm": {leaf: x}, "dp_norm": {leaf: x}}; the reference's also
    have "g1_norm" (the first step's gradient norms) and the program's
    frozen one "m_diff_norm" (norm of its first moment less the
    reference's).  Returns ({name: value}, {name: worst leaf})."""
    pf, rf, pt, rt = (prog["frozen"], ref["frozen"],
                      prog["trained"], ref["trained"])
    names = sorted(rt["m_norm"])
    g_med = statistics.median(rt["g1_norm"][k] for k in names)
    live = [k for k in names if rt["g1_norm"][k] >= DEAD_GRADIENT * g_med]

    def gaps(key, which):
        err = {k: abs(pt[key][k] - rt[key][k]) for k in which}
        return _by_leaf(err, rt[key], which)

    g_rel, grad_gap, grad_at = gaps("m_norm", names)
    s_rel, step_gap, step_at = gaps("dp_norm", live)
    d_rel, grad_diff, diff_at = _by_leaf(pf["m_diff_norm"], rf["m_norm"],
                                         names)
    numbers = {
        "grad_diff": grad_diff,
        "grad_diff_median": float(statistics.median(d_rel.values())),
        "loss0_gap": _loss_gap(pf["losses"], rf["losses"]),
        "loss0_rms": _loss_rms(pf["losses"], rf["losses"]),
        "frozen_moved": float(max(pf["dp_norm"].values())),
        "loss_gap": _loss_gap(pt["losses"], rt["losses"]),
        "grad_gap": grad_gap,
        "grad_gap_median": float(statistics.median(g_rel.values())),
        "step_gap": step_gap,
        "step_gap_median": float(statistics.median(s_rel.values())),
    }
    return numbers, {"grad_gap": grad_at, "step_gap": step_at,
                     "grad_diff": diff_at,
                     "left_out": [k for k in names if k not in live]}


def verdict(numbers, limits):
    """(correct, {name: {"value": v, "limit": l}}): every number that has a
    limit must be a number and lie at or under it."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers[name]
        compared[name] = {"value": value, "limit": limit}
        if not value <= limit:
            ok = False
    return ok, compared
