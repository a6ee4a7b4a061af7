"""Op-contract gate, enforcement-hard (VERDICT next-round #6).

Named test_zz_* so pytest collects it AFTER every other test file: by the
time it runs, conftest's FLAGS_record_lowered_ops has made the executor
trace (core/executor.py trace_block) and the imperative dispatcher record
every op type actually LOWERED during the session into
monitor.flight.lowered_op_types().

The gate asserts  registry.all_ops() ⊆ executed ∪ CONTRACT_EXEMPT.

Contrast with the old gate (grep test-file text for op-name substrings):
a test that merely *mentioned* "adadelta" in a comment satisfied it.
Here only execution counts — deleting a single op's test (e.g.
`--deselect tests/test_op_contract.py::TestAdadelta` and the op goes
red) breaks the build, which is the reference's every-op-has-a-test
contract (unittests/op_test.py) with teeth.
"""

import pytest

from test_op_contract import CONTRACT_EXEMPT

# Below this many distinct executed ops the session was clearly a partial
# run (single file / -k selection) where the gate is meaningless noise;
# a full default session records ~260.  Deleting ONE op's tests moves the
# count by single digits — nowhere near the skip line.
MIN_RECORDED_FOR_GATE = 150


def _recorded():
    from paddle_tpu.monitor import flight

    return flight.lowered_op_types()


def _partial(recorded):
    """Why this session cannot carry the gate, or None.  An xdist worker
    records only the files it was handed, however many ops those run: the
    gate is for a one-process session."""
    import os

    if os.environ.get("PYTEST_XDIST_WORKER"):
        return "an xdist worker sees only its own files' ops"
    if len(recorded) < MIN_RECORDED_FOR_GATE:
        return (f"only {len(recorded)} ops executed this session — the "
                "op-contract gate needs a full-suite run")
    return None


def test_registry_subset_of_executed_ops():
    from paddle_tpu.core import registry

    recorded = _recorded()
    why = _partial(recorded)
    if why:
        pytest.skip(why)
    missing = [op for op in registry.all_ops()
               if op not in recorded and op not in CONTRACT_EXEMPT]
    assert not missing, (
        f"{len(missing)} registered ops were never executed by any test "
        f"this session (add a test that RUNS the op, or an exemption "
        f"with a reason in test_op_contract.CONTRACT_EXEMPT): {missing}")


def test_contract_exemptions_not_stale():
    """An exempt op that IS executed means the exemption outlived its
    reason — prune it so the gate stays honest."""
    recorded = _recorded()
    why = _partial(recorded)
    if why:
        pytest.skip(why)
    stale = sorted(op for op in CONTRACT_EXEMPT if op in recorded)
    assert not stale, (
        f"CONTRACT_EXEMPT entries are now executed by tests — remove "
        f"them: {stale}")


def test_exemptions_name_registered_ops():
    """Exemptions must reference live registry entries (catches typos and
    ops deleted out from under their exemption)."""
    from paddle_tpu.core import registry

    regs = set(registry.all_ops())
    dead = sorted(op for op in CONTRACT_EXEMPT if op not in regs)
    assert not dead, f"CONTRACT_EXEMPT names unregistered ops: {dead}"
