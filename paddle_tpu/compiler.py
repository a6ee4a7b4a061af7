"""CompiledProgram: compile-time strategy wrapper (reference:
python/paddle/fluid/compiler.py:33 CompiledProgram,
with_data_parallel:72 wrapping ParallelExecutor).

TPU-first: `with_data_parallel` does NOT build per-device SSA graphs with
collective op-handles (details/multi_devices_graph_pass.cc).  It names a
layout, the batch sharded over a one-axis `jax.sharding.Mesh` and the state
replicated, and the executor's one call path jits the same traced step
function with it (run, run_steps and run_accumulated alike); XLA SPMD
inserts the all-reduces over ICI.  BuildStrategy /
ExecutionStrategy are kept as typed knobs for parity (build_strategy.h:34,
execution_strategy.h:22) — most of their fields are no-ops under XLA and are
documented as such.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from .core import framework as fw
from .core import executor as exec_mod


class ReduceStrategy:
    AllReduce = 0
    Reduce = 1


class BuildStrategy:
    """Parity container (details/build_strategy.h:34).  Under XLA SPMD most
    knobs are subsumed by the compiler; kept so user code ports cleanly."""

    ReduceStrategy = ReduceStrategy

    def __init__(self):
        self.reduce_strategy = ReduceStrategy.AllReduce
        self.gradient_scale_strategy = 0
        self.debug_graphviz_path = ""
        self.enable_sequential_execution = False
        self.fuse_elewise_add_act_ops = False  # XLA fuses automatically
        self.memory_optimize = True  # XLA buffer assignment
        self.enable_inplace = True
        self.cache_runtime_context = True


class ExecutionStrategy:
    def __init__(self):
        self.num_threads = 0  # XLA owns scheduling
        self.num_iteration_per_drop_scope = 1
        self.allow_op_delay = False
        self.use_experimental_executor = False


class CompiledProgram:
    def __init__(self, program: fw.Program):
        self._program = program
        self._data_parallel = False
        self._loss_name = None
        self._build_strategy = None
        self._exec_strategy = None
        self._share_vars_from = None
        self._places = None
        self._mesh = None
        self._layout = None

    # -- public API (parity: compiler.py:72) ------------------------------
    def with_data_parallel(
        self,
        loss_name: Optional[str] = None,
        build_strategy: Optional[BuildStrategy] = None,
        exec_strategy: Optional[ExecutionStrategy] = None,
        share_vars_from: Optional["CompiledProgram"] = None,
        places: Optional[Sequence] = None,
    ) -> "CompiledProgram":
        self._data_parallel = True
        self._loss_name = loss_name
        self._build_strategy = build_strategy or BuildStrategy()
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._share_vars_from = share_vars_from
        self._places = places
        return self

    # -- what the executor asks for -----------------------------------------
    def _get_mesh(self):
        import jax
        from jax.sharding import Mesh

        if self._mesh is not None:
            return self._mesh
        devices = np.array(jax.devices())
        if self._places is not None and len(self._places) > 0 and not isinstance(
            self._places[0], exec_mod.Place
        ):
            devices = np.array(list(self._places))
        self._mesh = Mesh(devices, axis_names=("data",))
        return self._mesh

    def _unwrap(self):
        """What the executor runs in this wrapper's place (core/executor.py
        `_unwrap`): the program and, in data-parallel mode, the layout
        "batch over `data`, everything else replicated".  XLA SPMD inserts
        the gradient all-reduces."""
        if not self._data_parallel:
            return self._program, None
        if self._layout is None:
            from jax.sharding import PartitionSpec as P

            self._layout = exec_mod.Layout(
                self._get_mesh(), lambda name: P("data"),
                lambda name, shape: P())
        return self._program, self._layout
