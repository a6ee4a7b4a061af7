"""Where a Pallas kernel may run — the ONE place a plan gate asks.

Every kernel family routes the same way: the compiled Mosaic kernel on
the TPU, the XLA reference off it or on a rejected plan, and the Pallas
interpreter only where a caller passes `interpret=True` (the kernel
tests) or — off the TPU — by default, so the CPU suite exercises the
kernel bodies.  On the TPU backend nothing defaults to the interpreter.

A compiled kernel also needs a trace it can be placed in: under a
GSPMD-partitioned program (CompiledProgram / ShardedProgram tracing with
a mesh) the lowering refuses — "Mosaic kernels cannot be automatically
partitioned. Please wrap the call in a shard_map" (jax 0.9.0) — so the
gates see `compiled=False` there and the sharded step takes the XLA
references, which GSPMD partitions.  Inside a fully-manual shard_map
(ring attention) the kernel runs per shard and is placeable again.
"""

from __future__ import annotations

import contextlib
import contextvars

_GSPMD_TRACE = contextvars.ContextVar("paddle_tpu_gspmd_trace",
                                      default=False)


@contextlib.contextmanager
def gspmd_trace(active: bool = True):
    """Mark the enclosed trace as GSPMD-partitioned (or, with
    active=False, as manual again — the body of a shard_map)."""
    token = _GSPMD_TRACE.set(bool(active))
    try:
        yield
    finally:
        _GSPMD_TRACE.reset(token)


def resolve(interpret):
    """(compiled, interpret) for a plan gate given the caller's
    `interpret` argument (None = default routing).

    compiled:  a Mosaic kernel can be compiled and placed here — TPU
               backend, not interpreted, not inside a GSPMD trace.
    interpret: run the Pallas interpreter — only an explicit True, or the
               default off the TPU.
    A gate accepts when `compiled or interpret`; neither means the XLA
    reference."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = not on_tpu
    interpret = bool(interpret)
    compiled = on_tpu and not interpret and not _GSPMD_TRACE.get()
    return compiled, interpret
