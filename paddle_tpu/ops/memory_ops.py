"""Memory-tier ops: the recompute scheduling gate and the host-offload
memcpy pair (paddle_tpu/memory — the Fluid memory-optimization transpiler
class, rebuilt as graph rewrites over XLA).

All three are IDENTITY ops value-wise; what they buy is scheduling/CSE
structure the memory rewrites need:

  * `recompute_barrier` — optimization_barrier identity.  The recompute
    pass (memory/recompute.py) reads every cloned segment's boundary
    inputs through one of these so (a) XLA's CSE cannot merge the clone
    chain back into the stashed original (which would silently reinstate
    the activation stash the pass removed), and (b) when a `Gate` value
    from the incoming backward is attached, the barrier ties the clone
    chain's start to the backward front — the jax.checkpoint
    scheduling idiom, so the recomputation cannot be hoisted into the
    forward where it would defeat the memory win.
  * `memcpy_d2h` / `memcpy_h2d` — paired host-offload copies
    (memory/offload.py): d2h parks a long-lived stash var in host memory
    at its last forward use; h2d fetches it back at the backward's first
    read (Gate-tied like the barrier).  Lowerings ride
    jax.device_put to a memory space (jax.memory.Space.Host <-> Device)
    inside the trace — value-identical to the un-offloaded program,
    asserted in tests/test_memory.py.  Eagerly-executed (imperative)
    memcpys ride np.asarray / reader.decorator.device_put_chunked, the
    chunked host<->device path the feed tier already uses.
"""

from __future__ import annotations

from ..core.registry import register


def _identity_infer(ctx):
    ctx.set_output("Out", ctx.input_shape("X"), ctx.input_dtype("X"))


def _is_traced(x) -> bool:
    import jax.core

    return isinstance(x, jax.core.Tracer)


@register("recompute_barrier", infer_shape=_identity_infer, no_grad=True,
          doc="optimization-barrier identity guarding a recompute "
              "segment's boundary input (memory/recompute.py)")
def lower_recompute_barrier(ctx, ins):
    import jax

    x = ins["X"][0]
    gate = (ins.get("Gate") or [None])[0]
    if gate is not None:
        x, _ = jax.lax.optimization_barrier((x, gate))
        return {"Out": [x]}
    return {"Out": [jax.lax.optimization_barrier(x)]}


@register("memcpy_d2h", infer_shape=_identity_infer, no_grad=True,
          doc="park a stash var in host memory at its liveness edge "
              "(memory/offload.py)")
def lower_memcpy_d2h(ctx, ins):
    import jax
    import numpy as np

    x = ins["X"][0]
    if not _is_traced(x):
        # eager/imperative: a real device->host readback
        return {"Out": [np.asarray(x)]}
    return {"Out": [jax.device_put(x, jax.memory.Space.Host)]}


@register("memcpy_h2d", infer_shape=_identity_infer, no_grad=True,
          doc="fetch an offloaded stash var back to HBM at the "
              "backward's first read (memory/offload.py)")
def lower_memcpy_h2d(ctx, ins):
    import jax

    x = ins["X"][0]
    gate = (ins.get("Gate") or [None])[0]
    if not _is_traced(x):
        from ..reader.decorator import device_put_chunked

        return {"Out": [device_put_chunked(x)]}
    if gate is not None:
        # the fetch must not be hoisted ahead of the backward front: tie
        # it to the earliest available backward value, like the
        # recompute barrier
        x, _ = jax.lax.optimization_barrier((x, gate))
    return {"Out": [jax.device_put(x, jax.memory.Space.Device)]}


__all__ = ["lower_recompute_barrier", "lower_memcpy_d2h",
           "lower_memcpy_h2d"]
