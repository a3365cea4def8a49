"""Array-native repair engine: compiled plans, planners + batched steppers.

The compile/plan/execute split mirrors a small compiler stack:

* `repro.core.engine.arrays` — `compile_plan` lowers the object plan IR
  to `PlanArrays` (padded integer arrays + uint64 term bitmasks),
  `plan_arrays_from_schedule` builds them straight from tuple schedules,
  `splice_path` mutates a compiled plan in place (the BMF replan hook),
  `decompile` round-trips exactly, `validate_plan_arrays` is the array
  fast path behind `repro.core.plan.validate_plan`;
* `repro.core.engine.planner_arrays` — the array-native planner layer:
  batched BMF path search / round optimization over `(B, N, N)`
  bandwidth stacks, and the tuple schedulers the object planners in
  `repro.core.{msrepair,bmf,ppt}` facade over;
* `repro.core.engine.vectorized` — masked-array event steppers that
  advance a whole `(B, ...)` batch of scenarios at once, plus
  `run_scheme_vectorized`, the batched twin of `simulator.run_scheme`
  that `repro.sim.sweep.run_sweep(executor="vectorized")` dispatches to;
* `repro.core.engine.jax_stepper` — the same steppers as jit-compiled
  JAX device programs (`lax.while_loop`/`scan` over static padded
  shapes) behind `run_sweep(executor="jax")`; planning and replanning
  stay on the host, execution runs on the accelerator;
* `repro.core.engine.dataplane` — the byte data plane: batches of
  compiled plans executed over *real bytes* (a compact `(rows, nbytes)`
  store of the (case, slot) buffers, kept on the device on the kernel
  path; batched GF(256) premultiply + segment-XOR through
  `repro.kernels.ops`), byte-identical to the serial oracle in
  `repro.core.executor`.

The object-based engine in `repro.core.simulator` stays the reference
implementation; parity tests pin the vectorized path to it.

`vectorized` is loaded lazily (PEP 562): it imports the simulator, whose
planner facades import `planner_arrays` from this package — eager loading
would cycle.
"""
from repro.core.engine.arrays import (PlanArrays, UnsupportedPlanError,
                                      compile_plan, decompile,
                                      plan_arrays_from_schedule,
                                      relabel_plan_nodes, splice_path,
                                      validate_plan_arrays)

__all__ = [
    "PlanArrays",
    "UnsupportedPlanError",
    "compile_plan",
    "decompile",
    "plan_arrays_from_schedule",
    "splice_path",
    "validate_plan_arrays",
    "execute_pipeline_batch",
    "execute_round_batch",
    "run_scheme_vectorized",
    "BatchExecutionResult",
    "execute_plans_batch",
    "identity_block_map",
    "relabel_plan_nodes",
]

_VECTORIZED = ("execute_pipeline_batch", "execute_round_batch",
               "run_scheme_vectorized")
# the byte data plane imports jax via repro.kernels — loaded lazily
_DATAPLANE = ("BatchExecutionResult", "execute_plans_batch",
              "identity_block_map")


def __getattr__(name):
    if name in _VECTORIZED:
        from repro.core.engine import vectorized

        return getattr(vectorized, name)
    if name in _DATAPLANE:
        from repro.core.engine import dataplane

        return getattr(dataplane, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
