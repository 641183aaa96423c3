"""Eval layer (engine/backend_pallas.py, engine/backend_jax.py): host
milliseconds per ``evaluate_grid`` call in the program's ``eval`` span
outside its ``eval.wait`` child, the part of the eval call in which the
chip is not running the cost kernel: stacking the plan rows, the views'
dispatch, the kernel's launch, the fetch of its outputs and their scatter
into the result."""

SPANS = ("eval",)
WAIT = ("eval.wait",)


def read(run):
    if run.units == 0 or run.span_s(WAIT) <= 0.0:
        return None
    return 1e3 * (run.span_s(SPANS) - run.span_s(WAIT)) / run.units
