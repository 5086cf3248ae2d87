"""compiles_per_call.batch: programs compiled, or loaded from the
persistent compilation cache, inside the window, per call; counted from
JAX's ``/jax/core/compile/backend_compile_duration`` monitoring events."""


def read(run):
    if not run.calls:
        return None
    return sum(c.compiles for c in run.calls) / len(run.calls)
