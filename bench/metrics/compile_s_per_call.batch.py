"""compile_s_per_call.batch: seconds per call inside the program's
``jax.compile`` spans over the window: every lowering to MLIR and every
backend compile, or load from the persistent compilation cache, that
JAX reported while the program's tracer was installed. Each span names
the jitted function (``fun``) and the program span it ran in (``span``).

A program whose tracer records no compile spans reports nothing; one
that records them reads 0 in a window that compiles nothing. Read only
beside a device trace of the same run: off the chip these are compiles
for XLA's CPU backend."""

SPAN = "jax.compile"


def _records_compiles() -> bool:
    try:
        from repro.obs import trace
    except ImportError:
        return False
    return getattr(trace, "COMPILE_SPAN", None) == SPAN


def read(run):
    if not run.calls or run.device is None or not _records_compiles():
        return None
    spans = [e for e in run.spans if e["name"] == SPAN]
    return sum(e["dur"] for e in spans) / 1e6 / len(run.calls)
