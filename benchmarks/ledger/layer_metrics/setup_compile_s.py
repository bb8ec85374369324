"""Seconds jax spent making this process's programs: tracing to jaxprs
(``jit.trace_s``), lowering to MLIR (``jit.lower_s``) and the backend's
compile, or the retrieval on a persistent-cache hit
(``jit.backend_compile_s``); the program's compile tracker keeps them from
``jax.monitoring``'s duration events.

No program may compile inside the window (``no_compile_in_window``), so
all of it is set-up but the few small programs of the loop's closing
checks. An UPPER bound on the wall time it took: a jitted function traced
inside another's trace fires its own trace event, so nested tracing is
counted twice. Tracing and lowering are paid on every run; the persistent
cache only shortens the third part. None where the program keeps no such
seconds."""
import paddle_tpu.observability as obs


def read(reduced, counts, config, peaks):
    total = sum(obs.counter(name).value for name in
                ("jit.trace_s", "jit.lower_s", "jit.backend_compile_s"))
    return total or None
