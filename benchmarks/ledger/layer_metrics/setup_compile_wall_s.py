"""Wall seconds jax spent making this process's programs: the length of the
UNION of the time spans of its trace, lowering and backend-compile events
(the program's ``jit.compile_wall_s``, kept by its compile tracker from
``jax.monitoring``'s time-span events), so a jitted function traced inside
another's trace counts once. ``setup_compile_s`` adds the same events'
durations and is an upper bound of this by the nested traces' seconds.

No program may compile inside the window (``no_compile_in_window``), so all
of it is set-up but the few small programs of the loop's closing checks.
Which program took how long is the program's
``observability.compiled_programs()``. None where the program keeps no such
seconds."""
import paddle_tpu.observability as obs


def read(reduced, counts, config, peaks):
    return obs.counter("jit.compile_wall_s").value or None
