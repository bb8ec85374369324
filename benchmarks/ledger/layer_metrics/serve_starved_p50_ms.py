"""Median over the cycles that dispatched a decode block of the seconds in
which, by the session's own books, the chip had nothing of the session's to
run, in ms: from the instant the fetch's wait returned (every program
enqueued is done) to the return of the next device call (an admit's or the
decode dispatch's), through the copy of the tokens, their delivery, the
caller's gap between two ``step()``s and the next step's work before its
first enqueue (the program's ``serving.cycle_starved_s`` histogram; a cycle
runs from a ``step()``'s return to the next one's return).

A LOWER bound on the idle a cycle's host work causes, where
``serve_step_host_p50_ms`` is an upper one: the launch latency after the
call returns is not seen, and what the host does behind an enqueued admit
starves nobody. A cycle's seconds with no request to serve are not in it,
and a cycle that dispatched nothing is not observed.

Read from the live registry of this process, so it is cumulative: the
window's cycles are over nine tenths of the observations, and a median does
not see the warm-up's (a program's first call compiles inside its dispatch,
starving). None where the program has no such histogram."""
import paddle_tpu.observability as obs


def read(reduced, counts, config, peaks):
    p50 = obs.histogram("serving.cycle_starved_s").percentile(0.5)
    return None if p50 is None else 1e3 * p50
