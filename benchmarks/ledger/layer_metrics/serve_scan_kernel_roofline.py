"""Bandwidth-roofline share of the prefill scan, in %: the bytes the
kernel's contract must move (``arch.scan_bytes``: ``xc``, ``delta`` in and
``y`` out once, ``B``, ``C`` once, the state in and out) over the published
bytes/s, over the device time the scan costs the program. The kernel's
operations are those the trace names after the configuration's
``build.serve.scan_scope`` (the Pallas call's own name); each one's positions
are read from the shape in its name (``f32[1,S,rows,128]``), so the bytes are
those of the very calls that were timed. Beside the kernel XLA copies ``xc``
into that layout and ``y`` out of it (``copy f32[S/8,8,rows,128]``,
``reshape f32[1,S,rows*128]``: the trace names an operation by its
instruction and result, not by the scope it was traced under, so they are
found by the shapes the kernel's own name gives); their seconds are the
scan's too. Expected well under 100 %: the kernel is bound by the vector and
transcendental units (some hundred vector operations and sixteen ``exp`` a
position and tile), for which ``peaks.py`` has no published peak.
None where the trace holds no such operation (another configuration, or a
program from before the kernel existed)."""
import re

from byname import load_module

_SHAPE = re.compile(r"\[(\d+),(\d+),(\d+),(\d+)\]$")


def scan_ops(reduced, config):
    """[(sequences, positions, seconds, calls)] of device 0's operations
    under the scan's name, each with the seconds of the copies into and out
    of its layout; empty where there are none."""
    scope = config["build"]["serve"].get("scan_scope")
    if reduced is None or not scope:
        return []
    dev = reduced["devices"][min(reduced["devices"])]
    out = []
    for key, (seconds, calls) in dev["op_s"].items():
        shape = _SHAPE.search(key)
        if key.split(" ")[0] == scope and shape:
            b, s, rows, lanes = (int(g) for g in shape.groups())
            beside = (f"copy f32[{b * s // 8},8,{rows},{lanes}]",
                      f"reshape f32[{b},{s},{rows * lanes}]")
            seconds += sum(dev["op_s"].get(k, (0.0, 0))[0] for k in beside)
            out.append((b, s, seconds, calls))
    return out


def read(reduced, counts, config, peaks):
    ops = scan_ops(reduced, config)
    seconds = sum(s for _b, _p, s, _n in ops)
    if not seconds:
        return None
    arch = load_module("arch", config["arch"])
    need = sum(n * b * arch.scan_bytes(config["sizes"], p)
               for b, p, _s, n in ops)
    return 100.0 * need / peaks["bytes_per_s"] / seconds
