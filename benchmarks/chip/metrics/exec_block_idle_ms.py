"""Chip idle per ``execute()`` while the host waits on a firing's outputs:
the idle seconds of each chip named by the program's ``exec.block`` span,
over the traced calls.  On one chip that is the wake-up and launch
latency that blocking on every firing exposes.  A program without
``exec.*`` spans leaves the metric silent."""


def read(r):
    if r.trace is None:
        return None
    if not any(k.startswith("exec.") for k in r.trace.idle_s):
        return None
    return 1e3 * r.trace.idle_s.get("exec.block", 0.0) / len(r.walls)
