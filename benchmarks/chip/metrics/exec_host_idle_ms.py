"""Chip idle per ``execute()`` while the host runs the executor's own
Python: the idle seconds of each chip named by an ``exec.*`` span of the
program (sweeps, readiness checks, pops and pushes, state set-up, the
report) other than ``exec.block``, over the traced calls.  Idle under a
JAX span nested inside, such as a dispatch's ``PjitFunction``, is not
counted.  A program without ``exec.*`` spans leaves the metric silent."""


def read(r):
    if r.trace is None:
        return None
    idle = {k: s for k, s in r.trace.idle_s.items() if k.startswith("exec.")}
    if not idle:
        return None
    host_s = sum(s for k, s in idle.items() if k != "exec.block")
    return 1e3 * host_s / len(r.walls)
