"""The KNN merge tree's cost per ``execute()``: the self time of every
operation of the jitted ``knn_merge`` program (the sorters and the
aggregator), plus the chip idle named by that program's dispatch
(``PjitFunction(knn_merge)``), summed over the chips, over the traced
calls.  A trace with neither leaves the metric silent."""


def _in_merge(key):
    return "knn_merge" in key.split("/", 1)[0]


def read(r):
    if r.trace is None:
        return None
    idle = [s for k, s in r.trace.idle_s.items() if "knn_merge" in k]
    if not idle and not any(_in_merge(k) for k in r.trace.op_s):
        return None
    return 1e3 * (r.trace.op_seconds(_in_merge) + sum(idle)) / len(r.walls)
