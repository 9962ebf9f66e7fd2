"""The KNN kernel's share of its roofline: the least time for the query
batches the traced window ran (the points and queries read once at peak
HBM bandwidth, or 2·D + 1 operations per query-point pair at peak compute,
whichever is longer) over the device time of the KNN kernel: the Pallas
calls (``custom-call:tpu_custom_call``) of a jitted program named for
``knn``.  The count is the algorithm's, so it holds when the kernel
changes; a kernel that no longer runs under such a program leaves the
metric silent."""


def _is_knn_kernel(key):
    module, op = key.split("/", 1)
    return "knn" in module and op == "custom-call:tpu_custom_call"


def read(r):
    if r.trace is None:
        return None
    kernel_s = r.trace.op_seconds(_is_knn_kernel)
    if kernel_s <= 0:
        return None
    return 100.0 * r.least_exec_s * len(r.walls) / kernel_s
