"""Host seconds of ``repro.compiler.compile`` in set-up: partition,
floorplan, FIFO pipelining and schedule of the design."""


def read(r):
    return r.compile_design_s
