"""repro.exec — the multi-device dataflow executor (paper §4.6 + §5).

The compiler (:mod:`repro.compiler`) plans a design; this package *runs*
it.  ``execute(design)`` turns a :class:`~repro.compiler.CompiledDesign`
into a synchronous-dataflow program: every task becomes a jax program bound
by the app's ``bind_programs`` hook, every graph channel becomes a bounded
FIFO whose capacity is the §4.6 balanced depth and whose latency is the
inserted pipeline registers, and inter-device channels move real arrays
between (host-emulated) jax devices, double-buffered when depth ≥ 2.

    from repro.compiler import CompileOptions, compile
    from repro.exec import execute

    design = compile(graph, cluster, CompileOptions(balance_kind="LUT"))
    result = execute(design)              # or design.execute()
    result.outputs                        # numerics == single-device ref
    result.report.agreement()             # measured vs Eq. 2 accounting

CI needs no accelerator: ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
provides the device mesh (see ``python -m repro.exec.smoke``), and a bare
single-device interpreter still executes every design — logical placement
keeps driving the traffic accounting.

Profiler spans (``jax.profiler.TraceAnnotation``, on the caller's thread,
recorded only while a ``jax.profiler`` trace is running):

==================  =====================  ================================
span                args                   holds
==================  =====================  ================================
``exec.execute``    ``graph``, ``call``    one ``execute()``; ``call`` counts
                                           the calls of this process
``exec.state``                             building the ``ExecutionState``:
                                           channels, memory streams, priming
``exec.sweep``      ``sweep``              one ``ExecutionState.advance``
``exec.fire``       ``task``, ``device``   one firing: pop, placement,
                                           dispatch, push
``exec.dispatch``   ``queued``             the task's program call (JAX's
                                           own spans nest inside); ``queued``
                                           is 1 where the device was still
                                           computing its previous firing
``exec.block``                             the host waiting once per run on
                                           the sinks' outputs
``exec.xfer``       ``channel``,           a token's move to its consumer's
                    ``nbytes``             device (inter-device channels)
``exec.finalize``                          the binding's output assembly
``exec.report``                            the ``ExecutionReport`` and its
                                           Eq. 2 accounting
==================  =====================  ================================
"""
from .channels import ChannelStats, FifoChannel, token_bytes
from .executor import (DeadlockError, ExecutionResult, ExecutionState,
                       StarvationError, execute)
from .programs import (BINDER_REGISTRY, ProgramBinding, RoutedOutput,
                       SOURCE_KEY, bind_programs, register_binder)
from .report import ChannelTrace, ExecutionReport, MemChannelTrace
from .snapshot import (latest_snapshot_step, load_snapshot, restore_state,
                       resume_execution, save_snapshot, snapshot_steps)

__all__ = [
    "BINDER_REGISTRY", "ChannelStats", "ChannelTrace", "DeadlockError",
    "ExecutionReport", "ExecutionResult", "ExecutionState", "FifoChannel",
    "MemChannelTrace", "ProgramBinding", "RoutedOutput", "SOURCE_KEY",
    "StarvationError", "bind_programs", "execute", "latest_snapshot_step",
    "load_snapshot", "register_binder", "restore_state", "resume_execution",
    "save_snapshot", "snapshot_steps", "token_bytes",
]
