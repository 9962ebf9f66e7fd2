"""The dataflow executor — runs a :class:`CompiledDesign` end to end.

Execution model (synchronous dataflow, one sweep ≈ one pipeline clock):

* Every task fires ``iterations`` times.  A task may fire in a sweep when
  every in-channel (back edges included — those carry the iteration
  dependency and are seeded by ``ProgramBinding.prime``) has a *visible*
  token and every out-channel has a free slot.
* Tasks are processed in **reverse topological order** within a sweep, so a
  consumer's pop frees its FIFO slot before the producer's push is
  considered — the software equivalent of simultaneous push+pop on a full
  hardware FIFO.  Tokens pushed in sweep *t* become visible at
  ``t + latency``, so data still advances at most one task per sweep.
* Channel capacity comes from the §4.6 balanced ``depth`` on the graph
  channel; channel latency from the pipeline report's ``added_latency``.
  With balanced depths every task fires every sweep once the pipeline fills
  (full throughput); clamp a depth below ``added + slack + 1`` and the
  reconvergent join starves — which the detector below reports instead of
  silently throttling.
* The host never waits on a firing: JAX queues each program call on its
  device, and the executor pops, places and pushes arrays not yet
  computed, so each device keeps a queue of firings and the devices of a
  chain run their stages at the same time.  A run waits once, on the
  sinks' outputs, before it reads its wall time (:meth:`ExecutionState.block`).

Network fabric (``repro.net``): when the design (or the caller) supplies a
:class:`~repro.net.fabric.Fabric`, inter-device pushes are packetized into
flits and routed over the physical links by a
:class:`~repro.net.transport.FabricTransport` stepped once per sweep —
channels sharing a link contend for its bandwidth, credits backpressure the
hops, and a token only becomes visible after its own message delivers.
``fabric=None`` forces the ideal point-to-point ``jax.device_put`` path
(the pre-fabric behaviour, bit-identical numerics).  After the last firing
the network is drained so the per-link byte accounting is complete.

HBM banks (``repro.mem``): when the binding declares ``mem_reads`` streams
and the design (or the caller) supplies a
:class:`~repro.mem.banks.MemConfig`, each stream becomes an
:class:`~repro.mem.channels.AsyncMemChannel` against a
:class:`~repro.mem.banks.MemorySystem` stepped once per sweep — the
``async_mmap`` split request/response contract: requests are pumped ahead
of consumption up to the credit bound, banks serve bursts fairly across
the channels mapped to them, and a task additionally waits on its head
memory response before firing (tallied in ``mem_waits``).  ``mem=None``
forces the ideal memory path: every response ready the sweep it is issued,
bit-identical numerics (payloads come from the binding either way).

Multi-tenant sharing (``repro.tenants``): the per-design machinery lives
in :class:`ExecutionState` — a resumable state machine that fires one
sweep at a time (:meth:`ExecutionState.advance`) and receives network and
memory completions from outside (:meth:`ExecutionState.net_deliver` /
:meth:`ExecutionState.mem_deliver`).  ``execute()`` wraps one state in the
classic solo loop and *owns* its transport and memory system; a tenant
server instead passes every state a flow-scoped **view** of one shared
transport/memory system (``transport=`` / ``memsys=``) plus a
``device_map`` placing the design's logical devices onto the shared
fabric's physical ids, then steps the shared substrate itself and demuxes
completions back to the states.  Sharing never touches the numerics — a
channel's payload rides outside the flit clock — so a tenant's outputs
are bit-identical to its solo run by construction, which the tenant layer
asserts rather than assumes.

Detection:

* **Hard deadlock** — a sweep fires nothing, and no queued token will ever
  become visible (tokens still transiting the fabric count as in flight).
  Raises :class:`DeadlockError` listing each unfinished task with the
  channel that blocks it.
* **FIFO starvation** — a join cannot fire because one in-channel is empty
  while a sibling in-channel sits *at capacity*: the signature of an
  unbalanced cut-set (§4.6).  Transient during pipeline fill never matches
  (balanced depths leave headroom); persistent imbalance accumulates events
  until :data:`STARVE_LIMIT` trips :class:`StarvationError` with the channel
  that needs more depth.  When the starved input still has tokens in the
  network, the wait is *congestion*, not imbalance — it is tallied in
  ``congestion_waits`` instead of tripping the detector.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Any, Dict, List, Mapping, Optional, Sequence

import jax

from ..compiler.artifact import CompiledDesign
from ..obs.trace import coerce_tracer
from .channels import FifoChannel, _put
from .programs import (SOURCE_KEY, ProgramBinding, RoutedOutput,
                       bind_programs)
from .report import ExecutionReport, build_report


class DeadlockError(RuntimeError):
    """No task can ever fire again, yet the run is incomplete."""


class StarvationError(DeadlockError):
    """A join repeatedly starves behind an unbalanced FIFO (§4.6)."""


#: Request ids of ``execute()`` calls in this process (the ``call`` argument
#: of the ``exec.execute`` span).
_CALLS = itertools.count()

#: Starvation events at one join that raise :class:`StarvationError`.
STARVE_LIMIT = 3

#: Sentinel for ``execute(fabric=...)``: use the design's fabric (pass
#: ``fabric=None`` explicitly to force the ideal transfer path).
FROM_DESIGN = object()


@dataclasses.dataclass
class ExecutionResult:
    """What came out of the pipe, plus the measured execution report."""

    outputs: Any                          # binding.finalize(...) result
    sink_outputs: Dict[str, List[Any]]    # raw per-firing sink values
    report: ExecutionReport


def _block(token: Any) -> None:
    for leaf in jax.tree_util.tree_leaves(token):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


def _ready(token: Any) -> bool:
    """Every array of ``token`` has been computed (asks, never waits).  An
    array a later program took by donation is deleted, and was computed:
    ``is_ready()`` must not be asked of it."""
    return all(leaf.is_deleted() or leaf.is_ready()
               for leaf in jax.tree_util.tree_leaves(token)
               if isinstance(leaf, jax.Array))


def device_name(device) -> str:
    """Short stable name of a jax device, e.g. ``tpu:2``."""
    return f"{device.platform}:{device.id}"


def _device_names(token: Any) -> set:
    names = set()
    for leaf in jax.tree_util.tree_leaves(token):
        if isinstance(leaf, jax.Array):
            names.update(device_name(d) for d in leaf.devices())
    return names


def _estimate_flit_hops(channels: Sequence[FifoChannel], transport) -> int:
    """Modeled flit-hops one full iteration pushes into the network (the
    sweep-bound heuristic; actual token sizes may exceed the model, so the
    caller pads generously)."""
    total = 0
    for fc in channels:
        if fc.transport is None:
            continue
        gch = fc.graph_channel
        nbytes = max(gch.bytes_per_step or 0.0, gch.width_bits / 8.0, 1.0)
        total += (transport.config.flits_for(int(nbytes))
                  * len(transport.fabric.route(fc.net_src_dev,
                                               fc.net_dst_dev)))
    return total


class ExecutionState:
    """One design's live execution — fire-a-sweep-at-a-time state machine.

    Owns everything per-design (FIFO channels, memory streams, firing
    counts, starvation/congestion tallies) and nothing shared: the network
    transport and memory system are either created here (solo mode — the
    classic ``execute()`` path, signalled by ``transport``/``memsys`` left
    at None) or handed in by a multi-tenant server as flow-scoped views
    over one shared substrate.  In shared mode the server steps the
    substrate and routes completions back through :meth:`net_deliver` /
    :meth:`mem_deliver`; this state never steps or drains what it does not
    own (``owns_transport`` / ``owns_memsys``).

    ``device_map[logical] -> fabric id`` places the design's partition
    onto the (possibly larger, shared) physical fabric; it defaults to the
    identity, and it also selects the backing jax device so two tenants
    mapped apart land on distinct devices.  Logical ids keep driving the
    Eq. 2 accounting either way — the map only changes what the *network*
    sees.
    """

    def __init__(self, design: CompiledDesign,
                 binding: Optional[ProgramBinding] = None, *,
                 inputs: Optional[Mapping[str, Any]] = None,
                 devices: Optional[Sequence[Any]] = None,
                 max_sweeps: Optional[int] = None,
                 fabric: Any = FROM_DESIGN,
                 net_config=None,
                 mem: Any = FROM_DESIGN,
                 transport: Any = None,
                 memsys: Any = None,
                 device_map: Optional[Sequence[int]] = None,
                 faults: Any = None,
                 tracer: Any = None,
                 trace_flow: int = 0):
        if design.partition is None:
            raise ValueError("execute() needs a partitioned design "
                             "(run the partition pass)")
        if binding is None:
            binding = bind_programs(design.graph, inputs)
        self.design = design
        self.binding = binding
        # Observability (repro.obs): the default NULL_TRACER keeps every
        # emit a guarded no-op — the untraced path allocates nothing.
        self.tracer = coerce_tracer(tracer)
        self.trace_flow = int(trace_flow)
        graph, assign = design.graph, design.partition.assignment
        self.graph, self.assign = graph, assign
        rep = design.pipeline_report
        ndev = design.partition.num_devices()

        if device_map is None:
            self.device_map = list(range(max(1, ndev)))
        else:
            self.device_map = [int(d) for d in device_map]
            if len(self.device_map) < ndev:
                raise ValueError(
                    f"device_map covers {len(self.device_map)} logical "
                    f"devices but the partition uses {ndev}")
        # Logical device d runs on pool[device_map[d]].  A map that names a
        # device the pool lacks is an error, except on a host with a single
        # device (the CPU test runs), which holds every logical device;
        # logical placement keeps driving the traffic accounting either
        # way, and the report records where each logical device ran.
        pool = list(devices) if devices is not None else list(jax.devices())
        used = self.device_map[:max(1, ndev)]
        if len(pool) == 1:
            jax_dev = [pool[0]] * len(used)
        else:
            missing = sorted({d for d in used if d >= len(pool)})
            if missing:
                raise ValueError(
                    f"device_map places logical devices on {missing}, but "
                    f"only {len(pool)} devices are present")
            jax_dev = [pool[d] for d in used]
        self.jax_dev = jax_dev

        self.owns_transport = transport is None
        if transport is None:
            if fabric is FROM_DESIGN:
                fabric = design.fabric
            if fabric is not None:
                from ..net.transport import FabricTransport  # optional layer
                if fabric.num_devices != design.cluster.num_devices:
                    raise ValueError(
                        f"fabric spans {fabric.num_devices} devices but the "
                        f"cluster has {design.cluster.num_devices}")
                transport = FabricTransport(fabric, net_config,
                                            faults=faults,
                                            tracer=self.tracer)
        else:
            nfab = transport.fabric.num_devices
            bad = [d for d in self.device_map[:max(1, ndev)] if d >= nfab]
            if bad:
                raise ValueError(f"device_map targets fabric devices {bad} "
                                 f"outside the shared fabric's 0..{nfab - 1}")
        self.transport = transport

        self.channels: List[FifoChannel] = []
        for i, ch in enumerate(graph.channels):
            latency = 1 + (rep.added_latency.get(i, 0)
                           if rep is not None else 0)
            self.channels.append(FifoChannel(
                i, ch, assign[ch.src], assign[ch.dst], latency=latency,
                dst_device=jax_dev[assign[ch.dst]],
                transport=transport,
                net_src_dev=self.device_map[assign[ch.src]],
                net_dst_dev=self.device_map[assign[ch.dst]],
                tracer=self.tracer, trace_flow=self.trace_flow))
        for i, token in binding.prime.items():
            self.channels[i].prime(token)

        self.in_chs: Dict[str, List[FifoChannel]] = {t: [] for t in
                                                     graph.tasks}
        self.out_chs: Dict[str, List[FifoChannel]] = {t: [] for t in
                                                      graph.tasks}
        for fc in self.channels:
            if any(prev.src == fc.src for prev in self.in_chs[fc.dst]):
                # token_in is keyed by predecessor name — a second channel
                # from the same producer would silently overwrite the
                # first's token.
                raise ValueError(
                    f"parallel channels {fc.src}->{fc.dst}: the executor "
                    "delivers one token per predecessor; merge the payloads "
                    "into one channel (tokens are arbitrary pytrees)")
            self.in_chs[fc.dst].append(fc)
            self.out_chs[fc.src].append(fc)
        # Sinks: no forward (non-back) out-channel — their firing values
        # are the pipeline's results (back edges recirculate, they don't
        # leave the pipe).
        self.sinks = [t for t in graph.tasks
                      if not any(not fc.is_back for fc in self.out_chs[t])]

        self.iterations = T = binding.iterations

        # Async memory channels (repro.mem) — one per declared mem_reads
        # stream, placed on the task's logical device and its compiled (or
        # default) bank.  memsys None + mem_config None is the ideal path:
        # same channels, immediate responses.
        mem_config = design.mem_config if mem is FROM_DESIGN else mem
        self.owns_memsys = memsys is None
        self.mem_channels: List[Any] = []
        self.mem_chs: Dict[str, List[Any]] = {t: [] for t in graph.tasks}
        if binding.mem_reads:
            from ..mem.channels import AsyncMemChannel   # optional layer
            bank_map = dict(design.bank_map or {})
            if memsys is None and mem_config is not None:
                from ..mem.banks import MemorySystem
                memsys = MemorySystem(ndev, mem_config, tracer=self.tracer)
            if memsys is not None and not bank_map:
                from ..mem.contention import default_bank_map
                bank_map = default_bank_map(graph, assign, memsys.config)
            for task in sorted(binding.mem_reads):
                for stream in sorted(binding.mem_reads[task]):
                    mc = AsyncMemChannel(
                        len(self.mem_channels), task, stream,
                        binding.mem_reads[task][stream], T,
                        device=assign[task], bank=bank_map.get(task, 0),
                        memsys=memsys, tracer=self.tracer,
                        trace_flow=self.trace_flow)
                    self.mem_channels.append(mc)
                    self.mem_chs[task].append(mc)
        self.memsys = memsys

        self.order = list(reversed(graph.topo_order()))
        max_lat = max((fc.latency for fc in self.channels), default=1)
        if max_sweeps is None:
            # Pipeline depth is bounded by tasks × max latency; each of the
            # T firings advances at least one task per sweep barring
            # throttling.
            max_sweeps = 64 + 4 * (T + len(graph.tasks)) * (1 + max_lat)
            if transport is not None:
                # The network serializes flits over shared links; transport
                # progress is guaranteed (>= 1 flit-hop per sweep while
                # active), so pad by a generous multiple of the modeled
                # per-iteration flit-hops (actual tokens may exceed the
                # model).
                est = _estimate_flit_hops(self.channels, transport)
                max_sweeps += 256 + 64 * (T + 1) * max(1, est)
                if getattr(transport, "faults", None) is not None:
                    # Losses inflate transmissions and backoff spaces the
                    # retries — budget for it so a lossy-but-progressing
                    # run is not misdiagnosed as throughput collapse.
                    max_sweeps += transport.faults.sweep_allowance(est, T)
            if memsys is not None:
                # Banks serve >= 1 burst per sweep while queued, so the
                # total burst demand bounds the extra memory-induced sweeps.
                max_sweeps += 256 + 4 * sum(mc.total_bursts()
                                            for mc in self.mem_channels)
        self.max_sweeps = max_sweeps

        self.fired: Dict[str, int] = {t: 0 for t in graph.tasks}
        self.starve_events: Dict[str, int] = {}
        self.starve_detail: List[Dict[str, Any]] = []
        self.congestion_waits: Dict[str, int] = {}
        self.mem_waits: Dict[str, int] = {}
        self.sink_outputs: Dict[str, List[Any]] = {t: [] for t in self.sinks}
        self.dev_fired: Dict[int, int] = {}
        # Devices each task's output arrays were found on (measured).
        self.task_devices: Dict[str, set] = {t: set() for t in graph.tasks}
        # Firings dispatched while the previous firing's output on the same
        # jax device was still being computed: the device had work queued.
        self.queued_firings = 0
        self._last_out: Dict[Any, Any] = {}
        self.sweeps_done = 0

    # -- progress queries ----------------------------------------------------
    @property
    def done(self) -> bool:
        return all(n >= self.iterations for n in self.fired.values())

    @property
    def total_firings(self) -> int:
        return self.iterations * len(self.graph.tasks)

    @property
    def firings(self) -> int:
        return sum(self.fired.values())

    def has_pending(self, sweep: int) -> bool:
        """Progress is still coming without any task firing: a token is
        ripening in a FIFO, a response in the reorder window, or traffic
        is in the network / bank pipe (flow-scoped in shared mode)."""
        if any(vis > sweep for fc in self.channels
               for vis in fc.pending_visibility()):
            return True
        if any(vis > sweep for mc in self.mem_channels
               for vis in mc.pending_visibility()):
            return True
        if self.transport is not None and self.transport.active:
            return True
        return self.memsys is not None and self.memsys.active

    def blockers(self, task: str, sweep: int) -> List[str]:
        why = []
        for fc in self.in_chs[task]:
            if not fc.head_visible(sweep):
                why.append(f"input {fc.src}->{task} empty "
                           f"(occupancy {fc.occupancy}/{fc.capacity})")
        for fc in self.out_chs[task]:
            if fc.full:
                why.append(f"output {task}->{fc.dst} full "
                           f"(depth {fc.capacity})")
        for mc in self.mem_chs[task]:
            if mc.stats.consumed < mc.count and not mc.response_ready(sweep):
                why.append(f"memory {task}.{mc.stream} response pending "
                           f"({mc.stats.consumed}/{mc.count} consumed, "
                           f"{mc.outstanding} outstanding)")
        return why

    def deadlock(self, sweep: int) -> DeadlockError:
        lines = [f"  {t} ({self.fired[t]}/{self.iterations} firings): " +
                 ("; ".join(self.blockers(t, sweep)) or "unknown")
                 for t in self.graph.tasks
                 if self.fired[t] < self.iterations]
        return DeadlockError(
            "dataflow deadlock at sweep %d — no task can fire and "
            "no token is in flight:\n%s" % (sweep, "\n".join(lines)))

    # -- completion demux (shared mode: called by the tenant server) ---------
    def net_deliver(self, channel_index: int, mid: int, sweep: int) -> None:
        self.channels[channel_index].on_delivered(mid, sweep)

    def mem_deliver(self, chan_index: int, rid: int, sweep: int) -> None:
        self.mem_channels[chan_index].on_complete(rid, sweep)

    # -- one sweep of task firing --------------------------------------------
    def advance(self, sweep: int) -> int:
        """Fire every ready task once (reverse topo order); returns the
        firing count.  Does NOT step the transport / memory system — the
        owner of those does (``run()`` solo, the tenant server shared)."""
        with jax.profiler.TraceAnnotation("exec.sweep", sweep=sweep):
            return self._advance(sweep)

    def _advance(self, sweep: int) -> int:
        binding, T = self.binding, self.iterations
        tr, flow = self.tracer, self.trace_flow
        fired_this_sweep = 0
        for mc in self.mem_channels:
            # Issue reads ahead of consumption, up to the credit bound —
            # the multiple-outstanding-transactions loop of async_mmap.
            mc.pump(sweep)
        for v in self.order:
            if self.fired[v] >= T:
                continue
            in_chs, out_chs = self.in_chs[v], self.out_chs[v]
            ready = all(fc.head_visible(sweep) for fc in in_chs)
            space = all(not fc.full for fc in out_chs)
            if not (ready and space):
                if in_chs:
                    empty = [fc for fc in in_chs
                             if not fc.head_visible(sweep)]
                    at_cap = [fc for fc in in_chs if fc.full]
                    if empty and at_cap:
                        if any(fc.in_flight > 0 for fc in empty):
                            # Data is coming — the wait is network
                            # congestion, not a §4.6 depth imbalance.
                            self.congestion_waits[v] = \
                                self.congestion_waits.get(v, 0) + 1
                            if tr.enabled:
                                # reason "net" mirrors this tally exactly
                                # (the trace-vs-report consistency assert).
                                tr.task_wait(sweep, v, self.assign[v],
                                             "net", flow)
                            continue
                        # A bounded FIFO may transiently saturate while the
                        # pipeline fills (bounded by the paths' hop-count
                        # difference) — only persistence past STARVE_LIMIT
                        # is the unbalanced-cut-set signature.
                        self.starve_events[v] = \
                            self.starve_events.get(v, 0) + 1
                        if tr.enabled:
                            tr.task_wait(sweep, v, self.assign[v],
                                         "starve", flow)
                        self.starve_detail.append({
                            "sweep": sweep, "task": v,
                            "starved_input": f"{empty[0].src}->{v}",
                            "full_input": f"{at_cap[0].src}->{v}",
                            "full_depth": at_cap[0].capacity})
                        if self.starve_events[v] >= STARVE_LIMIT:
                            d = self.starve_detail[-1]
                            raise StarvationError(
                                f"join {v!r} starved "
                                f"{self.starve_events[v]}x on "
                                f"{d['starved_input']} while sibling FIFO "
                                f"{d['full_input']} sat full at depth "
                                f"{d['full_depth']}: unbalanced cut-set — "
                                f"§4.6 balancing would deepen "
                                f"{d['full_input']} (run the "
                                f"pipeline_interconnect pass or raise "
                                f"min_depth)")
                        continue
                    if tr.enabled:
                        # Trace-only reasons (never tallied by the legacy
                        # counters): input still transiting the fabric
                        # without a saturated sibling, a plain dataflow
                        # dependency, or downstream backpressure.
                        if empty:
                            reason = ("transit" if any(
                                fc.in_flight > 0 for fc in empty)
                                else "upstream")
                        else:
                            reason = "backpressure"
                        tr.task_wait(sweep, v, self.assign[v], reason, flow)
                    continue
                if tr.enabled and not space:
                    # A source task (no in-channels) blocked on a full
                    # output FIFO.
                    tr.task_wait(sweep, v, self.assign[v], "backpressure",
                                 flow)
                continue
            if self.mem_chs[v] and not all(mc.response_ready(sweep)
                                           for mc in self.mem_chs[v]):
                # The graph is ready but a memory response is still in the
                # bank pipe — read_data.empty() on the async_mmap side.
                self.mem_waits[v] = self.mem_waits.get(v, 0) + 1
                if tr.enabled:
                    # reason "mem" mirrors the mem_waits tally exactly.
                    tr.task_wait(sweep, v, self.assign[v], "mem", flow)
                continue
            dev = self.assign[v]
            jdev = self.jax_dev[dev]
            with jax.profiler.TraceAnnotation("exec.fire", task=v, device=dev):
                token_in: Dict[str, Any] = {fc.src: fc.pop(sweep)
                                            for fc in in_chs}
                # Stream items and memory responses are placed on the firing
                # task's device, so the program runs there.
                if not in_chs and v in binding.source_inputs:
                    token_in[SOURCE_KEY] = _put(
                        binding.source_inputs[v][self.fired[v]], jdev)
                for mc in self.mem_chs[v]:
                    token_in[mc.stream] = _put(mc.consume(sweep), jdev)
                # No wait on ``out``: JAX queues the program on its device,
                # and the pushes below move arrays not yet computed.
                prev = self._last_out.get(jdev)
                queued = int(prev is not None and not _ready(prev))
                self.queued_firings += queued
                with jax.profiler.TraceAnnotation("exec.dispatch",
                                                  queued=queued):
                    out = binding.programs[v](token_in)
                self._last_out[jdev] = out
                self.task_devices[v].update(_device_names(out))
                self.dev_fired[dev] = self.dev_fired.get(dev, 0) + 1
                if tr.enabled:
                    tr.task_fire(sweep, v, dev, flow)
                if isinstance(out, RoutedOutput):
                    for fc in out_chs:
                        fc.push(out[fc.dst], sweep)
                else:
                    for fc in out_chs:
                        fc.push(out, sweep)
                if v in self.sinks:
                    self.sink_outputs[v].append(out)
                self.fired[v] += 1
            fired_this_sweep += 1
        self.sweeps_done = max(self.sweeps_done, sweep + 1)
        return fired_this_sweep

    # -- wrap-up -------------------------------------------------------------
    def block(self) -> None:
        """Wait until the sinks' outputs are computed: the one place a run
        waits on the device (``run()`` solo, the tenant server shared)."""
        with jax.profiler.TraceAnnotation("exec.block"):
            _block(self.sink_outputs)

    def build_result(self, sweeps: int, wall_time_s: float
                     ) -> ExecutionResult:
        """Fold the state into the measured report + finalized outputs."""
        with jax.profiler.TraceAnnotation("exec.report"):
            report = build_report(
                design=self.design, channels=self.channels,
                iterations=self.iterations, sweeps=sweeps,
                wall_time_s=wall_time_s, device_fired=self.dev_fired,
                queued_firings=self.queued_firings,
                starvation_events=self.starve_events,
                starvation_detail=self.starve_detail, transport=self.transport,
                congestion_waits=self.congestion_waits, memsys=self.memsys,
                mem_channels=self.mem_channels, mem_waits=self.mem_waits,
                tracer=self.tracer,
                placement={d: device_name(jd)
                           for d, jd in enumerate(self.jax_dev)},
                task_devices={t: sorted(n)
                              for t, n in self.task_devices.items()})
        outputs = self.sink_outputs
        if self.binding.finalize is not None:
            with jax.profiler.TraceAnnotation("exec.finalize"):
                outputs = self.binding.finalize(self.sink_outputs)
        return ExecutionResult(outputs=outputs,
                               sink_outputs=self.sink_outputs,
                               report=report)

    # -- the classic solo loop -----------------------------------------------
    def run(self, *, injector: Any = None, start_sweep: int = 0,
            checkpoint_dir: Optional[str] = None,
            checkpoint_every: Optional[int] = None) -> ExecutionResult:
        """Drive this state to completion, stepping the owned substrate.

        ``injector`` (a :class:`~repro.runtime.fault.FailureInjector`) is
        probed once per sweep — the chaos harness's kill switch.
        ``checkpoint_dir`` + ``checkpoint_every`` snapshot the full
        execution state every N sweeps (atomic ``step_<sweep>`` dirs, the
        repro.ckpt idiom) so :func:`~repro.exec.snapshot.resume_execution`
        can continue a killed run from the last barrier instead of
        re-running from scratch.  ``start_sweep`` is that resume entry
        point: the sweep counter continues where the snapshot stopped (the
        budget shifts with it, so a restored run keeps its full headroom).
        """
        transport, memsys = self.transport, self.memsys
        if checkpoint_every is not None and checkpoint_dir is None:
            raise ValueError("checkpoint_every needs a checkpoint_dir")
        t_start = time.perf_counter()
        sweep, done = start_sweep, False
        budget = self.max_sweeps + start_sweep
        while sweep < budget:
            if injector is not None:
                injector.check(sweep)
            fired_this_sweep = self.advance(sweep)
            if transport is not None and self.owns_transport:
                for mid, ch_index in transport.step(sweep):
                    self.net_deliver(ch_index, mid, sweep)
            if memsys is not None and self.owns_memsys:
                for rid, ch_index in memsys.step(sweep):
                    self.mem_deliver(ch_index, rid, sweep)
            if (checkpoint_every is not None
                    and (sweep + 1 - start_sweep) % checkpoint_every == 0):
                from .snapshot import save_snapshot   # avoid import cycle
                save_snapshot(self, sweep, checkpoint_dir)
                if self.tracer.enabled:
                    self.tracer.barrier(sweep, f"step_{sweep}",
                                        self.trace_flow)
            done = self.done
            if done:
                break
            if fired_this_sweep == 0 and not self.has_pending(sweep):
                # Tokens still ripening — or transiting the fabric — are
                # progress; a silent sweep without any is a cycle of
                # blocked tasks — diagnose it.
                raise self.deadlock(sweep)
            sweep += 1
        if not done:
            raise DeadlockError(
                f"executor exceeded max_sweeps={self.max_sweeps} "
                f"(fired {self.firings} of {self.total_firings} "
                f"firings) — throughput collapse; check FIFO depths"
                + (" and fabric link budgets" if transport is not None
                   else ""))

        if transport is not None and self.owns_transport and transport.active:
            # Run the network dry (e.g. final back-edge tokens nobody pops)
            # so the per-link byte conservation identities hold exactly.
            for mid, ch_index in transport.drain(sweep + 1):
                self.net_deliver(ch_index, mid, sweep)
        if memsys is not None and self.owns_memsys and memsys.active:
            # Every firing consumed its response, so the banks are normally
            # dry here — drain defensively so Σ bank bytes == Σ channel
            # bytes holds even if a program under-consumed.
            for rid, ch_index in memsys.drain(sweep + 1):
                self.mem_deliver(ch_index, rid, sweep)

        self.block()
        wall = time.perf_counter() - t_start
        return self.build_result(sweep + 1, wall)


def execute(design: CompiledDesign,
            binding: Optional[ProgramBinding] = None, *,
            inputs: Optional[Mapping[str, Any]] = None,
            devices: Optional[Sequence[Any]] = None,
            max_sweeps: Optional[int] = None,
            fabric: Any = FROM_DESIGN,
            net_config=None,
            mem: Any = FROM_DESIGN,
            faults: Any = None,
            injector: Any = None,
            checkpoint_dir: Optional[str] = None,
            checkpoint_every: Optional[int] = None,
            tracer: Any = None,
            device_map: Optional[Sequence[int]] = None) -> ExecutionResult:
    """Run ``design`` as a multi-device dataflow program.

    ``binding`` defaults to the app hook resolved from the graph's name
    (``bind_programs(design.graph, inputs)``); ``inputs`` is that hook's
    numeric spec (shapes / iteration counts / seeds).  ``devices`` overrides
    the pool of jax devices (default ``jax.devices()``), and
    ``device_map[logical]`` picks the one each logical device runs on
    (default: logical device d on ``devices[d]``; ``[0, 0, 0, 0]`` puts a
    4-device design on one chip).  ``report.placement`` records the
    choice and ``report.task_devices`` where each task's outputs landed.
    ``fabric`` defaults to the design's fabric (``CompileOptions.fabric``);
    pass ``fabric=None`` to force the ideal transfer path or a
    :class:`~repro.net.fabric.Fabric` to override.  ``net_config`` is the
    :class:`~repro.net.transport.NetConfig` for the fabric transport.
    ``mem`` defaults to the design's bank model (``CompileOptions.mem``);
    pass ``mem=None`` to force the ideal memory path or a
    :class:`~repro.mem.banks.MemConfig` to override.

    Chaos knobs (:mod:`repro.chaos`): ``faults`` is a
    :class:`~repro.net.faults.FaultModel` switching the fabric transport
    into lossy-link + ARQ + route-repair mode (``None`` keeps every path
    byte-identical); ``injector`` / ``checkpoint_dir`` /
    ``checkpoint_every`` are forwarded to :meth:`ExecutionState.run`.

    Observability (:mod:`repro.obs`): ``tracer`` is a
    :class:`~repro.obs.trace.Tracer` recording sweep-granular typed events
    from every layer (``None`` → the zero-overhead ``NULL_TRACER``); a
    recording tracer is attached to the result as ``report.trace``.
    """
    with jax.profiler.TraceAnnotation("exec.execute", graph=design.graph.name,
                                      call=next(_CALLS)):
        with jax.profiler.TraceAnnotation("exec.state"):
            state = ExecutionState(
                design, binding, inputs=inputs, devices=devices,
                max_sweeps=max_sweeps, fabric=fabric,
                net_config=net_config, mem=mem, faults=faults,
                tracer=tracer, device_map=device_map)
        return state.run(injector=injector, checkpoint_dir=checkpoint_dir,
                         checkpoint_every=checkpoint_every)
