"""Reduce a ``jax.profiler`` xplane trace to the benchmark's device numbers.

What a traced run needs from the trace:

* the traced window: the host span ``bench.window`` that the harness puts
  around its timed loop (its thread is the harness's own);
* per chip, the union of the intervals in which an operation ran (the
  ``XLA Ops`` line of each ``/device:TPU:<id>`` plane), clipped to the
  window: the chip's busy seconds;
* per chip and operation, its summed self time (an op's time less
  the ops nested in it, as a ``while`` holds its body's ops), keyed
  ``<module>/<opcode>``: the jitted program it ran in (the ``XLA Modules``
  line, without its fingerprint) and its HLO opcode, with the target of a
  custom call (``jit_dilate_op/custom-call:tpu_custom_call`` is a Pallas
  kernel launched from ``dilate_op``), and its count of events;
* per chip, how far its first and last op lie from the window's edges: a
  device clock that runs off the host's shows there;
* each idle gap of each chip, named by the innermost host span that was
  open on the harness's thread at the gap's midpoint: what the host was
  doing while the chip waited.

Only the profiler's own reader (``jax.profiler.ProfileData``) is used.
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import pathlib
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPCODE = re.compile(r"([a-z][a-z0-9_-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@dataclasses.dataclass
class TraceSummary:
    """What one traced window came to."""

    window_s: float
    busy_s: Dict[int, float]                 # chip id -> seconds busy
    chip_op_s: Dict[int, Dict[str, float]]   # chip -> module/opcode -> self s
    chip_op_count: Dict[int, Dict[str, int]]  # chip -> module/opcode -> events
    edges_s: Dict[int, Tuple[float, float]]  # chip -> (window start to its
    #                                          first op, last op to the end)
    idle_s: Dict[str, float]                 # host activity -> idle s, per chip

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    @property
    def op_s(self) -> Dict[str, float]:
        """Self seconds of each operation, summed over the chips."""
        return _summed(self.chip_op_s)

    @property
    def op_count(self) -> Dict[str, int]:
        """Events of each operation, summed over the chips."""
        return _summed(self.chip_op_count)

    def op_seconds(self, match: Callable[[str], bool]) -> float:
        """Summed device self time of the operations whose key matches."""
        return sum(s for key, s in self.op_s.items() if match(key))

    def breakdown(self, top: int = 10) -> Dict[str, List[List]]:
        """The ``breakdown`` of the result line: top device ops and the
        largest idle shares by host activity."""
        return {"device_ops": _ranked(self.op_s, top),
                "idle_gaps": _ranked(self.idle_s, top)}

    def per_chip(self, top: int = 3) -> Dict[int, Dict]:
        """Each chip's busy seconds, window edges and top operations as
        ``[key, self seconds, events]``: chips of one run, or short and
        long windows of one cell, should agree per event."""
        return {chip: {"busy_s": self.busy_s[chip],
                       "edges_s": list(self.edges_s[chip]),
                       "ops": [[k, s, self.chip_op_count[chip][k]] for k, s
                               in _ranked(self.chip_op_s[chip], top)]}
                for chip in sorted(self.busy_s)}


def _summed(by_chip):
    out = {}
    for ops in by_chip.values():
        for k, v in ops.items():
            out[k] = out.get(k, 0) + v
    return out


def _ranked(d, top):
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]


def load(path) -> "ProfileData":
    """Read an ``.xplane.pb`` file, gzipped or not."""
    from jax.profiler import ProfileData
    path = pathlib.Path(path)
    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    return ProfileData.from_file(str(path))


def find_xplane(log_dir) -> pathlib.Path:
    """The one ``.xplane.pb`` a ``jax.profiler.start_trace`` run wrote."""
    found = sorted(pathlib.Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {log_dir}, found {len(found)}")
    return found[0]


def _events(line) -> List[Tuple[int, int, str]]:
    out = []
    for ev in line.events:
        start = int(ev.start_ns)
        out.append((start, start + int(ev.duration_ns), ev.name))
    return out


def op_key(module: str, name: str) -> str:
    """``<module>/<opcode>`` for an ``XLA Ops`` event named by its HLO
    text (``%fusion.3 = f32[8]{0} fusion(...), kind=kLoop, ...``)."""
    rhs = name.split(" = ", 1)[-1]
    m = _OPCODE.search(rhs)
    opcode = m.group(1) if m else rhs.split(" ", 1)[0]
    target = _TARGET.search(rhs)
    if opcode == "custom-call" and target:
        opcode = f"custom-call:{target.group(1)}"
    return f"{module.split('(', 1)[0]}/{opcode}"


def self_times(ops: Sequence[Tuple[int, int, str]]) -> List[int]:
    """Each op's duration less the part that later ops starting inside it
    cover (a ``while`` less its body's ops)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = [e - s for s, e, _ in ops]
    stack: List[int] = []
    for i in order:
        s, e, _ = ops[i]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, ops[stack[-1]][1]) - s
        stack.append(i)
    return own


def _module_of(modules: Sequence[Tuple[int, int, str]]):
    starts = [m[0] for m in modules]

    def find(t: int) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < modules[i][1]:
            return modules[i][2]
        return "unknown"
    return find


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: Sequence[Tuple[int, int]], w0: int, w1: int
         ) -> List[Tuple[int, int]]:
    """The parts of ``[w0, w1)`` that the disjoint sorted ``busy`` leaves."""
    out, t = [], w0
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < w1:
        out.append((t, w1))
    return out


def innermost(spans: Sequence[Tuple[int, int, str]],
              points: Sequence[int], outside: str = "untraced"
              ) -> List[str]:
    """For each point, the name of the innermost span covering it.

    ``spans`` come from one thread, so they nest; ``points`` are sorted.
    """
    order = sorted(spans, key=lambda sp: (sp[0], -(sp[1] - sp[0])))
    stack: List[Tuple[int, int, str]] = []
    names, i = [], 0
    for p in points:
        while i < len(order) and order[i][0] <= p:
            while stack and stack[-1][1] <= order[i][0]:
                stack.pop()
            stack.append(order[i])
            i += 1
        while stack and stack[-1][1] <= p:
            stack.pop()
        names.append(stack[-1][2] if stack else outside)
    return names


def reduce(profile, chips: Sequence[int]) -> TraceSummary:
    """Reduce ``profile`` (a ``ProfileData``) over the chips ``chips``."""
    host = profile.find_plane_with_name(HOST_PLANE)
    if host is None:
        raise ValueError(f"trace has no {HOST_PLANE} plane")
    window: Optional[Tuple[int, int]] = None
    thread: List[Tuple[int, int, str]] = []
    for line in host.lines:
        evs = _events(line)
        spans = [ev for ev in evs if ev[2] == WINDOW_SPAN]
        if spans:
            if window is not None or len(spans) != 1:
                raise ValueError(f"trace has more than one {WINDOW_SPAN}")
            window, thread = spans[0][:2], evs
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    w0, w1 = window

    busy_s: Dict[int, float] = {}
    chip_op_s: Dict[int, Dict[str, float]] = {}
    chip_op_count: Dict[int, Dict[str, int]] = {}
    edges_s: Dict[int, Tuple[float, float]] = {}
    idle_ns: Dict[str, int] = {}
    wanted = set(int(c) for c in chips)
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) not in wanted:
            continue
        chip = int(m.group(1))
        lines = {line.name: _events(line) for line in plane.lines}
        module_of = _module_of(sorted(lines.get(MODULES_LINE, [])))
        ops = []
        for s, e, name in lines.get(OPS_LINE, []):
            s, e = max(s, w0), min(e, w1)
            if e > s:
                ops.append((s, e, op_key(module_of(s), name)))
        op_s, op_count = chip_op_s.setdefault(chip, {}), \
            chip_op_count.setdefault(chip, {})
        for (s, _, key), own in zip(ops, self_times(ops)):
            op_s[key] = op_s.get(key, 0.0) + own * 1e-9
            op_count[key] = op_count.get(key, 0) + 1
        busy = union([(s, e) for s, e, _ in ops])
        busy_s[chip] = sum(e - s for s, e in busy) * 1e-9
        edges_s[chip] = ((busy[0][0] - w0) * 1e-9, (w1 - busy[-1][1]) * 1e-9
                         ) if busy else ((w1 - w0) * 1e-9,) * 2
        idle = gaps(busy, w0, w1)
        mids = [(s + e) // 2 for s, e in idle]
        for (s, e), name in zip(idle, innermost(thread, mids)):
            idle_ns[name] = idle_ns.get(name, 0) + (e - s)
    missing = wanted - set(busy_s)
    if missing:
        raise ValueError(f"trace has no device plane for chip(s) "
                         f"{sorted(missing)}")
    if not any(busy_s.values()):
        raise ValueError("no device operation ran in the traced window")
    n = len(busy_s)
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_s, chip_op_s=chip_op_s,
        chip_op_count=chip_op_count, edges_s=edges_s,
        idle_s={k: v * 1e-9 / n for k, v in idle_ns.items()})
