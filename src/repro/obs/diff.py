"""Run-to-run metric regression diffing — the drift gate over registries.

Two :class:`~repro.obs.metrics.MetricsRegistry` snapshots (live objects
or their ``to_json()`` documents) are compared series-by-series: every
``(metric, labels)`` pair present in the baseline must appear in the
candidate within its **relative tolerance** — tolerance 0 (the default)
means integer/float equality, which is the right default here because
almost every metric this repo records is a deterministic integer
(conservation-checked byte and sweep counters).  Wall-clock gauges and
other nondeterministic series are *ignored* by name, not tolerated into
meaninglessness.

Tolerances travel **with the baseline file**, not the caller: a committed
``results/obs_baseline.json`` says which of its metrics may drift and by
how much, so the CI gate (``scripts/obs_diff.py``) has no magic numbers
of its own and a PR that legitimately shifts a metric updates the
baseline (and its tolerance) in the same diff a reviewer sees.

Baseline document format (``obs-baseline/v1``)::

    {"format": "obs-baseline/v1",
     "default_rel_tol": 0.0,
     "tolerances": {"net.link.utilization": 0.05},   # per-metric rel tol
     "ignore": [],                                   # nondeterministic
     "apps": {"stencil": { ...registry.to_json()... }, ...}}

A flat single-registry baseline (``"metrics"`` instead of ``"apps"``) is
accepted too.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

BASELINE_FORMAT = "obs-baseline/v1"
METRICS_FORMAT = "obs-metrics/v1"


def _doc(registry_or_doc: Any) -> Dict[str, Any]:
    """A registry's ``to_json()`` document, from either form."""
    if hasattr(registry_or_doc, "to_json"):
        return registry_or_doc.to_json()
    return dict(registry_or_doc)


def _flatten(doc: Mapping[str, Any]) -> Dict[Tuple[str, Tuple], Any]:
    """``(metric, sorted-label-items) → value`` over a registry doc."""
    out: Dict[Tuple[str, Tuple], Any] = {}
    for name, m in doc.items():
        for s in m.get("series", []):
            key = (name, tuple(sorted(s["labels"].items())))
            out[key] = s["value"]
    return out


@dataclasses.dataclass(frozen=True)
class MetricDelta:
    """One drifted / added / removed series."""

    metric: str
    labels: Dict[str, Any]
    base: Optional[float]          # None = series is new
    new: Optional[float]           # None = series disappeared
    rel_change: Optional[float]    # |new-base| / max(|base|, tiny)
    tol: float
    kind: str                      # "drift" | "added" | "removed"

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def describe(self) -> str:
        lbl = ",".join(f"{k}={v}" for k, v in sorted(self.labels.items()))
        where = f"{self.metric}{{{lbl}}}" if lbl else self.metric
        if self.kind == "added":
            return f"ADDED   {where} = {self.new}"
        if self.kind == "removed":
            return f"REMOVED {where} (was {self.base})"
        return (f"DRIFT   {where}: {self.base} -> {self.new} "
                f"(rel {self.rel_change:.3g} > tol {self.tol:.3g})")


@dataclasses.dataclass
class RegressionDiff:
    """The verdict of one baseline-vs-candidate comparison."""

    violations: List[MetricDelta]      # outside tolerance → gate fails
    added: List[MetricDelta]           # new series (informational)
    removed: List[MetricDelta]         # vanished series → gate fails
    compared: int                      # series checked within tolerance + out
    ignored: int                       # series skipped by the ignore list

    @property
    def ok(self) -> bool:
        return not self.violations and not self.removed

    def to_json(self) -> Dict[str, Any]:
        return {"format": "obs-diff/v1", "ok": self.ok,
                "compared": self.compared, "ignored": self.ignored,
                "violations": [d.to_json() for d in self.violations],
                "removed": [d.to_json() for d in self.removed],
                "added": [d.to_json() for d in self.added]}

    def format(self) -> str:
        lines = [f"compared {self.compared} series "
                 f"({self.ignored} ignored): "
                 + ("OK" if self.ok else "DRIFT DETECTED")]
        for d in self.violations + self.removed:
            lines.append("  " + d.describe())
        for d in self.added:
            lines.append("  " + d.describe())
        return "\n".join(lines)


def diff_registries(baseline: Any, candidate: Any, *,
                    tolerances: Optional[Mapping[str, float]] = None,
                    default_rel_tol: float = 0.0,
                    ignore: Sequence[str] = ()) -> RegressionDiff:
    """Compare two registries (or their ``to_json()`` docs).

    A baseline series drifts when ``|new - base| > tol × max(|base|,
    |new|)`` with ``tol`` the metric's entry in ``tolerances`` (falling
    back to ``default_rel_tol``; tol 0 = exact).  Metrics named in
    ``ignore`` are skipped entirely.  Series present only in the
    candidate are reported as added (informational — a grown repo adds
    metrics); series that vanished fail the gate.
    """
    tolerances = dict(tolerances or {})
    ignored_names = set(ignore)
    base = _flatten(_doc(baseline))
    new = _flatten(_doc(candidate))
    violations: List[MetricDelta] = []
    removed: List[MetricDelta] = []
    added: List[MetricDelta] = []
    compared = ignored = 0
    for key in sorted(base, key=repr):
        metric, litems = key
        if metric in ignored_names:
            ignored += 1
            continue
        tol = float(tolerances.get(metric, default_rel_tol))
        if key not in new:
            removed.append(MetricDelta(metric, dict(litems), base[key],
                                       None, None, tol, "removed"))
            continue
        compared += 1
        b, n = base[key], new[key]
        if isinstance(b, dict) or isinstance(n, dict):
            # Histogram series: compare their totals.
            b = b.get("total", 0) if isinstance(b, dict) else b
            n = n.get("total", 0) if isinstance(n, dict) else n
        scale = max(abs(float(b)), abs(float(n)))
        delta = abs(float(n) - float(b))
        if delta == 0:
            continue
        rel = delta / scale if scale else float("inf")
        if rel > tol:
            violations.append(MetricDelta(metric, dict(litems), b, n,
                                          rel, tol, "drift"))
    for key in sorted(set(new) - set(base), key=repr):
        metric, litems = key
        if metric in ignored_names:
            ignored += 1
            continue
        added.append(MetricDelta(metric, dict(litems), None, new[key],
                                 None, 0.0, "added"))
    return RegressionDiff(violations=violations, added=added,
                          removed=removed, compared=compared,
                          ignored=ignored)


def make_baseline(apps: Mapping[str, Any], *,
                  tolerances: Optional[Mapping[str, float]] = None,
                  ignore: Sequence[str] = (),
                  default_rel_tol: float = 0.0) -> Dict[str, Any]:
    """Build an ``obs-baseline/v1`` document from per-app registries."""
    return {"format": BASELINE_FORMAT,
            "default_rel_tol": float(default_rel_tol),
            "tolerances": dict(tolerances or {}),
            "ignore": list(ignore),
            "apps": {app: _doc(reg) for app, reg in apps.items()}}


def diff_against_baseline(baseline_doc: Mapping[str, Any],
                          candidate_apps: Mapping[str, Any]
                          ) -> Dict[str, RegressionDiff]:
    """Diff candidate per-app registries against a baseline document,
    with tolerances and ignores taken **from the baseline**.  Returns one
    :class:`RegressionDiff` per app; apps only in the baseline get a
    fully-'removed' diff (the smoke stopped covering them — gate fails)."""
    if baseline_doc.get("format") != BASELINE_FORMAT:
        raise ValueError(
            f"not an {BASELINE_FORMAT} document "
            f"(format={baseline_doc.get('format')!r})")
    tolerances = baseline_doc.get("tolerances", {})
    ignore = baseline_doc.get("ignore", [])
    default_tol = float(baseline_doc.get("default_rel_tol", 0.0))
    base_apps = baseline_doc.get("apps")
    if base_apps is None:
        base_apps = {"_": baseline_doc["metrics"]}
        candidate_apps = {"_": next(iter(candidate_apps.values()))} \
            if len(candidate_apps) == 1 else candidate_apps
    out: Dict[str, RegressionDiff] = {}
    for app, base_reg in base_apps.items():
        cand = candidate_apps.get(app, {})
        out[app] = diff_registries(base_reg, cand, tolerances=tolerances,
                                   default_rel_tol=default_tol,
                                   ignore=ignore)
    return out


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)
