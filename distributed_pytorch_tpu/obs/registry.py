"""Unified metrics registry: counters / gauges / reservoirs with labels.

One export surface for every subsystem's numbers. The serving engine,
allocator, admission controller, Trainer, and elastic agent each REGISTER
their metrics here instead of growing another ad-hoc ``stats()`` dialect;
the registry then renders them three ways:

* :meth:`MetricsRegistry.snapshot` — structured JSON (``counters`` /
  ``gauges`` / ``reservoirs``), the payload ``/statusz`` serves and tests
  assert against engine ground truth;
* :meth:`MetricsRegistry.prometheus_text` — Prometheus text exposition
  (counters/gauges as-is, reservoirs as ``summary`` with quantile labels);
* :meth:`MetricsRegistry.merge` — cross-host aggregation: counters and
  gauges sum, reservoirs merge sample-exactly via
  :meth:`~distributed_pytorch_tpu.metrics.ReservoirHistogram.merge_state`,
  so a fleet-wide p99 is computed over the union stream, not averaged
  per-host percentiles (which would be meaningless).

Registration is PULL-based: most metrics are registered as zero-arg
callables resolved at snapshot time (``counter_fn`` / ``gauge_fn`` /
``reservoir``), so the owning object keeps its counters as plain attributes
— one source of truth, no double bookkeeping, and an object that is
replaced wholesale (a caller may swap ``engine.metrics`` after warm-up) stays
correct as long as the callable re-resolves it. :class:`Counter` /
:class:`Gauge` cover the push-style cases (the elastic agent's restart
loop) where no long-lived owner exists.

Naming convention: ``<namespace>_<subsystem>_<name>_<unit>[_total]`` —
``_total`` marks monotonic counters (Prometheus idiom), units are spelled
out (``_seconds``, never ``_s``), and label splits ride on the reservoir's
``label`` key (``serving_ttft_seconds{source="hit"}``) rather than name
suffixes.
"""

from __future__ import annotations

import re
import threading
from typing import Callable, Dict, List, Optional, Sequence, Union

from distributed_pytorch_tpu.metrics import ReservoirGroup, ReservoirHistogram

_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")


def _sanitize(name: str) -> str:
    name = _NAME_RE.sub("_", name)
    if name and name[0].isdigit():
        name = "_" + name
    return name


class Counter:
    """Push-style monotonic counter for owners without a metrics object."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0

    def inc(self, n: int = 1) -> None:
        self.value += n


class Gauge:
    """Push-style settable gauge."""

    __slots__ = ("value",)

    def __init__(self, value: float = 0.0):
        self.value = value

    def set(self, value: float) -> None:
        self.value = value


class MetricsRegistry:
    """Named collection of counters, gauges, and reservoir histograms.

    ``namespace`` prefixes every metric name at export time
    (``serving_...``, ``elastic_...``), so registries from different
    subsystems can be merged or scraped side by side without collisions.
    """

    def __init__(self, namespace: str = ""):
        self.namespace = _sanitize(namespace) if namespace else ""
        # THE observability lock. Scrapes arrive on the introspection
        # server's thread while the engine steps on its own; every render
        # path below takes this lock, and the engine takes it around
        # step()/submit() whenever a server is attached — reservoir reads
        # lazily re-sort their sample buffer, so an unlocked scrape would
        # race the step loop's record() calls. Reentrant: the SLO monitor
        # ticks (reads quantiles) from inside a locked step.
        self.lock = threading.RLock()
        # name -> zero-arg callable returning the current value.
        self._counters: Dict[str, Callable[[], float]] = {}
        self._gauges: Dict[str, Callable[[], float]] = {}
        # name -> (resolver, label_key or None). The resolver returns a
        # ReservoirHistogram (label_key None) or a ReservoirGroup.
        self._reservoirs: Dict[str, tuple] = {}
        # sanitized name -> HELP text for the Prometheus exposition.
        self._help: Dict[str, str] = {}

    # --------------------------------------------------------- registration

    def _check_new(self, name: str) -> str:
        name = _sanitize(name)
        if (
            name in self._counters
            or name in self._gauges
            or name in self._reservoirs
        ):
            raise ValueError(f"metric {name!r} already registered")
        return name

    def counter(self, name: str, help: str = "") -> Counter:
        """Create and register a push-style :class:`Counter`."""
        c = Counter()
        self.counter_fn(name, lambda: c.value, help=help)
        return c

    def counter_fn(
        self, name: str, fn: Callable[[], float], help: str = ""
    ) -> None:
        """Register a pull-style counter: ``fn`` is read at snapshot time
        and must be monotonic over the owner's lifetime."""
        name = self._check_new(name)
        self._counters[name] = fn
        if help:
            self._help[name] = help

    def gauge(self, name: str, value: float = 0.0, help: str = "") -> Gauge:
        """Create and register a push-style :class:`Gauge`."""
        g = Gauge(value)
        self.gauge_fn(name, lambda: g.value, help=help)
        return g

    def gauge_fn(
        self, name: str, fn: Callable[[], float], help: str = ""
    ) -> None:
        name = self._check_new(name)
        self._gauges[name] = fn
        if help:
            self._help[name] = help

    def reservoir(
        self,
        name: str,
        hist: Union[
            ReservoirHistogram,
            ReservoirGroup,
            Callable[[], Union[ReservoirHistogram, ReservoirGroup]],
        ],
        label: Optional[str] = None,
        help: str = "",
    ) -> None:
        """Register a :class:`ReservoirHistogram` (``label=None``) or a
        :class:`ReservoirGroup` (``label`` names the label dimension, e.g.
        ``"source"``). Pass a zero-arg callable to re-resolve the object at
        snapshot time (survives owners that replace their metrics object)."""
        resolver = hist if callable(hist) else (lambda: hist)
        name = self._check_new(name)
        self._reservoirs[name] = (resolver, label)
        if help:
            self._help[name] = help

    # -------------------------------------------------------------- export

    def _qualified(self, name: str) -> str:
        return f"{self.namespace}_{name}" if self.namespace else name

    def _resolve(self, name: str) -> str:
        """Accept either the registered name or the namespace-qualified
        one (as it appears in snapshots) — accessors take both."""
        name = _sanitize(name)
        prefix = f"{self.namespace}_" if self.namespace else ""
        if (
            prefix
            and name.startswith(prefix)
            and not (
                name in self._counters
                or name in self._gauges
                or name in self._reservoirs
            )
        ):
            return name[len(prefix):]
        return name

    def read_counter(self, name: str) -> float:
        """Current value of a registered counter (by either name form)."""
        with self.lock:
            return self._counters[self._resolve(name)]()

    def read_gauge(self, name: str) -> float:
        """Current value of a registered gauge (by either name form)."""
        with self.lock:
            return self._gauges[self._resolve(name)]()

    def read_quantile(
        self, name: str, q: float, label_value: Optional[str] = None
    ) -> float:
        """Current quantile of a registered reservoir; ``label_value``
        selects the series of a labeled group. NaN on empty reservoirs,
        consistent with :meth:`ReservoirHistogram.quantile`."""
        with self.lock:
            resolver, label = self._reservoirs[self._resolve(name)]
            obj = resolver()
            if label is not None:
                if label_value is None:
                    raise ValueError(
                        f"reservoir {name!r} is labeled by {label!r}; "
                        "pass label_value"
                    )
                if label_value not in obj.labels:
                    return float("nan")
                obj = obj[label_value]
            return obj.quantile(q)

    @staticmethod
    def _summary(hist: ReservoirHistogram) -> Dict[str, float]:
        return hist.summary()

    def snapshot(self, include_state: bool = False) -> Dict[str, dict]:
        """Structured JSON view. ``include_state=True`` additionally embeds
        each reservoir's sample state so :meth:`merge` can aggregate
        percentiles sample-exactly across hosts."""
        with self.lock:
            return self._snapshot_locked(include_state)

    def scalars(self) -> Dict[str, Dict[str, float]]:
        """Counters and gauges only, qualified like :meth:`snapshot` but
        WITHOUT reservoir summaries — those sort their samples to build
        percentiles, far too expensive for the TSDB's once-per-engine-step
        sampling tick (reservoir latencies are already windowed by the
        reservoir itself; the derived per-step series cover that story)."""
        with self.lock:
            return {
                "counters": {
                    self._qualified(n): fn()
                    for n, fn in self._counters.items()
                },
                "gauges": {
                    self._qualified(n): fn()
                    for n, fn in self._gauges.items()
                },
            }

    def _snapshot_locked(self, include_state: bool) -> Dict[str, dict]:
        counters = {
            self._qualified(n): fn() for n, fn in self._counters.items()
        }
        gauges = {self._qualified(n): fn() for n, fn in self._gauges.items()}
        reservoirs: Dict[str, dict] = {}
        states: Dict[str, dict] = {}
        for name, (resolver, label) in self._reservoirs.items():
            obj = resolver()
            qname = self._qualified(name)
            if label is None:
                reservoirs[qname] = self._summary(obj)
                if include_state:
                    states[qname] = obj.state()
            else:
                reservoirs[qname] = {
                    "label": label,
                    "series": {
                        value: self._summary(obj[value])
                        for value in obj.labels
                    },
                }
                if include_state:
                    states[qname] = {"label": label, "series": obj.state()}
        out = {
            "counters": counters,
            "gauges": gauges,
            "reservoirs": reservoirs,
        }
        if include_state:
            out["reservoir_states"] = states
        return out

    @classmethod
    def merge(cls, snapshots: List[dict]) -> dict:
        """Aggregate ``snapshot(include_state=True)`` payloads from several
        processes into one snapshot of the same shape: counters and gauges
        sum; reservoirs merge their sample states (exact count/sum/min/max,
        reservoir-union percentiles) and re-render summaries. The multi-host
        story: each host JSON-dumps its snapshot, host 0 gathers and merges."""
        counters: Dict[str, float] = {}
        gauges: Dict[str, float] = {}
        merged_hists: Dict[str, object] = {}
        labels: Dict[str, Optional[str]] = {}
        for snap in snapshots:
            for name, value in snap.get("counters", {}).items():
                counters[name] = counters.get(name, 0) + value
            for name, value in snap.get("gauges", {}).items():
                gauges[name] = gauges.get(name, 0) + value
            for name, state in snap.get("reservoir_states", {}).items():
                if isinstance(state, dict) and "series" in state:
                    labels.setdefault(name, state["label"])
                    series = merged_hists.setdefault(name, {})
                    for lab, sub in state["series"].items():
                        hist = series.get(lab)
                        if hist is None:
                            hist = series[lab] = ReservoirHistogram(
                                int(sub["capacity"])
                            )
                        hist.merge_state(sub)
                else:
                    labels.setdefault(name, None)
                    hist = merged_hists.get(name)
                    if hist is None:
                        hist = merged_hists[name] = ReservoirHistogram(
                            int(state["capacity"])
                        )
                    hist.merge_state(state)
        reservoirs: Dict[str, dict] = {}
        states: Dict[str, dict] = {}
        for name, obj in merged_hists.items():
            if labels[name] is None:
                reservoirs[name] = cls._summary(obj)
                states[name] = obj.state()
            else:
                reservoirs[name] = {
                    "label": labels[name],
                    "series": {
                        lab: cls._summary(h) for lab, h in obj.items()
                    },
                }
                states[name] = {
                    "label": labels[name],
                    "series": {lab: h.state() for lab, h in obj.items()},
                }
        return {
            "counters": counters,
            "gauges": gauges,
            "reservoirs": reservoirs,
            "reservoir_states": states,
        }

    @classmethod
    def merge_remote(
        cls,
        urls: Sequence[str],
        timeout: float = 5.0,
        *,
        retries: int = 1,
        backoff_s: float = 0.1,
    ) -> dict:
        """Scrape each engine's ``/snapshot`` endpoint (see
        ``obs.server.IntrospectionServer``) and :meth:`merge` the payloads
        — N engines' metrics aggregated over HTTP, the routed-fleet signal.
        ``urls`` are server base URLs (``http://host:port``). ``timeout``
        bounds connect + every read per attempt and ``retries`` transport
        retries ride over blips (both forwarded to ``scrape``), so one
        dead or partitioned replica delays a fleet-wide merge by a bounded
        ``(retries+1) * timeout`` instead of hanging it. A peer still dead
        after the retries raises; fleet callers that want partial
        aggregation catch per-URL and merge what answered."""
        from distributed_pytorch_tpu.obs.server import scrape

        return cls.merge(
            [
                scrape(
                    url,
                    "/snapshot",
                    timeout=timeout,
                    retries=retries,
                    backoff_s=backoff_s,
                )
                for url in urls
            ]
        )

    @classmethod
    def render_snapshot(cls, snapshot: dict) -> str:
        """Render a snapshot dict — typically :meth:`merge` /
        :meth:`merge_remote` output — as a Prometheus text body, same
        grammar as :meth:`prometheus_text`. Reservoirs re-render from
        their sample states when present (exact merged quantiles), else
        from the precomputed summaries."""
        lines: List[str] = []

        def head(qname, mtype):
            lines.append(f"# HELP {qname} {cls._escape_help(qname)}")
            lines.append(f"# TYPE {qname} {mtype}")

        def emit_hist(qname, hist, extra=""):
            for q in (0.5, 0.95, 0.99):
                value = hist.quantile(q)
                if value == value:
                    lines.append(f'{qname}{{{extra}quantile="{q}"}} {value}')
            suffix = "{" + extra.rstrip(",") + "}" if extra else ""
            lines.append(f"{qname}_sum{suffix} {hist.sum}")
            lines.append(f"{qname}_count{suffix} {hist.count}")

        def rebuild(state):
            hist = ReservoirHistogram(int(state["capacity"]))
            hist.merge_state(state)
            return hist

        for name, value in snapshot.get("counters", {}).items():
            head(name, "counter")
            lines.append(f"{name} {value}")
        for name, value in snapshot.get("gauges", {}).items():
            head(name, "gauge")
            lines.append(f"{name} {value}")
        states = snapshot.get("reservoir_states")
        if states is not None:
            for name, state in states.items():
                head(name, "summary")
                if isinstance(state, dict) and "series" in state:
                    label = _sanitize(str(state["label"]))
                    for lab, sub in state["series"].items():
                        emit_hist(
                            name,
                            rebuild(sub),
                            extra=f'{label}="{cls._escape_label(lab)}",',
                        )
                else:
                    emit_hist(name, rebuild(state))
        else:
            for name, summ in snapshot.get("reservoirs", {}).items():
                head(name, "summary")
                series = (
                    summ["series"].items()
                    if isinstance(summ, dict) and "series" in summ
                    else [(None, summ)]
                )
                label = (
                    _sanitize(str(summ["label"]))
                    if isinstance(summ, dict) and "series" in summ
                    else None
                )
                for lab, sub in series:
                    extra = (
                        f'{label}="{cls._escape_label(lab)}",'
                        if lab is not None
                        else ""
                    )
                    count = sub.get("count", 0)
                    for q_key, q in (("p50", 0.5), ("p95", 0.95),
                                     ("p99", 0.99)):
                        if q_key in sub:
                            lines.append(
                                f'{name}{{{extra}quantile="{q}"}} '
                                f"{sub[q_key]}"
                            )
                    suffix = "{" + extra.rstrip(",") + "}" if extra else ""
                    total = sub.get("mean", 0.0) * count
                    lines.append(f"{name}_sum{suffix} {total}")
                    lines.append(f"{name}_count{suffix} {count}")
        return "\n".join(lines) + "\n"

    @staticmethod
    def _escape_label(value: object) -> str:
        """Escape a label VALUE per the exposition format: backslash,
        double-quote, and newline must be backslash-escaped."""
        return (
            str(value)
            .replace("\\", "\\\\")
            .replace('"', '\\"')
            .replace("\n", "\\n")
        )

    @staticmethod
    def _escape_help(text: str) -> str:
        """Escape HELP text: backslash and newline (quotes are legal)."""
        return str(text).replace("\\", "\\\\").replace("\n", "\\n")

    def prometheus_text(self) -> str:
        """Prometheus text exposition (one scrape body). Reservoirs render
        as ``summary`` metrics: quantile-labeled samples plus ``_sum`` and
        ``_count``; group labels become ordinary Prometheus labels. Every
        metric gets ``# HELP`` / ``# TYPE`` headers and label values are
        escaped, so real scrapers accept the body as-is (and
        ``obs.promtext.validate_exposition`` enforces it in tests)."""
        with self.lock:
            return self._prometheus_text_locked()

    def _prometheus_text_locked(self) -> str:
        lines: List[str] = []

        def emit_head(name, qname, mtype):
            text = self._help.get(name, qname)
            lines.append(f"# HELP {qname} {self._escape_help(text)}")
            lines.append(f"# TYPE {qname} {mtype}")

        def emit_summary(qname, hist, extra=""):
            for q in (0.5, 0.95, 0.99):
                value = hist.quantile(q)
                if value == value:  # skip NaN on empty reservoirs
                    lines.append(
                        f'{qname}{{{extra}quantile="{q}"}} {value}'
                    )
            suffix = "{" + extra.rstrip(",") + "}" if extra else ""
            lines.append(f"{qname}_sum{suffix} {hist.sum}")
            lines.append(f"{qname}_count{suffix} {hist.count}")

        for name, fn in self._counters.items():
            qname = self._qualified(name)
            emit_head(name, qname, "counter")
            lines.append(f"{qname} {fn()}")
        for name, fn in self._gauges.items():
            qname = self._qualified(name)
            emit_head(name, qname, "gauge")
            lines.append(f"{qname} {fn()}")
        for name, (resolver, label) in self._reservoirs.items():
            obj = resolver()
            qname = self._qualified(name)
            emit_head(name, qname, "summary")
            if label is None:
                emit_summary(qname, obj)
            else:
                for value in obj.labels:
                    extra = (
                        f'{_sanitize(label)}="{self._escape_label(value)}",'
                    )
                    emit_summary(qname, obj[value], extra=extra)
        return "\n".join(lines) + "\n"
