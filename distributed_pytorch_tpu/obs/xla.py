"""Device-truth accounting: compiled-program ledger + recompile sentinel.

Everything else in ``obs/`` measures the host's view — wall-clock phases,
queue depths, reservoir latencies. This module measures what XLA is
actually doing with the device:

* :class:`ProgramLedger` wraps each of the engine's compiled programs.
  On the first call with a new argument signature (shapes/dtypes of the
  flattened args) it runs an ANALYSIS-ONLY ahead-of-time compile —
  ``fn.lower(*args).compile()`` — and records compile wall time,
  ``memory_analysis()`` HBM breakdown (argument / output / temp /
  generated-code bytes) and ``cost_analysis()`` FLOPs per program. The
  analyzed executable is then dropped: execution always goes through the
  original jitted callable, so ledger-on output is bitwise-identical to
  ledger-off by construction (the ledger pays one extra compile per
  signature, never a different program). The ledger also carries the
  host↔device transfer counters (staging bytes up, readback bytes down)
  that the engine feeds per step, and a live-buffer HBM watermark read
  from ``jax.live_arrays()``.

* :class:`RecompileSentinel` — after warmup, any new XLA compilation is
  a silent perf killer (a stray shape reaching the step fn recompiles a
  multi-second program mid-serve). Once :meth:`~RecompileSentinel.arm`\\ ed,
  the sentinel trips on (a) any ledger signature miss — with the program
  name and offending shapes — and (b) any backend-compile event from
  ``jax.monitoring`` that is NOT attributed to a ledgered compile, which
  catches compilations the ledger never saw. Each trip bumps a counter
  (exported as ``engine_recompiles_total``), records a flight-recorder
  event, drops a tracer instant, and latches an SLO-style firing gauge.

``jax.monitoring`` has no per-listener removal API, so this module
installs ONE process-wide dispatcher lazily and fans events out to a
``WeakSet`` of armed sentinels — engines come and go, the listener stays
inert when the set is empty.
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

# Substring of the jax.monitoring event key fired once per real XLA
# backend compilation (cached jit calls fire nothing).
_COMPILE_EVENT = "/jax/core/compile/backend_compile"

# ---------------------------------------------------------------------------
# Process-wide compile-event dispatcher (jax.monitoring offers global
# registration only — see module doc).
# ---------------------------------------------------------------------------

_armed_sentinels: "weakref.WeakSet" = weakref.WeakSet()
_dispatcher_lock = threading.Lock()
_dispatcher_installed = False

# Compile events fire synchronously on the thread doing the compilation,
# so a thread-local attribution scope is race-free.
_attribution = threading.local()


def _current_attribution() -> Optional[Tuple[str, tuple]]:
    return getattr(_attribution, "scope", None)


def _on_monitoring_event(event: str, duration: float, **kwargs) -> None:
    if _COMPILE_EVENT not in event:
        return
    for sentinel in list(_armed_sentinels):
        sentinel._on_backend_compile(duration)


def _install_dispatcher() -> bool:
    global _dispatcher_installed
    with _dispatcher_lock:
        if _dispatcher_installed:
            return True
        try:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(
                _on_monitoring_event
            )
        except Exception:
            return False
        _dispatcher_installed = True
        return True


def _signature(args: tuple, kwargs: dict) -> tuple:
    """Cheap per-call signature: shapes/dtypes/weak_type of array leaves,
    repr of everything else — a superset of what distinguishes jit cache
    entries for the engine's call patterns."""
    out: List[object] = []
    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            out.append(
                (
                    tuple(shape),
                    str(dtype),
                    bool(getattr(leaf, "weak_type", False)),
                )
            )
        else:
            out.append(repr(leaf))
    return tuple(out)


def _shape_str(sig: tuple) -> str:
    parts = []
    for entry in sig:
        if isinstance(entry, tuple) and len(entry) == 3:
            shape, dtype, _ = entry
            parts.append(f"{dtype}[{','.join(str(d) for d in shape)}]")
    return " ".join(parts) if parts else "<no array args>"


class ProgramRecord:
    """Analysis results for one (program, signature) pair."""

    __slots__ = (
        "name",
        "signature",
        "compile_seconds",
        "flops",
        "argument_bytes",
        "output_bytes",
        "temp_bytes",
        "generated_code_bytes",
        "calls",
    )

    def __init__(self, name: str, signature: tuple):
        self.name = name
        self.signature = signature
        self.compile_seconds = 0.0
        self.flops = 0.0
        self.argument_bytes = 0
        self.output_bytes = 0
        self.temp_bytes = 0
        self.generated_code_bytes = 0
        self.calls = 0

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "shapes": _shape_str(self.signature),
            "compile_seconds": self.compile_seconds,
            "flops": self.flops,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "calls": self.calls,
        }


class _LedgeredProgram:
    """Callable wrapper installed by :meth:`ProgramLedger.wrap`. The hit
    path is one dict probe on the signature; the miss path runs the AOT
    analysis and notifies the sentinel, all inside an attribution scope so
    the monitoring dispatcher knows these compile events are accounted."""

    __slots__ = ("ledger", "name", "fn", "_records")

    def __init__(self, ledger: "ProgramLedger", name: str, fn: Callable):
        self.ledger = ledger
        self.name = name
        self.fn = fn
        self._records: Dict[tuple, ProgramRecord] = {}

    def __call__(self, *args, **kwargs):
        sig = _signature(args, kwargs)
        record = self._records.get(sig)
        if record is not None:
            record.calls += 1
            return self.fn(*args, **kwargs)
        _attribution.scope = (self.name, sig)
        try:
            record = self.ledger._analyze(self.name, sig, self.fn, args, kwargs)
            self._records[sig] = record
            record.calls += 1
            # First jit execution compiles its own cache entry; keep the
            # attribution scope open so those events are not "foreign".
            return self.fn(*args, **kwargs)
        finally:
            _attribution.scope = None


class ProgramLedger:
    """Per-engine device-truth ledger (see module doc).

    ``analyze=False`` keeps the signature tracking (the sentinel's miss
    detector) but skips the extra AOT compile — for callers who want the
    sentinel without paying double compile time.
    """

    def __init__(self, analyze: bool = True):
        self.analyze = analyze
        self.programs: Dict[Tuple[str, tuple], ProgramRecord] = {}
        self.sentinel: Optional["RecompileSentinel"] = None
        self.analysis_failures = 0
        # Host<->device transfer ledger; the engine feeds byte counts at
        # its staging/readback sites and pulls per-step deltas for the
        # tracer counter tracks.
        self.bytes_h2d_total = 0
        self.bytes_d2h_total = 0
        # Optional per-source attribution: callers passing ``tag=`` to
        # count_h2d/count_d2h (e.g. the host KV tier's "hostkv_spill" /
        # "hostkv_fetch") get their bytes double-entry booked here, so a
        # subsystem's own byte counter can be cross-checked against the
        # device-truth ledger exactly.
        self.bytes_h2d_by_tag: Dict[str, int] = {}
        self.bytes_d2h_by_tag: Dict[str, int] = {}
        self._step_mark_h2d = 0
        self._step_mark_d2h = 0
        # Live-buffer HBM watermark.
        self.live_bytes = 0
        self.live_peak_bytes = 0

    # ------------------------------------------------------------- wrapping

    def wrap(self, name: str, fn: Callable) -> Callable:
        """Wrap one compiled program. Idempotent on already-wrapped fns."""
        if isinstance(fn, _LedgeredProgram):
            return fn
        return _LedgeredProgram(self, name, fn)

    def _analyze(
        self, name: str, sig: tuple, fn: Callable, args: tuple, kwargs: dict
    ) -> ProgramRecord:
        record = ProgramRecord(name, sig)
        self.programs[(name, sig)] = record
        if self.analyze:
            t0 = time.perf_counter()
            try:
                compiled = fn.lower(*args, **kwargs).compile()
            except Exception:
                self.analysis_failures += 1
                compiled = None
            record.compile_seconds = time.perf_counter() - t0
            if compiled is not None:
                self._fill_from_compiled(record, compiled)
        if self.sentinel is not None:
            self.sentinel._on_ledger_miss(name, sig)
        return record

    @staticmethod
    def _fill_from_compiled(record: ProgramRecord, compiled) -> None:
        try:
            mem = compiled.memory_analysis()
        except Exception:
            mem = None
        if mem is not None:
            record.argument_bytes = int(
                getattr(mem, "argument_size_in_bytes", 0) or 0
            )
            record.output_bytes = int(
                getattr(mem, "output_size_in_bytes", 0) or 0
            )
            record.temp_bytes = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
            record.generated_code_bytes = int(
                getattr(mem, "generated_code_size_in_bytes", 0) or 0
            )
        try:
            cost = compiled.cost_analysis()
        except Exception:
            cost = None
        if isinstance(cost, dict):
            record.flops = float(cost.get("flops", 0.0) or 0.0)

    # ------------------------------------------------------ transfer ledger

    def count_h2d(self, nbytes: int, tag: Optional[str] = None) -> None:
        self.bytes_h2d_total += int(nbytes)
        if tag is not None:
            self.bytes_h2d_by_tag[tag] = (
                self.bytes_h2d_by_tag.get(tag, 0) + int(nbytes)
            )

    def count_d2h(self, nbytes: int, tag: Optional[str] = None) -> None:
        self.bytes_d2h_total += int(nbytes)
        if tag is not None:
            self.bytes_d2h_by_tag[tag] = (
                self.bytes_d2h_by_tag.get(tag, 0) + int(nbytes)
            )

    def step_transfer_deltas(self) -> Tuple[int, int]:
        """Bytes moved since the previous call — the per-step numbers the
        engine exports as tracer counter tracks."""
        dh2d = self.bytes_h2d_total - self._step_mark_h2d
        dd2h = self.bytes_d2h_total - self._step_mark_d2h
        self._step_mark_h2d = self.bytes_h2d_total
        self._step_mark_d2h = self.bytes_d2h_total
        return dh2d, dd2h

    # ---------------------------------------------------------- live buffers

    def update_live_bytes(self) -> int:
        """Sum the bytes of every live device array and advance the peak
        watermark. O(live arrays); the engine calls it once per step."""
        total = 0
        try:
            for arr in jax.live_arrays():
                total += int(getattr(arr, "nbytes", 0) or 0)
        except Exception:
            return self.live_bytes
        self.live_bytes = total
        if total > self.live_peak_bytes:
            self.live_peak_bytes = total
        return total

    # --------------------------------------------------------------- export

    @property
    def program_count(self) -> int:
        return len(self.programs)

    def total_compile_seconds(self) -> float:
        return sum(r.compile_seconds for r in self.programs.values())

    def total_flops(self) -> float:
        return sum(r.flops for r in self.programs.values())

    def total_temp_bytes(self) -> int:
        return sum(r.temp_bytes for r in self.programs.values())

    def total_generated_code_bytes(self) -> int:
        return sum(r.generated_code_bytes for r in self.programs.values())

    def metadata(self) -> Dict[str, Any]:
        """The tracer/statusz metadata block: every analyzed program with
        its compile time, HBM breakdown, and FLOPs."""
        return {
            "programs": [
                r.to_dict()
                for r in sorted(
                    self.programs.values(), key=lambda r: r.name
                )
            ],
            "analysis_failures": self.analysis_failures,
            "bytes_h2d_total": self.bytes_h2d_total,
            "bytes_d2h_total": self.bytes_d2h_total,
            "bytes_h2d_by_tag": dict(self.bytes_h2d_by_tag),
            "bytes_d2h_by_tag": dict(self.bytes_d2h_by_tag),
            "live_buffer_bytes": self.live_bytes,
            "live_buffer_peak_bytes": self.live_peak_bytes,
        }

    def register_into(self, registry) -> None:
        """Export the ledger through a :class:`MetricsRegistry`."""
        registry.gauge_fn(
            "xla_programs",
            lambda: float(self.program_count),
            help="Distinct (program, signature) pairs compiled",
        )
        registry.counter_fn(
            "xla_compile_seconds_total",
            self.total_compile_seconds,
            help="Wall-clock spent in ledgered XLA compilation",
        )
        registry.gauge_fn(
            "xla_program_flops",
            self.total_flops,
            help="Sum of cost-analysis FLOPs across compiled programs",
        )
        registry.gauge_fn(
            "xla_temp_bytes",
            lambda: float(self.total_temp_bytes()),
            help="Sum of memory-analysis temp HBM bytes across programs",
        )
        registry.gauge_fn(
            "xla_generated_code_bytes",
            lambda: float(self.total_generated_code_bytes()),
            help="Sum of generated-code bytes across compiled programs",
        )
        registry.gauge_fn(
            "xla_live_buffer_bytes",
            lambda: float(self.live_bytes),
            help="Bytes held by live device arrays at last step",
        )
        registry.gauge_fn(
            "xla_live_buffer_peak_bytes",
            lambda: float(self.live_peak_bytes),
            help="High-water mark of live device array bytes",
        )
        registry.counter_fn(
            "transfer_h2d_bytes_total",
            lambda: float(self.bytes_h2d_total),
            help="Host-to-device staging bytes",
        )
        registry.counter_fn(
            "transfer_d2h_bytes_total",
            lambda: float(self.bytes_d2h_total),
            help="Device-to-host readback bytes",
        )


class RecompileSentinel:
    """Post-warmup compile detector (see module doc). Construct with the
    observability sinks to fan alerts into; ``arm()`` once the engine has
    seen its full working set of shapes."""

    def __init__(
        self,
        ledger: Optional[ProgramLedger] = None,
        tracer=None,
        flight=None,
        name: str = "recompile",
    ):
        self.name = name
        self.tracer = tracer
        self.flight = flight
        self.armed = False
        self.firing = False
        self.count = 0
        self.trips: List[Dict[str, Any]] = []
        self.monitoring_available = False
        if ledger is not None:
            ledger.sentinel = self

    def arm(self) -> None:
        """Start treating every new compilation as an incident."""
        self.armed = True
        self.monitoring_available = _install_dispatcher()
        _armed_sentinels.add(self)

    def disarm(self) -> None:
        self.armed = False
        _armed_sentinels.discard(self)

    # ----------------------------------------------------------- detectors

    def _on_ledger_miss(self, name: str, sig: tuple) -> None:
        if self.armed:
            self._trip(program=name, shapes=_shape_str(sig), source="ledger")

    def _on_backend_compile(self, duration: float) -> None:
        if not self.armed:
            return
        if _current_attribution() is not None:
            # A ledgered program is compiling on this thread; the ledger
            # miss already tripped (or will) with the program's name.
            return
        self._trip(
            program="unattributed",
            shapes="<unknown>",
            source="monitoring",
            compile_seconds=duration,
        )

    # -------------------------------------------------------------- fan-out

    def _trip(self, **fields) -> None:
        self.count += 1
        self.firing = True
        event = dict(fields)
        event["t"] = time.time()
        self.trips.append(event)
        if self.flight is not None:
            try:
                self.flight.record("recompile", **fields)
            except Exception:
                pass
        if self.tracer is not None and getattr(self.tracer, "enabled", False):
            try:
                self.tracer.instant("recompile_sentinel", **fields)
            except Exception:
                pass

    def acknowledge(self) -> None:
        """Clear the firing latch (the counter stays — it is monotonic)."""
        self.firing = False

    def status(self) -> Dict[str, Any]:
        return {
            "armed": self.armed,
            "firing": self.firing,
            "count": self.count,
            "monitoring_available": self.monitoring_available,
            "trips": list(self.trips[-16:]),
        }

    def register_into(self, registry) -> None:
        registry.counter_fn(
            "engine_recompiles_total",
            lambda: float(self.count),
            help="Post-warmup XLA compilations detected by the sentinel",
        )
        registry.gauge_fn(
            "recompile_sentinel_armed",
            lambda: float(self.armed),
            help="1 while the recompile sentinel is armed",
        )
        registry.gauge_fn(
            "recompile_sentinel_firing",
            lambda: float(self.firing),
            help="1 after a post-warmup recompile until acknowledged",
        )


__all__ = [
    "ProgramLedger",
    "ProgramRecord",
    "RecompileSentinel",
]
