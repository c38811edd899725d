"""Device-runtime observability: compile/retrace telemetry + the launch
ledger (docs/observability.md "Device runtime").

The layers built since PR 5 — compressed residency, the decode
workspace, the dispatch batcher — run blind at the device-runtime level:
the PR 7 silent-retrace bug (a cached executable re-traced with another
group's container buckets, dropping run containers) produced zero signal
and was only caught by a bench differential.  This module is the signal:

* ``CompileRegistry`` (process-wide ``COMPILES``): every jit/shard_map
  executable boundary (parallel/mesh_exec.py, parallel/batcher.py's
  launches ride the same executables, the standalone decode buckets in
  ops/containers.py) notes each TRACE of its python body — jax only runs
  the body while tracing, so a ``mark_traced()`` call inside it is an
  exact compile detector.  Per signature: compile count, cumulative/last
  trace+compile wall time, and the argument-shape fingerprint of the
  last trace.  A signature compiling MORE than once is a retrace — a
  visible red flag (structured ``Logger.event`` with the fingerprint
  diff, a ``device.retrace`` span under the active trace, and the
  ``device.retraces_total`` gauge) instead of a silent wrong answer.

* ``LaunchLedger`` (process-wide ``LEDGER``): a bounded ring of recent
  device launches — signature, batch/group size, padded vs actual
  stacked rows (batcher padding waste becomes a measured ratio),
  decode-workspace bytes requested vs the ``decode-workspace-mb``
  ceiling, slice position, and the queue-vs-dispatch wall split — plus
  always-on launch/queue-wait histograms exported at /metrics
  (``pilosa_tpu_device_launch_seconds`` etc., the batcher-histogram
  pattern) and aggregates served at /debug/launches.

Timing discipline: every duration here comes from perf_counter pairs
taken by the instrumented call sites; ``_wall_stamp`` is display-only
correlation, never subtracted (scripts/check.sh lint).
"""

from __future__ import annotations

import contextvars
import hashlib
import threading
import time
from collections import OrderedDict, deque

from .locks import make_lock
from .stats import BucketHistogram


def _wall_stamp() -> float: return time.time()  # display-only wall clock


def device_info() -> dict:
    """The device jax runs this process on, as jax reports it —
    {platform, deviceKind, deviceCount}: what the server logs at
    start-up and /debug/vars' ``device`` section shows, so a process
    that fell back to the CPU says so."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "deviceKind": devs[0].device_kind,
            "deviceCount": len(devs)}


def device_memory() -> list:
    """What each local device's allocator reports now — /debug/vars'
    ``device.memory``, one entry a device in jax's order.  A stack
    sharded over a mesh reads the same on every device; one replicated
    by mistake reads as the whole of it on each.  A backend that
    reports nothing (the CPU) gives nulls."""
    import jax
    out = []
    for d in jax.local_devices():
        m = d.memory_stats() or {}
        out.append({"id": d.id,
                    "bytesInUse": m.get("bytes_in_use"),
                    "peakBytesInUse": m.get("peak_bytes_in_use"),
                    "bytesLimit": m.get("bytes_limit")})
    return out


def fingerprint(args) -> str:
    """Compact argument-shape fingerprint of one executable call —
    ``8x4:int32|16x12x32768:uint32|...`` — the thing a retrace DIFFS:
    the PR 7 bug was exactly a shape change (stacked group size) hitting
    a cached executable."""
    parts = []
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is None:
            parts.append(type(a).__name__)
        else:
            parts.append("x".join(str(d) for d in shape) + ":"
                         + str(getattr(a, "dtype", "?")))
    return "|".join(parts)


def sig_of(key) -> str:
    """Stable short id for an executable cache key (the mesh plan key is
    a long tuple embedding plan reprs): ``<kind>:<10-hex-digest>``."""
    kind = key[0] if isinstance(key, tuple) and key else "exec"
    digest = hashlib.sha1(repr(key).encode()).hexdigest()[:10]
    return f"{kind}:{digest}"


class CompileRegistry:
    """Per-executable-signature compile/retrace telemetry.

    Call protocol (see mesh_exec._InstrumentedExec): ``begin_call()``
    clears this thread's trace flag, the traced python body calls
    ``mark_traced()``, and ``note_call()`` folds the finished call into
    the signature's entry when (and only when) the flag fired.  Tracing
    is synchronous on the calling thread, so a thread-local flag is
    exact even with concurrent launches."""

    MAX_ENTRIES = 512  # bounds /debug/compiles (LRU on compile recency)
    # jax's own event for every executable it builds or loads from its
    # cache, eager ones included (the jit_dynamic_slice of a new padded
    # batch size never passes through an instrumented boundary)
    BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self._lock = make_lock("compile-registry")
        self._local = threading.local()
        self._entries: OrderedDict[str, dict] = OrderedDict()
        self.compiles_total = 0
        self.retraces_total = 0
        self.compile_seconds_total = 0.0
        self.backend_compiles_total = 0
        self._listening = False
        # Server injects its Logger so retraces land in the server log;
        # None (engine/bench standalone) keeps the registry silent.
        self.logger = None

    def listen(self):
        """Count jax's backend-compile events from now on (idempotent;
        the process-wide registry starts listening with the first
        MeshExecutor).  ``backendCompiles - compiles`` is what the
        instrumented boundaries missed."""
        with self._lock:
            if self._listening:
                return
            self._listening = True
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_jax_event)

    def _on_jax_event(self, event: str, duration: float, **kw):
        if event == self.BACKEND_COMPILE_EVENT:
            with self._lock:
                self.backend_compiles_total += 1

    # -- trace detection (thread-local; tracing is synchronous) ------------

    def begin_call(self):
        self._local.traced = False

    def mark_traced(self):
        self._local.traced = True

    def traced(self) -> bool:
        return getattr(self._local, "traced", False)

    # -- recording ---------------------------------------------------------

    def note_call(self, sig: str, kind: str, dur_s: float, fp: str,
                  detail: str = "") -> bool:
        """Fold one finished executable call that TRACED (the caller
        checks ``traced()`` first — fingerprinting is only paid on
        compiles).  Returns True when this was a RETRACE (the signature
        had compiled before)."""
        retrace = None
        with self._lock:
            e = self._entries.get(sig)
            if e is None:
                while len(self._entries) >= self.MAX_ENTRIES:
                    self._entries.popitem(last=False)
                e = {"sig": sig, "kind": kind, "detail": detail,
                     "compiles": 0, "totalCompileS": 0.0,
                     "lastCompileS": 0.0, "lastFingerprint": "",
                     "lastCompileWall": 0.0}
                self._entries[sig] = e
            else:
                self._entries.move_to_end(sig)
            prev_fp = e["lastFingerprint"]
            e["compiles"] += 1
            e["totalCompileS"] += dur_s
            e["lastCompileS"] = dur_s
            e["lastFingerprint"] = fp
            e["lastCompileWall"] = _wall_stamp()
            self.compiles_total += 1
            self.compile_seconds_total += dur_s
            if e["compiles"] > 1:
                self.retraces_total += 1
                retrace = (prev_fp, e["compiles"])
        if retrace is None:
            return False
        prev_fp, n = retrace
        # journal the retrace (docs/observability.md "Cluster plane"):
        # the fleet timeline is where a retrace burst correlates with
        # the p99 spike it caused; emit() never raises
        from . import events
        events.emit("device.retrace", sig=sig, kind=kind, compiles=n,
                    shapes=fp)
        # Telemetry sinks must never take the query path down: the
        # injected logger outlives its Server (process-global registry,
        # most-recent-Server-wins), so a stale/closed stream is a lost
        # log line, not a failed dispatch.
        log = self.logger
        if log is not None:
            try:
                # the signature diff IS the diagnosis: what shape change
                # hit a cached executable (PR 7's was the stacked group
                # size)
                log.event("device.retrace", sig=sig, kind=kind,
                          compiles=n, compileS=round(dur_s, 4),
                          prevShapes=prev_fp, shapes=fp)
            # lint: allow(swallowed-exception) — a stale/closed log
            # stream costs a log line, never the dispatch; the retrace
            # is still counted in the compile registry above
            except Exception:
                pass
        try:
            from .tracing import GLOBAL_TRACER
            ctx = GLOBAL_TRACER.current()
            if ctx is not None and ctx.sampled:
                GLOBAL_TRACER.record_span(
                    "device.retrace", ctx.trace_id, ctx.span_id, dur_s,
                    {"sig": sig, "kind": kind, "compiles": n,
                     "prevShapes": prev_fp, "shapes": fp},
                    collect=ctx.collect)
        # lint: allow(swallowed-exception) — span synthesis is best-
        # effort decoration; the registry + log line above already
        # recorded the retrace, and tracing must never fail a dispatch
        except Exception:
            pass
        return True

    # -- surfaces ----------------------------------------------------------

    def totals(self) -> dict:
        with self._lock:
            return {"compiles": self.compiles_total,
                    "retraces": self.retraces_total,
                    "compileSecondsTotal": round(
                        self.compile_seconds_total, 4),
                    "backendCompiles": self.backend_compiles_total,
                    "executables": len(self._entries)}

    def snapshot(self) -> dict:
        """/debug/compiles: totals + per-signature entries, most recent
        compile last."""
        with self._lock:
            entries = [dict(e) for e in self._entries.values()]
        out = self.totals()
        out["entries"] = entries
        return out


# -- launch context (batcher -> ledger) -------------------------------------
# The dispatcher thread knows the queue wait and ticket count of the
# launch it is about to make; the instrumented executable it calls into
# reads them here.  A contextvar (not a plain thread-local) so the value
# also survives any context-propagating hop in between.

_LAUNCH_CTX: contextvars.ContextVar[dict | None] = \
    contextvars.ContextVar("pilosa_tpu_launch_ctx", default=None)
# Streaming slice position, set by mesh_exec._ShardSchedule around each
# yielded slice: (slice_index, slice_count).
_SLICE: contextvars.ContextVar[tuple | None] = \
    contextvars.ContextVar("pilosa_tpu_launch_slice", default=None)


def set_launch_ctx(queue_s: float = 0.0, tickets: int = 1,
                   rows: int | None = None):
    """Annotate subsequent launches on this thread of execution (the
    batcher's dispatcher sets it per launch); returns a reset token."""
    return _LAUNCH_CTX.set(
        {"queue_s": queue_s, "tickets": tickets, "rows": rows})


def reset_launch_ctx(token):
    _LAUNCH_CTX.reset(token)


def launch_ctx() -> dict | None:
    return _LAUNCH_CTX.get()


def set_slice(idx: int | None, count: int | None = None):
    _SLICE.set(None if idx is None else (idx, count))


def current_slice() -> tuple | None:
    return _SLICE.get()


class LaunchLedger:
    """Bounded ring of recent device launches + always-on aggregates.

    One entry per compiled-executable invocation (the mesh dispatch
    choke point): what launched, how padded, how much transient decode
    workspace it asked for, and how long it queued vs dispatched.
    ``rows`` here are launch units — stacked shard rows x fused query
    rows — so both the shard-axis bucket padding and the batcher's
    pow-2 query-axis padding show up in one waste ratio."""

    def __init__(self, size: int = 256):
        self._lock = make_lock("launch-ledger")
        self.size = max(int(size), 1)
        self._ring: deque = deque(maxlen=self.size)
        self.launches_total = 0
        self.rows_actual_total = 0
        self.rows_padded_total = 0
        # stacked shards every launch ran over, and how many of them
        # were the bucket's zero padding (MeshExecutor._bucket)
        self.shards_stacked_total = 0
        self.shards_padded_total = 0
        self.decode_peak_bytes = 0   # high-watermark of per-launch decode
        self.decode_bytes_total = 0
        # Pallas container-kernel accounting (ops/kernels.py): launches
        # that embedded fused decode kernels, and the VMEM container
        # tiles those kernels walked — decode bytes measured as tile
        # traffic instead of an XLA temp watermark
        self.kernel_launches_total = 0
        self.kernel_tiles_total = 0
        # host seconds of the dispatch path's layers, summed over every
        # launch (docs/observability.md "Layer spans"): waiting for the
        # batcher, stacking and placing, handing programs to the runtime
        self.queue_seconds_total = 0.0
        self.place_seconds_total = 0.0
        self.dispatch_seconds_total = 0.0
        # exported as pilosa_tpu_device_* histogram families at /metrics
        # (own exposition like the batcher's, outside the stats client)
        self.launch_hist = BucketHistogram(
            [0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
             0.5, 1.0, 2.5, 5.0])
        self.queue_hist = BucketHistogram(
            [0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.05,
             0.1, 0.5])

    def resize(self, size: int):
        """Apply launch-ledger-size (most recent Server's config wins,
        like the memory budgets); keeps the newest entries."""
        size = max(int(size), 1)
        with self._lock:
            if size != self.size:
                self._ring = deque(self._ring, maxlen=size)
                self.size = size

    def record(self, *, sig: str, kind: str, shards: int,
               shards_padded: int, batch_rows: int,
               batch_rows_padded: int, queue_s: float, dispatch_s: float,
               decode_bytes: int, compiled: bool, tickets: int = 1,
               slice_pos: tuple | None = None, kernel_launches: int = 0,
               kernel_tiles: int = 0):
        actual = max(shards, 0) * max(batch_rows, 1)
        total = max(shards_padded, shards) * max(batch_rows_padded,
                                                 batch_rows, 1)
        padded = max(total - actual, 0)
        entry = {
            "wall": _wall_stamp(), "sig": sig, "kind": kind,
            "shards": shards, "shardsPadded": shards_padded,
            "batchRows": batch_rows, "batchRowsPadded": batch_rows_padded,
            "rowsActual": actual, "rowsPadded": padded,
            "queueS": round(queue_s, 6), "dispatchS": round(dispatch_s, 6),
            "decodeBytes": decode_bytes, "compiled": compiled,
            "tickets": tickets,
        }
        if slice_pos is not None:
            entry["slice"] = slice_pos[0]
            entry["slices"] = slice_pos[1]
        if kernel_launches:
            entry["kernelLaunches"] = kernel_launches
            entry["kernelTiles"] = kernel_tiles
        with self._lock:
            self._ring.append(entry)
            self.launches_total += 1
            self.rows_actual_total += actual
            self.rows_padded_total += padded
            self.shards_stacked_total += max(shards_padded, shards, 0)
            self.shards_padded_total += max(shards_padded - shards, 0)
            self.decode_bytes_total += decode_bytes
            self.decode_peak_bytes = max(self.decode_peak_bytes,
                                         decode_bytes)
            self.kernel_launches_total += kernel_launches
            self.kernel_tiles_total += kernel_tiles
            self.queue_seconds_total += queue_s
            self.dispatch_seconds_total += dispatch_s
        self.launch_hist.observe(dispatch_s)
        if queue_s > 0:
            self.queue_hist.observe(queue_s)

    def timing(self, name: str, seconds: float):
        """The ledger as the sink of the ``dispatch.place`` layer spans
        (``layer_span("dispatch.place", LEDGER)``), and of no other:
        stacking and placing a launch's inputs happens in modules that
        hold no stats client."""
        if name != "dispatch.place":
            raise ValueError(f"the launch ledger keeps no seconds of "
                             f"{name!r}")
        with self._lock:
            self.place_seconds_total += seconds

    def reset_decode_peak(self):
        """Restart the decode-workspace high-watermark (bench-leg
        brackets — the gauge analog of DeviceBudget.reset_peak), so each
        leg reports its own peak instead of a predecessor's."""
        with self._lock:
            self.decode_peak_bytes = 0

    def padding_waste_ratio(self) -> float:
        with self._lock:
            total = self.rows_actual_total + self.rows_padded_total
            return self.rows_padded_total / total if total else 0.0

    def aggregates(self) -> dict:
        with self._lock:
            total = self.rows_actual_total + self.rows_padded_total
            return {
                "launches": self.launches_total,
                "rowsActual": self.rows_actual_total,
                "rowsPadded": self.rows_padded_total,
                "paddingWasteRatio": round(
                    self.rows_padded_total / total, 4) if total else 0.0,
                "shardsStacked": self.shards_stacked_total,
                "shardsPadded": self.shards_padded_total,
                "decodePeakBytes": self.decode_peak_bytes,
                "decodeBytesTotal": self.decode_bytes_total,
                "kernelLaunches": self.kernel_launches_total,
                "kernelTiles": self.kernel_tiles_total,
                "queueSecondsTotal": self.queue_seconds_total,
                "placeSecondsTotal": self.place_seconds_total,
                "dispatchSecondsTotal": self.dispatch_seconds_total,
                "size": self.size,
            }

    def snapshot(self) -> dict:
        """/debug/launches: aggregates + the ring, newest last."""
        out = self.aggregates()
        with self._lock:
            out["entries"] = list(self._ring)
        out["launchS"] = self.launch_hist.snapshot()
        out["queueS"] = self.queue_hist.snapshot()
        return out

    def prometheus_text(self) -> str:
        lines = self.launch_hist.prometheus_lines(
            "pilosa_tpu_device_launch_seconds")
        lines += self.queue_hist.prometheus_lines(
            "pilosa_tpu_device_launch_queue_seconds")
        return "\n".join(lines) + "\n"


class FetchCounts:
    """How often results crossed to the host (parallel/fetch.py;
    /debug/vars ``device.fetches``): ``transfers`` counts every
    ``jax.device_get`` the result-fetch layer made, shared or not;
    ``shared_tickets`` the tickets of fused launches served from a
    transfer another ticket's thread made.  Plain ints, written and
    read without a lock: a lock every request thread takes is dear."""

    __slots__ = ("transfers", "shared_tickets")

    def __init__(self):
        self.transfers = 0
        self.shared_tickets = 0

    def snapshot(self) -> dict:
        return {"transfers": self.transfers,
                "sharedTickets": self.shared_tickets}


# Process-wide singletons, like DEFAULT_BUDGET: one device runtime per
# process, one telemetry surface.  Tests use deltas or private instances.
COMPILES = CompileRegistry()
LEDGER = LaunchLedger()
FETCHES = FetchCounts()
