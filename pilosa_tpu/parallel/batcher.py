"""Cross-query dynamic batching for device dispatch.

Concurrent request threads used to launch one shard_map executable per
query behind the process-wide collective-launch lock
(mesh_exec._DISPATCH_LOCK): under load the device serialized one
dispatch-floor launch per query, which is why the served HTTP path peaked
~two orders of magnitude below the hand-batched engine path (BENCH_r05
``2_http_path`` vs ``1_count_row_1shard``).  The reference amortizes
per-query overhead by fanning shard jobs into a shared goroutine pool
(executor.go:2455 mapReduce); the TPU-native analog is to coalesce
compatible in-flight queries into ONE fused device launch — the
continuous/dynamic-batching shape serving stacks use to amortize kernel
dispatch.

Mechanics: each per-stage reducer node with a batch axis (``count``,
``row_counts``, ``bsi_sum``, ``segments``; parallel/nodes.py) is enqueued
as a ticket carrying the node and its params matrix, keyed by what its
launch would share (node kind and repr, index, shard set, holder); a
dispatcher thread drains compatible tickets — stacking their params rows
along the leading query axis, launching the node ONCE over the stacked
matrix (``MeshExecutor.reduce_async``, the node's body vmapped over that
axis), and handing each waiting future its rows of the results: views
of ONE host copy the launch's tickets share for the reduced kinds, a
device slice a ticket for ``segments`` (parallel/fetch.py).  Launch
policy is adaptive: fire when the queue reaches ``max_batch`` tickets or
the oldest ticket has waited ``window_us`` microseconds; fused
query-axis sizes pad up to powers of two so compile-cache churn stays
bounded.  A ticket that drains alone launches the same node over its
own rows: a lone call is B = 1 of the program a fused pack runs, not
another executable.

Deadlines (docs/robustness.md): time queued here counts against the
query budget — tickets carry their QueryContext, and an expired or
cancelled ticket is dropped from the batch BEFORE launch (its waiter
gets DeadlineExceeded -> HTTP 504), never after.  Composition with the
other serving layers (docs/batching.md): over-budget working sets (the
PR1 shard-streaming path) bypass fusion and stream per ticket;
result-cache lookups (PR3) happen before a ticket is ever created;
admission control (PR2) gates the HTTP edge upstream of the queue.
Multi-process meshes bypass the batcher entirely — independent
per-process windows would fuse different batch shapes and wedge the
collectives.
"""

from __future__ import annotations

import itertools
import threading
import time
from concurrent.futures import Future
from contextlib import contextmanager

import numpy as np

from ..utils import devobs
from ..utils import profile as qprof
from ..utils.deadline import DeadlineExceeded, activate, current
from ..utils.faults import FAULTS
from ..utils.locks import make_condition
from ..utils.stats import BucketHistogram, NopStatsClient, ReservoirTimer
from ..utils.tracing import GLOBAL_TRACER, layer_span
from .fetch import HostView, SharedFetch
from .mesh_exec import _DISPATCH_LOCK, field_rows
from .nodes import BATCH_KINDS, PER_SHARD_KINDS, ROW_BYTES, \
    batch_temp_bound, node_keys, node_temp_rows, pad_pow2_rows

# Total fused query-axis rows per launch: a batched call group's tickets
# are pre-chunked by executor._batch_chunks to keep per-device gather
# temps bounded, but fusing k of them multiplies those temps by k — cap
# the fused row count so a burst of large prepared batches cannot OOM
# the device.  A ticket that alone exceeds the cap launches un-fused.
FUSED_ROWS_MAX = 4096


class _Ticket:
    __slots__ = ("kind", "key", "params", "payload", "ctx",
                 "enq", "future", "background", "trace", "prof",
                 "prof_node", "temp_weight")

    def __init__(self, kind, key, params, payload, background,
                 temp_weight: int = 0):
        self.kind = kind
        self.key = key
        self.params = params          # [B_local, P] int32
        self.payload = payload
        # device-temp bytes one fused B-row of this ticket costs (the
        # [B, rows, W] masked temp of filtered row_counts; 0 = only the
        # FUSED_ROWS_MAX row cap applies; a whole-query ticket's is the
        # runner's to say, at dispatch).  The fusion packer holds
        # SUM(rows x weight) to the batch-temp bound — fusing k
        # over-sized tickets multiplied the temp k-fold and OOM'd
        # small-RAM hosts (the BENCH_r07 sizing gap).
        self.temp_weight = temp_weight
        self.ctx = current()          # the submitting query's deadline
        # trace + profile context cross the dispatcher-thread boundary
        # with the ticket (a thread-local would silently drop them):
        # spans/stage events recorded at launch parent under the
        # submitting query (docs/observability.md)
        self.trace = GLOBAL_TRACER.capture()
        self.prof, self.prof_node = qprof.capture()
        self.enq = time.monotonic()
        self.future = Future()
        self.background = background


class _RoundTimings:
    """The dispatcher thread's layer-span seconds, handed to the stats
    client once a round: one acquisition of the stats lock, which every
    request thread contends for, in place of one a span and one a
    ticket.  Stands where a span expects its stats client."""

    __slots__ = ("stats", "pending")

    def __init__(self, stats):
        self.stats = stats
        self.pending: list[tuple[str, float]] = []

    def timing(self, name: str, seconds: float):
        self.pending.append((name, seconds))

    def flush(self):
        pending, self.pending = self.pending, []
        self.stats.timings(pending)


class DispatchBatcher:
    """Front door for every mesh reducer dispatch (docs/batching.md).

    Request threads call ``reduce`` (one reducer node, per stage) and
    ``whole_query`` (a request's whole program) instead of the
    MeshExecutor and WholeQueryRunner entry points; when batching is
    enabled the call becomes a ticket and blocks until the dispatcher
    thread has LAUNCHED it (results stay unfetched device arrays,
    preserving the executor's dispatch-all-then-fetch-once pipeline).
    Disabled (``dispatch-batch = off``), both are plain delegations —
    the explicit fallback the batcher-bypass lint allows."""

    def __init__(self, mesh, enabled: bool = True, max_batch: int = 32,
                 window_us: float = 200.0, stats=None):
        self.mesh = mesh
        self.enabled = enabled
        self.max_batch = max(int(max_batch), 1)
        self.window_s = max(float(window_us), 0.0) / 1e6
        self.stats = stats if stats is not None else NopStatsClient()
        self._round_stats = _RoundTimings(self.stats)
        self._cond = make_condition("batcher", rlock=True)
        self._queue: list[_Ticket] = []
        self._thread: threading.Thread | None = None
        self._tid: int | None = None
        self._closed = False
        self._bg_local = threading.local()
        # observability (surfaced at /debug/vars + /metrics)
        self.fused_launches = 0
        self.single_launches = 0
        self.stream_fallbacks = 0
        self.expired_drops = 0
        self.batch_size_hist = BucketHistogram([1, 2, 4, 8, 16, 32, 64])
        self.window_wait = ReservoirTimer(512)

    # -- lifecycle ---------------------------------------------------------

    def _ensure_thread(self):
        if self._thread is None:
            t = threading.Thread(target=self._loop, daemon=True,
                                 name="ptpu-dispatch")
            self._thread = t
            self._tid = None
            t.start()

    def close(self):
        """Stop accepting tickets, drain the queue (remaining tickets
        still launch — their waiters are blocked on the futures), and
        join the dispatcher."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout=10)

    # -- routing -----------------------------------------------------------

    def _use_ticket(self) -> bool:
        # multiprocess: per-process windows would fuse DIFFERENT batch
        # shapes across processes and wedge the collectives; dispatcher
        # re-entrance would deadlock on its own queue
        return (self.enabled and not self.mesh.multiprocess
                and threading.get_ident() != self._tid)

    def _submit(self, kind, key, params, payload, temp_weight: int = 0):
        bg = getattr(self._bg_local, "flag", False)
        t = _Ticket(kind, key, np.ascontiguousarray(params, dtype=np.int32),
                    payload, bg, temp_weight=temp_weight)
        with self._cond:
            if self._closed:
                return None
            self._ensure_thread()
            self._queue.append(t)
            self._cond.notify_all()
        return t.future.result()

    @contextmanager
    def background(self):
        """Mark this thread's submissions as background work (cache
        rebuilds, maintenance): counted separately, and the thread is
        expected to interleave ``yield_to_foreground()`` between units so
        it never starves foreground queries of the dispatcher."""
        self._bg_local.flag = True
        try:
            yield self
        finally:
            self._bg_local.flag = False

    def yield_to_foreground(self, max_wait: float = 0.05):
        """Bounded wait while foreground tickets are queued — background
        loops (recalculate-caches rank rebuilds) call this between
        fragments so a long rebuild can't monopolize the GIL/dispatcher
        while queries wait."""
        deadline = time.monotonic() + max_wait
        while time.monotonic() < deadline:
            with self._cond:
                busy = any(not t.background for t in self._queue)
            if not busy:
                return
            time.sleep(0.001)

    def pending(self) -> int:
        with self._cond:
            return len(self._queue)

    # -- the ticket surface (executor-facing) ------------------------------

    def _temp_weight(self, node, holder, index, shards) -> int:
        """Per-fused-B-row device-temp bytes of a filtered row_counts
        launch ([rows, W] masked temp per stacked shard per device, by
        nodes.node_temp_rows) — the fusion packer's unit.  0 for the
        filter-less broadcast pass (B-independent) and for the kinds
        only the fused-row cap holds."""
        if node.kind != "row_counts" or node.plan is None:
            return 0
        rows = node_temp_rows(node.kind, node.plan, 0,
                              field_rows(holder, index, *node.primary))
        per_dev = self.mesh.stacked_per_device(max(len(shards), 1))
        return rows * per_dev * ROW_BYTES

    def reduce(self, node, mat, holder, index, shards,
               scheduled: bool = False, fuse: bool = True):
        """One reducer node (parallel/nodes.py) with its params matrix
        ``mat`` [B, P] over ``shards``, per stage: what
        ``MeshExecutor.reduce_async`` returns, (unfetched parts, their
        groups' shard lists).  A single call is B = 1; a batched call
        group brings its B rows.  A node with a batch axis becomes a
        ticket that fuses with every other of its key — the node's
        repr, index, shard set and holder — whatever their row counts;
        bsi_minmax and group_counts have none and launch from the
        calling thread, as every node does with batching off, on a
        multi-process mesh and with ``fuse`` off (a caller that walks a
        multi-slice schedule of its own).  ``scheduled``: ``shards`` is
        a slice of the caller's shard schedule, not to be scheduled
        again."""
        if fuse and node.kind in BATCH_KINDS and self._use_ticket():
            out = self._submit(
                node.kind,
                (node.kind, repr(node), index, tuple(shards), id(holder)),
                mat,
                {"node": node, "holder": holder, "index": index,
                 "shards": list(shards), "scheduled": scheduled},
                temp_weight=self._temp_weight(node, holder, index, shards))
            if out is not None:     # None: closed mid-flight, go direct
                return out
        return self.mesh.reduce_async(node, mat, holder, index, shards,
                                      scheduled=scheduled)

    # -- whole-query programs (docs/whole-query.md) ------------------------

    _wq_nofuse = itertools.count()

    def whole_query(self, runner, program, mats, holder, index, shards):
        """One whole-query program launch.  Concurrent requests whose
        programs share a shape (same reducer tuple, index, shard set)
        fuse by concatenating each node's params matrix along the batch
        axis — the batched parameter axis rides the SAME compiled
        program, so the fused-launch economics of the reducer tickets
        carry over to whole requests.  Programs with non-batchable
        nodes (bsi_minmax, group_counts) launch un-fused.  Fusing
        programs multiplies their batch-dependent temporaries: the
        packer asks the runner what a batch row of the program cost by
        the compiler's figure for its last launch (``row_temp_bytes``),
        over the fewest shards the launch can take at once."""
        if not self._use_ticket():
            return runner.run(program, mats, holder, index, shards)
        key = ("wholequery", repr(program), index, tuple(shards),
               id(holder))
        if not runner.fusible(program):
            # unique key: never coalesced with another ticket
            key = key + ("nofuse", next(self._wq_nofuse))
        rows = sum(m[0].shape[0] if isinstance(m, tuple) else m.shape[0]
                   for m in mats)
        out = self._submit(
            "wholequery", key,
            np.zeros((max(rows, 1), 0), dtype=np.int32),
            {"runner": runner, "program": program, "mats": mats,
             "holder": holder, "index": index, "shards": list(shards)})
        if out is None:  # closed mid-flight: direct
            return runner.run(program, mats, holder, index, shards)
        return out

    # -- dispatcher --------------------------------------------------------

    def _loop(self):
        # Every instant of this thread lies in one of three layer spans
        # (docs/observability.md "Layer spans"): dispatch.idle (no
        # ticket: the cause of a device gap is upstream), dispatch.window
        # (the coalescing hold) and dispatch.round (busy).  Each span
        # closes after the condition is released, so that no submitter
        # waits for this thread's bookkeeping, and a round's timings go
        # to the stats client together once it has ended.
        self._tid = threading.get_ident()
        rstats = self._round_stats
        while True:
            # only this thread takes tickets off the queue, so one seen
            # without the lock stays
            if not self._queue:
                with layer_span("dispatch.idle", rstats), self._cond:
                    while not self._queue and not self._closed:
                        self._cond.wait()
                if not self._queue:
                    rstats.flush()
                    return  # closed and drained
            # adaptive window: launch when full OR the oldest ticket
            # has waited its window (new arrivals re-check the gate)
            with layer_span("dispatch.window", rstats), self._cond:
                limit = self._queue[0].enq + self.window_s
                while not self._closed and \
                        len(self._queue) < self.max_batch:
                    now = time.monotonic()
                    if now >= limit:
                        break
                    self._cond.wait(limit - now)
                batch, self._queue = self._queue, []
            with layer_span("dispatch.round", rstats, tickets=len(batch)):
                try:
                    self._dispatch(batch)
                # the loop must survive anything
                except BaseException as e:
                    err = e if isinstance(e, Exception) else RuntimeError(
                        f"dispatcher aborted: {e!r}")
                    for t in batch:
                        if not t.future.done():
                            t.future.set_exception(err)
            rstats.flush()

    def _dispatch(self, batch):
        groups: dict[tuple, list[_Ticket]] = {}
        for t in batch:
            if t.background:
                self.stats.count("dispatch.background")
            ctx = t.ctx
            if ctx is not None and ctx.expired():
                # queued time counted against the budget: drop BEFORE the
                # launch — the waiter maps this to 504 at the HTTP edge
                try:
                    ctx.check("dispatch batch window")
                except DeadlineExceeded as e:
                    t.future.set_exception(e)
                else:  # pragma: no cover — expired() implies check raises
                    t.future.set_exception(DeadlineExceeded(
                        "query deadline exceeded in dispatch batch window"))
                self.expired_drops += 1
                self.stats.count("dispatch.expired_drop")
                continue
            groups.setdefault(t.key, []).append(t)
        bound = batch_temp_bound()
        for key, tickets in groups.items():
            # foreground first, then pack under the ticket, fused-row,
            # and batch-temp caps; an over-cap ticket launches alone
            # (un-fused)
            tickets.sort(key=lambda t: t.background)
            pack: list[_Ticket] = []
            rows = 0
            temp = 0
            weight = None
            if key[0] == "wholequery" and len(tickets) > 1:
                weight = tickets[0].payload["runner"].row_temp_bytes(
                    key[1], key[2])
            for t in tickets:
                n = t.params.shape[0]
                w = t.temp_weight if weight is None else weight
                cost = n * w
                over_temp = pack and w > 0 and temp + cost > bound
                if over_temp:
                    # fusing this ticket would take the pack's
                    # temporaries past the batch-temp bound (they
                    # scale with the fused row count): split the
                    # pack, visibly
                    self.stats.count("dispatch.fused_temp_split")
                    self.mesh.temp_splits += 1
                if pack and (len(pack) >= self.max_batch
                             or rows + n > FUSED_ROWS_MAX
                             or over_temp):
                    self._launch(key[0], pack)
                    pack, rows, temp = [], 0, 0
                pack.append(t)
                rows += n
                temp += cost
            if pack:
                self._launch(key[0], pack)

    def _fail_all(self, tickets, exc):
        for t in tickets:
            if not t.future.done():
                t.future.set_exception(exc)

    def _note_wait(self, tickets) -> float:
        """Each ticket's wait for the batcher, taken once where its
        launch begins: ``dispatch.ticket_wait`` (a timing only — it
        crosses threads), the ``window_wait`` reservoir, and the
        profile's ``batcher.queue`` event under the stage the query was
        in when it submitted.  Returns the longest, the launch ledger's
        ``queueS``."""
        now = time.monotonic()
        longest = 0.0
        for t in tickets:
            wait = max(now - t.enq, 0.0)
            longest = max(longest, wait)
            self._round_stats.timing("dispatch.ticket_wait", wait)
            self.window_wait.observe(wait)
            if t.prof is not None:
                t.prof.event("batcher.queue", wait, node=t.prof_node,
                             kind=t.kind)
        return longest

    def _launch(self, kind, tickets, queue_s: float | None = None):
        """``queue_s`` is given where the tickets' wait was already taken
        (a fused launch that falls back to one launch a ticket)."""
        if queue_s is None:
            queue_s = self._note_wait(tickets)
        self.batch_size_hist.observe(len(tickets))
        if len(tickets) == 1:
            t = tickets[0]
            try:
                # the ticket's QueryContext rides into the direct path so
                # shard-slice deadline checks + failpoints behave exactly
                # as an un-batched call would; trace + profile context
                # re-attach so slice events/spans parent under the query;
                # the launch-ledger context carries the queued wait into
                # the device launches this ticket drives
                ltok = devobs.set_launch_ctx(
                    queue_s=queue_s, tickets=1, rows=t.params.shape[0])
                try:
                    with activate(t.ctx), GLOBAL_TRACER.attach(t.trace), \
                            qprof.activate(t.prof):
                        t0 = time.perf_counter()
                        result = self._direct(t)
                        if t.prof is not None:
                            t.prof.event("batcher.launch",
                                         time.perf_counter() - t0,
                                         node=t.prof_node, kind=t.kind,
                                         fused=False)
                finally:
                    devobs.reset_launch_ctx(ltok)
            except BaseException as e:
                t.future.set_exception(
                    e if isinstance(e, Exception)
                    else RuntimeError(repr(e)))
                return
            self.single_launches += 1
            self.stats.count("dispatch.launch.single")
            t.future.set_result(result)
            return
        self._launch_fused(kind, tickets, queue_s)

    def _direct(self, t):
        """A lone ticket's launch: the node over its own rows (B = 1
        for a single call), or the whole-query program."""
        p = t.payload
        if t.kind == "wholequery":
            return p["runner"].run(p["program"], p["mats"], p["holder"],
                                   p["index"], p["shards"])
        return self.mesh.reduce_async(
            p["node"], t.params, p["holder"], p["index"], p["shards"],
            scheduled=p["scheduled"])

    def _note_fused(self, tickets, dur_s, batch_rows=0, padded_rows=0):
        """Attribute one fused launch back to EVERY participating query:
        a profile event under each ticket's captured node and a
        synthesized span under each sampled trace (there is no single
        owner to nest a live span under) — so warm profiles of batched
        queries stop under-reporting device time.  Each ticket's event
        carries the fused batch size, its own row share, and its share
        of the pow-2 padding rows the launch computed for nobody."""
        pad_share = round(padded_rows / len(tickets), 2) if padded_rows \
            else 0
        for t in tickets:
            if t.prof is not None:
                t.prof.event("batcher.launch", dur_s, node=t.prof_node,
                             kind=t.kind, fused=True,
                             batchTickets=len(tickets),
                             batchRows=batch_rows,
                             ticketRows=t.params.shape[0],
                             paddedRowsShare=pad_share)
            if t.trace is not None and t.trace.sampled:
                GLOBAL_TRACER.record_span(
                    "dispatch.fused_launch", t.trace.trace_id,
                    t.trace.span_id, dur_s,
                    {"kind": t.kind, "tickets": len(tickets),
                     "batchRows": batch_rows,
                     "paddedRows": padded_rows},
                    collect=t.trace.collect)

    def _scatter(self, tickets, kinds, parts, spans, result):
        """Resolve every ticket of a fused launch with its rows of the
        outputs: ``parts[ni]`` are node ni's device arrays (kind
        ``kinds[ni]``) over the fused batch axis, ``spans[ti][ni]`` is
        ticket ti's (first row, rows) on it, and ``result`` makes a
        ticket's result of its parts (one list a node, the ticket's
        batch axis where the launch's was) and spans.  The one rule
        (docs/batching.md): reduced kinds are handed out as views of
        the launch's ONE shared fetch (parallel/fetch.py) — no jax
        call, so the collective-launch lock, which orders enqueues, is
        not held and this thread goes on to its next launch; kinds in
        ``PER_SHARD_KINDS`` (the shard axis first, the batch axis
        second) keep a device slice a ticket, an eager op under that
        lock."""
        with layer_span("dispatch.scatter", self._round_stats,
                        tickets=len(tickets)):
            reduced, first = [], []
            for kind, ps in zip(kinds, parts):
                first.append(len(reduced))
                if kind not in PER_SHARD_KINDS:
                    reduced.extend(ps)
            shared = SharedFetch(reduced) if reduced else None
            for t, span in zip(tickets, spans):
                mine = []
                for kind, ps, j0, (lo, b) in zip(kinds, parts, first, span):
                    if kind in PER_SHARD_KINDS:
                        with _DISPATCH_LOCK:
                            mine.append([a[:, lo:lo + b] for a in ps])
                    else:
                        mine.append([HostView(shared, j0 + k, lo, b)
                                     for k in range(len(ps))])
                t.future.set_result(result(mine, span))

    def _launch_fused_whole(self, tickets, queue_s):
        """Fuse same-shape whole-query programs: concatenate each
        node's params matrix along the batch axis and launch the shared
        compiled program ONCE; each ticket gets its rows of the outputs
        (``_scatter``).  Fusibility (batch-kind nodes only) was decided
        at ticket creation via the key."""
        from .wholequery import WholeQueryUnsupported
        p0 = tickets[0].payload
        runner = p0["runner"]
        program = p0["program"]
        t_launch0 = time.perf_counter()
        try:
            # no pre-schedule here: runner.run's precheck walks the
            # shard schedule exactly once; an over-budget working set
            # raises WholeQueryUnsupported into every waiter below and
            # the executors reroute to the per-stage streaming path
            node_mats = []
            spans = [[] for _ in tickets]
            for ni in range(len(program)):
                mats_n = [t.payload["mats"][ni] for t in tickets]
                lo = 0
                for span, m in zip(spans, mats_n):
                    span.append((lo, m.shape[0]))
                    lo += m.shape[0]
                node_mats.append(np.concatenate(mats_n)
                                 if len(mats_n) > 1 else mats_n[0])
            B = sum(m.shape[0] for m in node_mats)
            pad_total = sum(
                (1 << max(0, m.shape[0] - 1).bit_length()) - m.shape[0]
                for m in node_mats)
            # no FAULTS.hit here: runner.run gates the launch (one
            # mesh.slice hit per launch, matching the direct path)
            ltok = devobs.set_launch_ctx(queue_s=queue_s,
                                         tickets=len(tickets), rows=B)
            try:
                out = runner.run(program, node_mats, p0["holder"],
                                 p0["index"], p0["shards"])
            finally:
                devobs.reset_launch_ctx(ltok)
            self._note_fused(tickets, time.perf_counter() - t_launch0,
                             batch_rows=B, padded_rows=pad_total)
            self._scatter(tickets, [n.kind for n in program], out.parts,
                          spans, out.for_ticket)
        except BaseException as e:
            if isinstance(e, WholeQueryUnsupported) and \
                    e.node == "streamed-working-set":
                self.stream_fallbacks += 1
                self.stats.count("dispatch.launch.stream_fallback")
            self._fail_all(tickets, e if isinstance(e, Exception)
                           else RuntimeError(repr(e)))
            return
        self.fused_launches += 1
        self.stats.count("dispatch.launch.fused")
        self.stats.count("dispatch.fused_queries", len(tickets))

    def _launch_fused(self, kind, tickets, queue_s):
        if kind == "wholequery":
            return self._launch_fused_whole(tickets, queue_s)
        p0 = tickets[0].payload
        node, holder, index = p0["node"], p0["holder"], p0["index"]
        mesh = self.mesh
        t_launch0 = time.perf_counter()
        try:
            # PR1 composition: an over-budget working set streams in shard
            # slices — the fused single-slice path would stage it whole,
            # so stream each ticket through its direct path instead
            with layer_span("dispatch.place", devobs.LEDGER,
                            devices=mesh.n_devices):
                sched = mesh.shard_schedule(
                    holder, index, [node_keys(node)], p0["shards"])
            if len(sched.slices) > 1:
                self.stream_fallbacks += 1
                self.stats.count("dispatch.launch.stream_fallback")
                for t in tickets:
                    self._launch(kind, [t], queue_s)
                return
            mats = [t.params for t in tickets]
            mat = np.concatenate(mats) if len(mats) > 1 else mats[0]
            B = mat.shape[0]
            # pow-2 query axis bounds compile-cache churn
            mat = pad_pow2_rows(mat)
            # one failpoint/chaos gate per fused launch, matching the
            # per-slice gate of the direct path
            FAULTS.hit("mesh.slice", key=index)
            # launch ledger context: the queued wait and the ACTUAL fused
            # row count ride into the device launch so padding waste is
            # measured, not inferred (docs/observability.md)
            ltok = devobs.set_launch_ctx(queue_s=queue_s,
                                         tickets=len(tickets), rows=B)
            try:
                # the schedule is this launch's own: one slice, so the
                # launcher takes the shards as scheduled
                parts, groups = mesh.reduce_async(
                    node, mat, holder, index, p0["shards"], scheduled=True)
            finally:
                devobs.reset_launch_ctx(ltok)
            # attribute the launch BEFORE resolving any future: once a
            # future resolves, its owner thread may serialize the profile
            # tree, and late appends would race that (profile.py's
            # owner-blocked invariant)
            self._note_fused(tickets, time.perf_counter() - t_launch0,
                             batch_rows=B, padded_rows=mat.shape[0] - B)
            spans, lo = [], 0
            for t in tickets:
                spans.append([(lo, t.params.shape[0])])
                lo += t.params.shape[0]
            self._scatter(tickets, [kind], [parts], spans,
                          lambda mine, _span: (mine[0], groups))
        except BaseException as e:
            self._fail_all(tickets, e if isinstance(e, Exception)
                           else RuntimeError(repr(e)))
            return
        self.fused_launches += 1
        self.stats.count("dispatch.launch.fused")
        self.stats.count("dispatch.fused_queries", len(tickets))

    # -- observability ------------------------------------------------------

    def snapshot(self) -> dict:
        return {
            "enabled": self.enabled,
            "maxBatch": self.max_batch,
            "windowUs": round(self.window_s * 1e6, 1),
            "queued": self.pending(),
            "fusedLaunches": self.fused_launches,
            "singleLaunches": self.single_launches,
            "streamFallbacks": self.stream_fallbacks,
            "expiredDrops": self.expired_drops,
            "batchSize": self.batch_size_hist.snapshot(),
            "windowWaitS": self.window_wait.snapshot(),
        }

    def prometheus_text(self) -> str:
        lines = self.batch_size_hist.prometheus_lines(
            "pilosa_tpu_dispatch_batch_size")
        ws = self.window_wait.snapshot()
        lines.append("# TYPE pilosa_tpu_dispatch_window_wait_seconds "
                     "summary")
        for q, v in (("0.5", ws["p50"]), ("0.99", ws["p99"])):
            if v is not None:
                lines.append(
                    f'pilosa_tpu_dispatch_window_wait_seconds'
                    f'{{quantile="{q}"}} {v:.6g}')
        lines.append("pilosa_tpu_dispatch_window_wait_seconds_count "
                     f"{ws['count']}")
        return "\n".join(lines) + "\n"
