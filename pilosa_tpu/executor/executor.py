"""Executor: recursive PQL call dispatch over shards (executor.go:44-339).

The reference fans per-shard work out to a goroutine pool and reduces
streamed results (executor.go:2455 mapReduce).  Here each shard's bitmap
work is one cached XLA computation (see plan.py); shards are dispatched
asynchronously (jax queues them) and reduced on host.  Aggregations ship
only scalars/count-vectors back from the device.
"""

from __future__ import annotations

from datetime import datetime
from typing import Any

import numpy as np

from ..core import SHARD_WIDTH, SHARD_WORDS, VIEW_STANDARD
from ..ops import bitset, bsi
from ..pql import Call, parse
from ..storage.field import FIELD_TYPE_INT, FIELD_TYPE_BOOL
from ..storage import time_quantum as tq
from .plan import PlanCompiler, ReduceNode, Resolver, parametrize
# (after .plan: parallel/ imports it, and this package through it)
from ..parallel.fetch import fetch_parts
from ..parallel.nodes import (
    ROW_BYTES, TOPN_EXTRA, batch_temp_bound, node_keys, node_temp_rows,
    pad_pow2_rows, pow2_rows, topn_extra, with_n,
)
from .results import (
    FieldRow, GroupCount, Pair, RowIdentifiers, RowResult, ValCount,
    acc_counts, rank_counts, sort_pairs,
)

BITMAP_CALLS = {"Row", "Range", "Intersect", "Union", "Difference", "Xor",
                "Not", "Shift"}
WRITE_CALLS = {"Set", "Clear", "ClearRow", "Store", "SetRowAttrs",
               "SetColumnAttrs"}


class ExecutionError(ValueError):
    pass


# TopN args that the batched/prepared fast paths cannot express — queries
# carrying any of them take the per-call path (and the cluster finalizes
# them globally at the coordinator).
TOPN_EXTRAS = ("tanimotoThreshold", "attrName", "attrValues")


def topn_extras(c: Call):
    """(tanimotoThreshold, attrName, attrValues) with the reference's
    argument validation (executor.go:930-960).  Shared by the local
    executor and the cluster fan-out (which must finalize these globally —
    per-node tanimoto would diverge from single-node answers)."""
    tan_thresh = c.args.get("tanimotoThreshold")
    attr_name = c.args.get("attrName")
    attr_values = c.args.get("attrValues")
    if attr_name is not None and attr_values is None:
        raise ExecutionError("TopN(attrName=...) requires attrValues")
    if attr_values is not None and attr_name is None:
        raise ExecutionError("TopN(attrValues=...) requires attrName")
    if tan_thresh is not None:
        if not isinstance(tan_thresh, int) or isinstance(tan_thresh, bool) \
                or not 0 < tan_thresh <= 100:
            raise ExecutionError(
                "tanimotoThreshold must be an integer in (0, 100]")
        if not c.children:
            raise ExecutionError("tanimotoThreshold requires a source row")
    return tan_thresh, attr_name, attr_values


class _PendingGroup:
    """One pending filling MANY result slots: a batched call group's B
    results resolve with ONE vectorized ``fin`` instead of B per-call
    closures (measurably cheaper at B≥1024 on the serving hot path).
    Place the same instance at every slot in ``call_idxs``; ``fin(hp)``
    returns an indexable of per-slot values."""

    __slots__ = ("parts", "pos", "fin", "_vec")

    def __init__(self, parts, call_idxs, fin):
        self.parts = list(parts)
        self.pos = {i: b for b, i in enumerate(call_idxs)}
        self.fin = fin
        self._vec = None

    @classmethod
    def counts(cls, parts, call_idxs):
        """Group of B Counts: per-group [B] vectors summed in one numpy
        op (shared by the grouped executor and the prepared cache)."""
        nB = len(call_idxs)
        return cls(parts, call_idxs,
                   lambda hp: (np.sum(hp, axis=0).tolist()
                               if hp else [0] * nB))


# Per-stage batches are dispatched in chunks whose temporaries stay
# under the batch-temp bound (parallel/nodes.py: ``node_temp_rows`` a
# batch row, held against ``batch_temp_bound``), every chunk padded up
# to a power of two (repeating its last row — always in-range) so
# arbitrary client batch sizes reuse a bounded set of compiled
# executables.  BATCH_CHUNK_MAX was sized when one compile cost 20-40 s
# on a remote device that no longer exists; it awaits re-derivation on
# the chip (ROADMAP.md S9).
BATCH_CHUNK_MAX = 32768


def batch_chunk_size(rows: int, n_shards: int) -> int:
    """Pow-2 batch-axis chunk size whose temporaries, at ``rows``
    (``node_temp_rows``) a batch row over ``n_shards`` stacked shards a
    device, fit the bound; one row where not even one fits (a launch
    that cannot shrink further still runs)."""
    chunk = batch_temp_bound() // (max(1, rows) * n_shards * ROW_BYTES)
    chunk = max(1, min(BATCH_CHUNK_MAX, chunk))
    return 1 << (chunk.bit_length() - 1)


def _batch_chunks(params_mat: np.ndarray, n_shards: int, rows: int = 0):
    """Yield (lo, n, padded_params) covering params_mat[lo:lo+n]; padded
    rows beyond n are duplicates whose results the caller ignores.
    ``n_shards`` is the per-device stacked-shard count — temporaries
    live per device, so the bound divides by the mesh size, not the
    total shard count.  ``n_shards <= 0`` marks a filter-less group whose
    device pass is a B-independent broadcast: it dispatches as ONE chunk
    regardless of B (splitting would repeat the full fragment pass per
    chunk — r5 advisor, the old path still cut at BATCH_CHUNK_MAX).
    ``rows``: the group's ``node_temp_rows``; one gather temp a params
    slot where none is given."""
    B, P = params_mat.shape
    if n_shards <= 0:
        chunk = max(1, B)
    else:
        chunk = batch_chunk_size(max(1, P, rows), n_shards)
    for lo in range(0, B, chunk):
        sub = params_mat[lo: lo + chunk]
        yield lo, sub.shape[0], pad_pow2_rows(sub)


def _run_batched_groups(batcher, holder, index, shards, groups, results):
    """Dispatch batched call groups chunk-wise and fill ``results``.

    ``groups``: iterable of (node, params_mat, call_idxs, extra) — the
    group's reducer node (count, bsi_sum or row_counts), its [B, P]
    params matrix, the calls its rows answer, and what its finisher
    needs beside the parts: bsi_sum ``base``, row_counts ``ids_n`` with
    one (ids, n) pair per call (a top-n node's matrix carries n as its
    last column besides).  Shared by the classic grouped path and
    the prepared-statement cache so the chunking policy lives in exactly
    one place.

    Dispatch flows through the cross-query batcher
    (parallel/batcher.py): on the common single-slice schedule each
    chunk becomes a ticket, so concurrent queries replaying the same
    prepared template fuse into one device launch.

    Dispatch order is SLICE-MAJOR over one residency-aware shard schedule
    covering the whole batch: every group's every chunk runs against a
    shard slice before the budget rotates to the next slice.  Chunk-major
    order re-staged the full over-budget working set once per chunk;
    slice-major pays the rotation once for the entire batch, with the
    next slice prefetching while the current one computes.  When the
    working set fits the budget the schedule is a single slice and this
    is exactly the old dispatch."""
    groups = list(groups)
    if not groups:
        return
    mesh = batcher.mesh

    key_lists: list = []
    for node, _pm, _ci, _extra in groups:
        kl = node_keys(node)
        if kl not in key_lists:
            key_lists.append(kl)
    sched = mesh.shard_schedule(holder, index, key_lists, shards)
    # chunk layout must be identical across slices so per-chunk parts can
    # accumulate; size by the largest slice (conservative for the rest)
    per_dev = mesh.stacked_per_device(sched.max_slice_len)
    # multi-slice (over-budget) schedules keep the direct slice-major
    # dispatch; batching a streamed working set would re-stage it whole
    fuse = len(sched.slices) == 1

    def _chunks(node, params_mat):
        # count plans always gather per-row temps; bsi_sum/row_counts
        # without a filter broadcast one pass — single chunk (see
        # _batch_chunks)
        if node.kind != "count" and node.plan is None:
            return _batch_chunks(params_mat, 0)
        rows = 0
        if node.kind != "count":
            from ..parallel.mesh_exec import field_rows
            rows = field_rows(holder, index, *node.primary)
        return _batch_chunks(
            params_mat, per_dev,
            node_temp_rows(node.kind, node.plan, params_mat.shape[1],
                           rows))

    # chunk layouts computed ONCE; on the multi-slice direct path the
    # padded params also go to device once (slice-major iteration would
    # otherwise repeat the concatenate padding and the host->device
    # params transfer per slice on identical data) — fused tickets stay
    # host-side so the batcher can concatenate them across queries
    import jax.numpy as jnp
    group_chunks = [
        [(lo, n_c, sub if fuse else jnp.asarray(sub))
         for lo, n_c, sub in _chunks(node, params_mat)]
        for node, params_mat, _ci, _extra in groups]
    # the batch axis split to honor the bound: visible, not silent
    # (docs/observability.md — `query.batch_temp_splits`, and
    # `batchTemp.splits` with the batcher's and the block walks')
    n_splits = sum(len(ch) - 1 for ch in group_chunks if len(ch) > 1)
    if n_splits:
        batcher.stats.count("query.batch_temp_splits", n_splits)
        mesh.temp_splits += n_splits

    parts_acc: dict[tuple[int, int], list] = {}
    for shard_slice in sched:
        for gi, (node, _pm, _ci, _extra) in enumerate(groups):
            for lo, _n, sub in group_chunks[gi]:
                # the slice is this schedule's: not to be scheduled again
                parts, _ = batcher.reduce(node, sub, holder, index,
                                          shard_slice, scheduled=True,
                                          fuse=fuse)
                parts_acc.setdefault((gi, lo), []).extend(parts)

    # all parts dispatched; build the pendings (finalizers sum/merge the
    # per-slice parts exactly as they merge per-shape-group parts —
    # every reduction here is additive over shards)
    for gi, (node, _pm, call_idxs, extra) in enumerate(groups):
        for lo, n_c, _sub in group_chunks[gi]:
            _wire_group(node, parts_acc.get((gi, lo), []),
                        call_idxs[lo: lo + n_c], extra, lo, mesh, results)


def _wire_group(node, parts, call_idxs, extra, lo, mesh, results,
                walked: bool = False):
    """Pendings of one batched call group (or one chunk of it, whose
    first row is call ``lo`` of the group) over its fetched ``parts``,
    whichever way they were launched; ``walked``: a top-n node's launch
    answered by ``nodes.topn_walk`` (its meta says so)."""
    if node.kind == "count":
        grp = _PendingGroup.counts(parts, call_idxs)
        for i in call_idxs:
            results[i] = grp
    elif node.kind == "bsi_sum":
        for b, i in enumerate(call_idxs):
            results[i] = _Pending(
                parts, lambda hp, b=b, base=extra["base"]:
                _sum_fin(hp, b, base))
    else:   # row_counts
        for b, i in enumerate(call_idxs):
            ids, n = extra["ids_n"][lo + b]
            results[i] = _Pending(
                parts, lambda hp, b=b, ids=ids, n=n:
                rank_counts(_topn_counts(
                    mesh, hp, b, node.extra == TOPN_EXTRA, walked),
                    n or None, ids))


class _Pending:
    """A dispatched-but-unresolved call result.

    Mesh-path aggregations return these so a multi-call query dispatches
    ALL device work before the first host block (the reference overlaps
    calls via its worker pool, executor.go:80-110).  ``parts`` are the
    call's unfetched device arrays; ``fin`` maps their host copies to the
    final result.  ``execute`` fetches every pending's parts in ONE
    device->host transfer (concatenated), because each separate fetch is a
    full dispatch round trip.  What a round trip costs on the chip has
    not been measured (ROADMAP.md S9)."""

    __slots__ = ("parts", "fin")

    def __init__(self, parts, fin):
        self.parts = list(parts)
        self.fin = fin


def _resolve_pendings(results):
    """Resolve all _Pending results with a single device->host fetch
    (``fetch.fetch_parts``).  Parts shared between pendings (batched
    call groups) fetch once; a part that is a view of a fused launch's
    shared fetch becomes its rows of the host copy here, so a finalizer
    sees its ticket's batch axis leading whichever way it was served."""
    unique: dict[int, Any] = {}
    for r in results:
        if isinstance(r, (_Pending, _PendingGroup)):
            for p in r.parts:
                unique.setdefault(id(p), p)
    host = dict(zip(unique, fetch_parts(list(unique.values()))))
    out = []
    for i, r in enumerate(results):
        if isinstance(r, _Pending):
            out.append(r.fin([host[id(p)] for p in r.parts]))
        elif isinstance(r, _PendingGroup):
            if r._vec is None:
                r._vec = r.fin([host[id(p)] for p in r.parts])
            out.append(r._vec[r.pos[i]])
        else:
            out.append(r)
    return out


# -- host finalizers -------------------------------------------------------
# Applied to the fetched device parts of a reducer node, whichever way it
# was launched: every part has the batch axis leading (segments: after
# the shard axis), a whole-query launch returns one part a node (one a
# shape group for segments and bsi_minmax), a per-stage launch one a
# shape group and shard slice.

def _sum_fin(hp, b, base):
    total, cnt = 0, 0
    for p in hp:
        s, c_ = bsi.weighted_sum(np.asarray(p[b]))
        total += s
        cnt += c_
    return ValCount(total + cnt * base, cnt)


def _topn_counts(mesh, hp, b, topn: bool, walked: bool):
    """Row counts of params row ``b`` of a row_counts node from its
    fetched parts.  A walked launch (``nodes.topn_walk``) has one part,
    [B, R + 1]: the counts — exact for the rows visited, 0 for those
    that cannot reach the top n — and the rows visited.  Counts a top-n
    question in ``topnPrune.*`` either way: the walk by how far it
    went, the full pass as a full scan."""
    if walked:
        row = np.asarray(hp[0][b])
        mesh.topn_queries += 1
        mesh.topn_rows_visited += int(row[-1])
        mesh.topn_rows_stacked += row.size - 1
        return row[:-1].astype(np.int64)
    if topn:
        mesh.topn_full_scans += 1
    return mesh.merge_counts([p[b] for p in hp])


def _segments(hp, b, groups, shards) -> dict:
    """{shard: host [W] words} of params row ``b``: ``groups`` are the
    shard lists of the parts ``hp``, in order; a shard of ``shards`` in
    none of them holds no fragment and reads empty."""
    segs: dict[int, np.ndarray] = {}
    for shard_list, arr in zip(groups, hp):
        flat = bitset.from_tile(arr)        # [S, B, W]: a host view
        for i, shard in enumerate(shard_list):
            segs[shard] = flat[i, b]
    zero = np.zeros(SHARD_WORDS, dtype=np.uint32)
    for shard in shards:
        segs.setdefault(shard, zero)
    return segs


def _minmax_fin(hp, groups, base, want_max):
    acc = ValCount()
    j = 0
    for shard_list in groups:
        bits, neg, cnt = hp[j], hp[j + 1], hp[j + 2]
        j += 3
        for i in range(len(shard_list)):
            val, c = bsi.reconstruct_min_max(
                np.asarray(bits[i]), int(neg[i]), int(cnt[i]))
            vc = ValCount(val + base if c else 0, c)
            acc = acc.larger(vc) if want_max else acc.smaller(vc)
    return acc


def _minrow_fin(hp, want_max):
    counts = np.asarray(hp[0][0], dtype=np.int64) if hp \
        else np.zeros(0, dtype=np.int64)
    nz = np.nonzero(counts)[0]
    if nz.size == 0:
        return ValCount(0, 0)
    rid = int(nz[-1] if want_max else nz[0])
    return ValCount(rid, int(counts[rid]))


def _rows_fin(hp, limit, previous):
    row_ids: set[int] = set()
    for p in hp:
        row_ids.update(int(i) for i in np.nonzero(np.asarray(p[0]))[0])
    out = sorted(row_ids)
    if previous is not None:
        out = [r for r in out if r > previous]
    if limit is not None:
        out = out[:limit]
    return RowIdentifiers(rows=out)


def _group_rows(hp, combos, last_ids, last_field) -> list[GroupCount]:
    """The non-empty groups of the prefix ``combos`` of one group_counts
    launch, from its fetched [C, rows] count parts."""
    acc = None
    for p in hp:
        a = np.asarray(p, dtype=np.int64)
        acc = a.copy() if acc is None else acc_counts(acc, a)
    out: list[GroupCount] = []
    for ci, combo in enumerate(combos):
        for rid in last_ids:
            cnt = (int(acc[ci, rid]) if acc is not None
                   and rid < acc.shape[1] else 0)
            if cnt > 0:
                group = [FieldRow(fn, ri) for fn, ri in combo]
                group.append(FieldRow(last_field, rid))
                out.append(GroupCount(group, cnt))
    return out


def _group_page(out: list[GroupCount], prev_ids, limit):
    """GroupBy's answer from its groups: ordered, resumed strictly
    after ``prev_ids``, cut to ``limit``."""
    out.sort(key=lambda g: tuple(
        (fr.field, fr.row_id) for fr in g.group))
    if prev_ids is not None:
        out = [g for g in out
               if tuple(fr.row_id for fr in g.group) > prev_ids]
    if limit is not None:
        out = out[:limit]
    return out


class Executor:
    def __init__(self, holder, mesh=None, use_mesh: bool | None = None,
                 stats=None, dispatch_batch: bool = True,
                 dispatch_batch_max: int = 32,
                 dispatch_batch_window_us: float = 200.0,
                 whole_query: bool = True,
                 whole_query_fallback: str = "legacy"):
        """``mesh``: a jax Mesh to execute shard batches on (stacked
        shard_map execution with ICI reductions, parallel/mesh_exec.py).
        When None, per-shard dispatch is used.  ``use_mesh=True`` with no
        mesh builds one over all local devices.  ``stats``: a StatsClient
        for per-phase timings (parse/translate/dispatch/fetch) and cache
        counters, surfaced at /debug/vars (the instrumentation sites of
        executor.go:295-336).  ``dispatch_batch*``: cross-query dynamic
        batching of device dispatch (parallel/batcher.py,
        docs/batching.md) — with it off, the batcher still fronts every
        mesh dispatch but delegates directly.  ``whole_query``: compile
        each read request into ONE pjit program over the mesh
        (parallel/wholequery.py, docs/whole-query.md); off, every call
        (or batched call group) is lowered to the same reducer node and
        launched per stage, one launch a node
        (``MeshExecutor.reduce_async`` through ``batcher.reduce``): the
        two paths share their node bodies (parallel/nodes.py).
        ``whole_query_fallback``:
        "legacy" reroutes unsupported shapes to the per-stage path
        (counted + logged); "error" raises instead — a debugging mode
        that makes every silent slow path loud."""
        self.holder = holder
        self.compiler = PlanCompiler()
        from ..utils.stats import NopStatsClient
        self.stats = stats if stats is not None else NopStatsClient()
        from .translator import Translator
        self.translator = Translator(holder)
        # Generation-keyed result cache (cache/results.py).  Disabled on
        # bare executors (limit 0) so tests and chaos harnesses exercise
        # the real execution path; the server wires ``result-cache-mb``
        # through, and the cluster layer reuses this same instance for
        # coordinator-scope entries (one shared byte budget).
        from ..cache.results import ResultCache
        self.result_cache = ResultCache(stats=self.stats)
        self.mesh_exec = None
        self.batcher = None
        self.prepared = None
        self.wholequery = None
        self.whole_query = bool(whole_query)
        self.whole_query_fallback = whole_query_fallback
        # Server injects its Logger so wholequery.fallback events land in
        # the server log; None (engine/bench standalone) stays silent.
        self.logger = None
        # Warm-start corpus recorder (warmup/corpus.py), injected by the
        # Server like the logger; None (bare executors) records nothing.
        self.warm_recorder = None
        self.wq_requests = 0
        self.wq_fallbacks = 0
        self.wq_last_fallback = ""
        if mesh is not None or use_mesh:
            from ..parallel.batcher import DispatchBatcher
            from ..parallel.mesh_exec import MeshExecutor
            from ..parallel.wholequery import WholeQueryRunner
            from .prepared import PreparedCache
            self.mesh_exec = MeshExecutor(mesh)
            self.batcher = DispatchBatcher(
                self.mesh_exec, enabled=dispatch_batch,
                max_batch=dispatch_batch_max,
                window_us=dispatch_batch_window_us, stats=self.stats)
            self.prepared = PreparedCache(self)
            # multiprocess meshes are statically outside the program's
            # vocabulary — gating here (like the batcher's _use_ticket)
            # keeps them off the per-request exception/fallback-log path
            if not self.mesh_exec.multiprocess:
                self.wholequery = WholeQueryRunner(self.mesh_exec)

    def close(self):
        if self.batcher is not None:
            self.batcher.close()
        if self.mesh_exec is not None:
            self.mesh_exec.close()

    # -- entry point (executor.go:113 Execute) -----------------------------

    def execute(self, index_name: str, query, shards=None,
                translate: bool = True, ctx=None) -> list[Any]:
        """``translate=False`` for internal (already-translated) requests —
        the reference's opt.Remote skipping translateCalls
        (executor.go:147).

        ``ctx``: optional QueryContext (utils/deadline.py).  Defaults to
        the caller's active context; installed as current for the whole
        execution so the mesh shard-slice loops can abort an expired
        query between slices, and checked here between per-call
        dispatches and before the blocking fetch."""
        from ..utils.deadline import activate, check_current, current
        if ctx is None:
            ctx = current()
        with activate(ctx):
            return self._execute_ctx(index_name, query, shards, translate,
                                     check_current)

    def _execute_ctx(self, index_name: str, query, shards, translate,
                     check_current) -> list[Any]:
        from ..utils import profile as qprof
        from ..utils.tracing import GLOBAL_TRACER
        check_current("execute")
        # one span per execution so a remote node's piggybacked trace
        # carries its execution stage (docs/observability.md)
        with GLOBAL_TRACER.span("executor.execute") as espan:
            espan.set_tag("index", index_name)
            return self._execute_stages(index_name, query, shards,
                                        translate, check_current, qprof)

    def _execute_stages(self, index_name: str, query, shards, translate,
                        check_current, qprof) -> list[Any]:
        from ..utils import degraded
        from ..utils import tenant as qtenant
        from ..utils.tracing import layer_span
        stats = self.stats
        # warm-start corpus (warmup/corpus.py) records by query TEXT —
        # the only replayable identity across restarts
        qtext = query if isinstance(query, str) else None
        # Result-cache lookup FIRST (before even the parse): node-local
        # entries key on the query text (an AST keys on its normalized
        # repr), the pinned shard set, and the index's fragment
        # generation vector — any mutation bumps a gen and the key stops
        # matching (cache/results.py).
        qkey = ckey = None
        cache = self.result_cache
        if cache is not None and cache.limit_bytes > 0:
            idx0 = self.holder.index(index_name)
            if idx0 is not None:
                if shards is None:
                    shards = sorted(idx0.available_shards())
                from ..core import attr_epoch, schema_epoch
                from ..cache.results import gen_vector
                from ..utils.tracing import GLOBAL_TRACER
                qrepr = query if isinstance(query, str) else repr(query)
                qkey = ("local", index_name, qrepr, tuple(shards),
                        bool(translate))
                ckey = qkey + (gen_vector(self.holder, index_name,
                                          set(shards)),
                               schema_epoch(), attr_epoch())
                with GLOBAL_TRACER.span("resultcache.lookup") as span, \
                        qprof.stage("resultcache.lookup") as pnode:
                    out = cache.lookup(ckey)
                    outcome = "hit" if out is not None else "miss"
                    span.set_tag("outcome", outcome)
                    if pnode is not None:
                        pnode.tags["outcome"] = outcome
                from ..utils import explain as qexplain
                qexplain.note("caches", {
                    "cache": "result", "scope": "local",
                    "outcome": outcome,
                    # the key COMPONENTS, not the raw key: what would
                    # have to change for this entry to stop matching
                    "key": {"index": index_name, "shards": len(shards),
                            "genVector": hash(ckey[5]) & 0xFFFFFFFF,
                            "schemaEpoch": ckey[6],
                            "attrEpoch": ckey[7]}})
                if out is not None:
                    # result-cache entries exist only for read-only
                    # queries (the fill sites gate on it)
                    self._warm_note(index_name, qtext)
                    return out
        if isinstance(query, str):
            if translate and self.prepared is not None:
                with stats.timer("query.prepared"), \
                        qprof.stage("prepared") as pnode:
                    hit, out = self.prepared.attempt(index_name, query,
                                                     shards)
                    if pnode is not None:
                        pnode.tags["outcome"] = "hit" if hit else "miss"
                if hit:
                    stats.count("query.prepared.hit")
                    from ..utils import explain as qexplain
                    # the replay's launch already noted its wholequery
                    # program (or fell back inside the template); this
                    # entry records that the PREPARED cache drove it
                    qexplain.note("plan", {"mode": "prepared",
                                           "shards": len(shards or ())})
                    if ckey is not None and not degraded.is_degraded():
                        # prepared entries exist only for Count/Sum/TopN
                        # templates — read-only by construction; a
                        # quarantined-degraded answer stays uncached
                        cache.fill(qkey, ckey, out,
                                   tenant=qtenant.current_or_none())
                    self._warm_note(index_name, qtext)
                    return out
                stats.count("query.prepared.miss")
                if out is not None:
                    query = out  # parsed (tagged) AST — don't parse twice
        # query.plan off the prepared path: parse and translate (the
        # lowering sits with its launch, inside query.dispatch)
        with layer_span("query.plan", stats):
            if isinstance(query, str):
                with stats.timer("query.parse"), qprof.stage("parse"):
                    query = parse(query)
            idx = self.holder.index(index_name)
            if idx is None:
                raise ExecutionError(f"index not found: {index_name}")
            if translate:
                # always runs: validates stray string keys even when no
                # store is enabled (executor.go:2658 "string 'col' value
                # not allowed...")
                with stats.timer("query.translate"), \
                        qprof.stage("translate"):
                    query = self.translator.translate_query(index_name,
                                                            query)
            if shards is None:
                shards = sorted(idx.available_shards())
        # Batched grouping reorders dispatch, which is only sound when no
        # call mutates state a later call could read — mixed write/read
        # queries run strictly sequentially like the reference.
        with stats.timer("query.dispatch"), \
                qprof.stage("dispatch") as dnode:
            if dnode is not None:
                # device-budget counters bracketing the dispatch: the
                # deltas attribute upload/eviction traffic to THIS query
                # (approximate under concurrency — they are process-wide)
                from ..storage.membudget import DEFAULT_BUDGET
                up0, ev0 = (DEFAULT_BUDGET.upload_bytes,
                            DEFAULT_BUDGET.evictions)
                dnode.tags["calls"] = len(query.calls)
                dnode.tags["shards"] = len(shards)
            read_only = not any(c.name in WRITE_CALLS
                                for c in query.calls)
            results = None
            if self.wholequery is not None and self.whole_query and \
                    read_only:
                # whole-query path (docs/whole-query.md): the entire
                # request compiles to ONE pjit program over the mesh;
                # unsupported shapes fall back below, counted
                results = self._try_whole_query(index_name, query.calls,
                                                shards)
            if results is not None:
                pass
            elif self.mesh_exec is not None and len(query.calls) > 1 and \
                    read_only:
                from ..utils import explain as qexplain
                qexplain.note("plan", {"mode": "legacy-grouped",
                                       "calls": len(query.calls),
                                       "shards": len(shards)})
                results = self._execute_calls_grouped(index_name,
                                                      query.calls, shards)
            else:
                from ..utils import explain as qexplain
                qexplain.note("plan", {"mode": "legacy-per-call",
                                       "calls": len(query.calls),
                                       "readOnly": read_only,
                                       "shards": len(shards)})
                results = []
                for c in query.calls:
                    check_current("call dispatch")
                    results.append(self._execute_call(index_name, c,
                                                      shards))
            if dnode is not None:
                dnode.tags["uploadBytes"] = \
                    DEFAULT_BUDGET.upload_bytes - up0
                dnode.tags["evictions"] = DEFAULT_BUDGET.evictions - ev0
        check_current("result fetch")
        with layer_span("query.fetch", stats), qprof.stage("fetch"):
            results = _resolve_pendings(results)
        if translate and self.translator.needs_translation(index_name):
            results = self.translator.translate_results(
                index_name, query.calls, results)
        if ckey is not None and not degraded.is_degraded():
            # degraded answers (quarantined fragments serving empty rows,
            # or shards lost under partialResults) are never memoized: a
            # healthy repeat must recompute
            from ..cache.results import query_is_readonly
            if query_is_readonly(query):
                cache.fill(qkey, ckey, results,
                           tenant=qtenant.current_or_none())
        if read_only:
            self._warm_note(index_name, qtext)
        return results

    def _warm_note(self, index_name: str, qtext):
        """Feed one successfully served read-only string query to the
        warm-start corpus recorder (no-op on bare executors)."""
        rec = self.warm_recorder
        if rec is not None and qtext is not None:
            rec.note(index_name, qtext)

    # -- batched multi-call execution --------------------------------------

    _EMPTY_PARAMS = np.zeros(0, dtype=np.int32)

    # GroupBy row-id grid bounds: total combos cap the int32 count fetch
    # (total x 4 bytes device->host), prefix combos cap the dispatched
    # grid (chunked GROUP_CHUNK per executable invocation).  Both values
    # were sized for a ~5 MB/s remote-device link that no longer exists
    # and await re-derivation on the chip (ROADMAP.md S9).
    GROUP_GRID_MAX = 1 << 20
    GROUP_GRID_PREFIX_MAX = 16384

    def _node(self, kind: str, plan, primary=(), extra=()):
        """(reducer node, its params row [P]) of one call: ``plan`` is
        the resolved bitmap plan (count, segments) or the optional
        filter plan of a field reducer, slotted here."""
        slotted, params = (None, self._EMPTY_PARAMS) if plan is None \
            else parametrize(plan)
        return ReduceNode(kind, slotted, primary, extra), params

    def _batch_desc(self, index: str, c: Call):
        """{node, params, finisher facts} for calls that can batch into
        one vmapped launch with per-call params rows (same-node calls
        group); None for everything else."""
        if c.name == "Count" and len(c.children) == 1:
            node, params = self._node(
                "count", self._resolve(index, c.children[0]))
            return {"node": node, "params": params}
        if c.name == "Sum":
            f = self._bsi_field(index, c)
            node, params = self._node(
                "bsi_sum", self._filter_plan(index, c),
                (f.name, f.bsi_view_name()))
            return {"node": node, "params": params,
                    "base": f.options.base}
        if c.name == "TopN":
            if any(k in c.args for k in TOPN_EXTRAS):
                return None  # extras need extra passes: per-call path
            field_name, ok = c.string_arg("_field")
            if not ok or self.holder.field(index, field_name) is None:
                return None  # per-call path raises the proper error
            n, _ = c.uint_arg("n")
            ids = c.args.get("ids")
            plan = self._filter_plan(index, c)
            node, params = self._node(
                "row_counts", plan, (field_name, VIEW_STANDARD),
                topn_extra(plan, n, ids))
            if node.extra:
                params = with_n(params, n)
            return {"node": node, "params": params, "ids": ids, "n": n}
        return None

    def _execute_calls_grouped(self, index: str, calls, shards):
        """Group same-shape Count/TopN/Sum calls and execute each group as
        ONE device computation over stacked params — the worker-pool
        equivalent for a multi-call query (executor.go:80-110), minus N-1
        dispatch round trips."""
        descs: list = [None] * len(calls)
        groups: dict[str, list[int]] = {}
        for i, c in enumerate(calls):
            d = self._batch_desc(index, c)
            if d is not None:
                descs[i] = d
                groups.setdefault(repr(d["node"]), []).append(i)

        results: list = [None] * len(calls)
        batched: set[int] = set()
        to_run = []
        for idxs in groups.values():
            if len(idxs) < 2:
                continue
            ds = [descs[i] for i in idxs]
            node = ds[0]["node"]
            params_mat = np.stack([d["params"] for d in ds])
            if node.kind == "bsi_sum":
                extra = {"base": ds[0]["base"]}
            elif node.kind == "row_counts":
                extra = {"ids_n": [(d["ids"], d["n"]) for d in ds]}
            else:
                extra = None
            to_run.append((node, params_mat, idxs, extra))
            batched.update(idxs)
        # ONE invocation for every group: they share one residency-aware
        # shard schedule, so under budget pressure the whole multi-group
        # batch drains against each shard slice before the budget rotates
        _run_batched_groups(self.batcher, self.holder, index, shards,
                            to_run, results)

        for i, c in enumerate(calls):
            if i not in batched:
                results[i] = self._execute_call(index, c, shards)
        return results

    # -- whole-query pjit programs (docs/whole-query.md) -------------------
    # A read request lowers to a tuple of plan.ReduceNode reducers plus
    # one params matrix per node, and the WHOLE request launches as one
    # compiled program over the mesh (parallel/wholequery.py).  Shapes
    # the program cannot express raise WholeQueryUnsupported and the
    # request reroutes to the per-stage dispatch with
    # ``wholequery.fallback`` counted and a structured log event naming
    # the unsupported node — no silent slow paths.

    def _try_whole_query(self, index: str, calls, shards):
        from ..parallel.wholequery import WholeQueryUnsupported
        try:
            results = self._wq_execute(index, calls, shards)
        except WholeQueryUnsupported as e:
            self._note_wq_fallback(index, e)
            return None
        self.wq_requests += 1
        self.stats.count("wholequery.requests")
        return results

    def _note_wq_fallback(self, index: str, e):
        self.wq_fallbacks += 1
        self.wq_last_fallback = e.node if not e.detail \
            else f"{e.node}: {e.detail}"
        self.stats.count("wholequery.fallback")
        from ..utils import events, explain as qexplain
        events.emit("wholequery.fallback", index=index, node=e.node,
                    detail=e.detail or None)
        qexplain.note("plan", {"mode": "legacy-fallback", "node": e.node,
                               "detail": e.detail or None})
        log = self.logger
        if log is not None:
            try:
                log.event("wholequery.fallback", index=index, node=e.node,
                          detail=e.detail)
            # lint: allow(swallowed-exception) — a stale/closed log
            # stream costs a log line, never the query; the fallback is
            # still counted in the stats above
            except Exception:
                pass
        if self.whole_query_fallback == "error":
            raise ExecutionError(
                f"whole-query fallback disabled by the 'error' policy: "
                f"{e.node}"
                + (f": {e.detail}" if e.detail else "")) from e

    def _wq_dispatch(self, index: str, shards, program, mats):
        """One program launch through the dispatch batcher (concurrent
        same-shape requests fuse along the params batch axis).  The
        launch holds the compiler's figure for the program's
        temporaries against the batch-temp bound and walks the device's
        shards in blocks where the whole would not fit; only a batch
        whose temporaries do not fit over ONE stacked shard raises
        ``batch-chunks`` and stays on the per-stage chunked path, which
        cuts the batch axis."""
        return self.batcher.whole_query(self.wholequery, program, mats,
                                        self.holder, index, shards)

    def _wq_run_batched(self, index: str, shards, groups, results):
        """Whole-query dispatch of standard batched call groups —
        (node, params_mat, call_idxs, extra), the _run_batched_groups
        contract — as ONE program launch.  Used by the
        prepared-statement replay so a whole template is one launch;
        raises WholeQueryUnsupported for shapes the program can't take
        (caller falls back)."""
        groups = list(groups)
        if not groups:
            return
        nodes = tuple(g[0] for g in groups)
        out = self._wq_dispatch(index, shards, nodes,
                                [g[1] for g in groups])
        if self.warm_recorder is not None:
            self.warm_recorder.note_sig(out.sig)
        from ..utils import explain as qexplain
        qexplain.note("plan", {
            "mode": "wholequery", "program": out.sig,
            "compile": "cold" if out.compiled else "warm",
            "nodes": [n.kind for n in nodes],
            "shards": len(shards)})
        for gi, (node, _pm, call_idxs, extra) in enumerate(groups):
            _wire_group(node, out.parts[gi], call_idxs, extra, 0,
                        self.mesh_exec, results,
                        walked=out.meta[gi].get("walk", False))

    def _wq_execute(self, index: str, calls, shards):
        """Lower every call of a read request to reducer nodes, launch
        the whole program once, and wire _Pending results (resolved by
        the caller's single fetch).  Raises WholeQueryUnsupported for
        anything outside the program's fallback matrix
        (docs/whole-query.md); real validation errors raise exactly as
        the legacy path would."""
        idx = self.holder.index(index)
        if idx is None:
            raise ExecutionError(f"index not found: {index}")
        descs = [self._wq_desc(index, c, shards) for c in calls]
        results: list = [None] * len(calls)
        units: list[dict] = []
        by_gkey: dict = {}
        for i, d in enumerate(descs):
            if d["kind"] == "const":
                results[i] = d["result"]
                continue
            gk = d.get("gkey")
            u = by_gkey.get(gk) if gk is not None else None
            if u is None:
                u = {"kind": d["kind"], "descs": [], "idxs": []}
                if gk is not None:
                    by_gkey[gk] = u
                units.append(u)
            u["descs"].append(d)
            u["idxs"].append(i)
        if not units:
            return results

        nodes, mats, unit_nodes = [], [], []
        for u in units:
            kind, ds = u["kind"], u["descs"]
            lo = len(nodes)
            d0 = ds[0]
            if kind in ("count", "segments"):
                mat = np.stack([d["params"] for d in ds])
                nodes.append(ReduceNode(kind, d0["slotted"]))
                mats.append(mat)
            elif kind == "sum":
                mat = np.stack([d["params"] for d in ds])
                nodes.append(ReduceNode("bsi_sum", d0["slotted"],
                                        (d0["field"], d0["view"])))
                mats.append(mat)
            elif kind == "topn":
                mat = np.stack([d["params"] for d in ds])
                nodes.append(ReduceNode("row_counts", d0["slotted"],
                                        (d0["field"], VIEW_STANDARD),
                                        d0["extra"]))
                mats.append(np.stack([with_n(d["params"], d["n"])
                                      for d in ds])
                            if d0["extra"] else mat)
                if d0["tan"]:
                    # tanimoto rides two extra reducers in the SAME
                    # program: unfiltered row totals + the source count
                    nodes.append(ReduceNode(
                        "row_counts", None, (d0["field"], VIEW_STANDARD)))
                    mats.append(np.zeros((1, 0), dtype=np.int32))
                    nodes.append(ReduceNode("count", d0["slotted"]))
                    mats.append(mat)
            elif kind == "minmax":
                nodes.append(ReduceNode(
                    "bsi_minmax", d0["slotted"],
                    (d0["field"], d0["view"]),
                    ("max" if d0["want_max"] else "min",)))
                mats.append(np.asarray(d0["params"],
                                       dtype=np.int32).reshape(1, -1))
            elif kind == "minrow":
                nodes.append(ReduceNode(
                    "row_counts", None, (d0["field"], VIEW_STANDARD)))
                mats.append(np.zeros((1, 0), dtype=np.int32))
            elif kind == "rows":
                for vname in d0["views"]:
                    nodes.append(ReduceNode(
                        "row_counts", None, (d0["field"], vname)))
                    mats.append(np.zeros((1, 0), dtype=np.int32))
            else:  # groupby
                nodes.append(ReduceNode(
                    "group_counts", d0["slotted"],
                    (d0["last_field"], VIEW_STANDARD),
                    tuple(d0["prefix_keys"]) + (d0["pad_c"],)))
                mats.append((d0["rids"], d0["params"]))
            unit_nodes.append((lo, len(nodes)))

        out = self._wq_dispatch(index, shards, tuple(nodes), mats)
        if self.warm_recorder is not None:
            self.warm_recorder.note_sig(out.sig)
        from ..utils import explain as qexplain
        qexplain.note("plan", {
            "mode": "wholequery",
            # the compiled program's devobs signature — the SAME id the
            # compile registry and launch ledger record, so the explain
            # record cross-checks the ledger (None = empty launch)
            "program": out.sig,
            # warm: served from a cached/persistent-cache executable;
            # cold: this request paid a trace+compile (docs/warmup.md)
            "compile": "cold" if out.compiled else "warm",
            "nodes": [n.kind for n in nodes],
            "calls": len(calls), "shards": len(shards)})
        for u, (lo, hi) in zip(units, unit_nodes):
            self._wq_wire(u, out, lo, hi, results)
        return results

    def _wq_wire(self, unit, out, lo, hi, results):
        """Attach _Pending finalizers for one unit's calls over its
        nodes' device parts — the host finalizers the per-stage call
        sites use (results stay byte-identical)."""
        kind, ds, idxs = unit["kind"], unit["descs"], unit["idxs"]
        mesh = self.mesh_exec
        if kind == "count":
            grp = _PendingGroup.counts(out.parts[lo], idxs)
            for i in idxs:
                results[i] = grp
            return
        if kind == "segments":
            parts, meta = out.parts[lo], out.meta[lo]
            for b, i in enumerate(idxs):
                attrs = ds[b].get("attrs")
                results[i] = _Pending(
                    parts, lambda hp, b=b, groups=meta["groups"],
                    empty=meta["empty"], attrs=attrs:
                    RowResult(_segments(hp, b, groups, empty),
                              attrs=attrs))
            return
        if kind == "sum":
            parts = out.parts[lo]
            for b, i in enumerate(idxs):
                base = ds[b]["base"]
                results[i] = _Pending(
                    parts, lambda hp, b=b, base=base:
                    _sum_fin(hp, b, base))
            return
        if kind == "topn":
            d0 = ds[0]
            parts = [p for j in range(lo, hi) for p in out.parts[j]]
            k = len(out.parts[lo])
            ku = len(out.parts[lo + 1]) if d0["tan"] else 0
            f = d0["f"]
            topn = d0["extra"] == TOPN_EXTRA
            walked = out.meta[lo].get("walk", False)
            for b, i in enumerate(idxs):
                d = ds[b]
                results[i] = _Pending(
                    parts,
                    lambda hp, b=b, ids=d["ids"], n=d["n"], k=k, ku=ku,
                    tan=d["tan"], an=d["attr_name"], av=d["attr_values"],
                    f=f, mesh=mesh:
                    self._topn_finalize(
                        _topn_counts(mesh, hp[:k], b, topn, walked),
                        mesh.merge_counts([p[0] for p in hp[k:k + ku]])
                        if tan else None,
                        sum(int(p[0]) for p in hp[k + ku:]) if tan
                        else 0,
                        ids, n, tan, an, av, f))
            return
        if kind == "minmax":
            d0 = ds[0]
            results[idxs[0]] = _Pending(
                out.parts[lo],
                lambda hp, groups=out.meta[lo]["groups"],
                base=d0["base"], want_max=d0["want_max"]:
                _minmax_fin(hp, groups, base, want_max))
            return
        if kind == "minrow":
            results[idxs[0]] = _Pending(
                out.parts[lo],
                lambda hp, want_max=ds[0]["want_max"]:
                _minrow_fin(hp, want_max))
            return
        if kind == "rows":
            d0 = ds[0]
            parts = [p for j in range(lo, hi) for p in out.parts[j]]
            results[idxs[0]] = _Pending(
                parts, lambda hp, limit=d0["limit"],
                previous=d0["previous"]: _rows_fin(hp, limit,
                                                      previous))
            return
        # groupby
        d0 = ds[0]
        results[idxs[0]] = _Pending(
            out.parts[lo],
            lambda hp, combos=d0["combos"], last_ids=d0["last_ids"],
            last_field=d0["last_field"], prev_ids=d0["prev_ids"],
            limit=d0["limit"]:
            _group_page(_group_rows(hp, combos, last_ids, last_field),
                        prev_ids, limit))

    def _wq_desc(self, index: str, c: Call, shards) -> dict:
        """Lower one call to a whole-query unit descriptor, running the
        same validation (and raising the same errors) as the per-stage
        per-call path.  Raises WholeQueryUnsupported for call shapes
        outside the program's vocabulary."""
        from ..parallel.wholequery import WholeQueryUnsupported
        name = c.name
        if name == "Count":
            if len(c.children) != 1:
                raise ExecutionError("Count() requires one input")
            slotted, params = parametrize(
                self._resolve(index, c.children[0]))
            return {"kind": "count", "gkey": ("count", repr(slotted)),
                    "slotted": slotted, "params": params}
        if name == "Sum":
            f = self._bsi_field(index, c)
            fp = self._filter_plan(index, c)
            slotted, params = (None, self._EMPTY_PARAMS) if fp is None \
                else parametrize(fp)
            return {"kind": "sum", "gkey": ("sum", f.name, repr(slotted)),
                    "slotted": slotted, "params": params, "field": f.name,
                    "view": f.bsi_view_name(), "base": f.options.base}
        if name in ("Min", "Max"):
            f = self._bsi_field(index, c)
            fp = self._filter_plan(index, c)
            slotted, params = (None, self._EMPTY_PARAMS) if fp is None \
                else parametrize(fp)
            return {"kind": "minmax", "gkey": None, "slotted": slotted,
                    "params": params, "field": f.name,
                    "view": f.bsi_view_name(), "base": f.options.base,
                    "want_max": name == "Max"}
        if name in ("MinRow", "MaxRow"):
            field_name, ok = c.string_arg("field")
            if not ok:
                raise ExecutionError(f"{c.name}(): field required")
            if self.holder.field(index, field_name) is None:
                raise ExecutionError(f"field not found: {field_name}")
            return {"kind": "minrow", "gkey": None, "field": field_name,
                    "want_max": name == "MaxRow"}
        if name == "TopN":
            return self._wq_desc_topn(index, c, shards)
        if name == "Rows":
            return self._wq_desc_rows(index, c)
        if name == "GroupBy":
            return self._wq_desc_group_by(index, c)
        if name in BITMAP_CALLS:
            plan = self._resolve(index, c)
            slotted, params = parametrize(plan)
            attrs = None
            if c.name in ("Row", "Range"):
                fa = c.field_arg()
                if fa is not None and isinstance(fa[1], int) \
                        and not isinstance(fa[1], bool):
                    f = self.holder.field(index, fa[0])
                    if f is not None:
                        attrs = f.row_attrs.attrs(fa[1]) or None
            return {"kind": "segments",
                    "gkey": ("segments", repr(slotted)),
                    "slotted": slotted, "params": params, "attrs": attrs}
        if name == "Options":
            raise WholeQueryUnsupported("options",
                                        "per-call shard overrides")
        raise ExecutionError(f"unknown call: {name}")

    def _wq_desc_topn(self, index: str, c: Call, shards) -> dict:
        field_name, ok = c.string_arg("_field")
        if not ok:
            raise ExecutionError("TopN() requires a field")
        f = self.holder.field(index, field_name)
        if f is None:
            raise ExecutionError(f"field not found: {field_name}")
        n, _ = c.uint_arg("n")
        ids = c.args.get("ids")
        tan_thresh, attr_name, attr_values = topn_extras(c)
        if not c.children and ids is None and tan_thresh is None \
                and attr_name is None \
                and f.options.cache_type in ("ranked", "lru"):
            from ..cache.rank import topn_from_rank
            pairs = topn_from_rank(f, shards, n, stats=self.stats)
            if pairs is not None:
                return {"kind": "const", "result": pairs}
        fp = self._filter_plan(index, c)
        slotted, params = (None, self._EMPTY_PARAMS) if fp is None \
            else parametrize(fp)
        extras = tan_thresh is not None or attr_name is not None
        extra = topn_extra(slotted, n, ids, extras)
        return {"kind": "topn", "extra": extra,
                "gkey": None if extras
                else ("topn", field_name, repr(slotted), extra),
                "slotted": slotted, "params": params,
                "field": field_name, "ids": ids, "n": n,
                "tan": tan_thresh, "attr_name": attr_name,
                "attr_values": attr_values, "f": f}

    def _wq_desc_rows(self, index: str, c: Call) -> dict:
        from ..parallel.wholequery import WholeQueryUnsupported
        field_name, ok = c.string_arg("_field")
        if not ok:
            raise ExecutionError("Rows() requires a field")
        f = self.holder.field(index, field_name)
        if f is None:
            raise ExecutionError(f"field not found: {field_name}")
        if c.args.get("column") is not None:
            # a column probe reads one bit per row — the per-shard path
            # owns it (no reduction to express)
            raise WholeQueryUnsupported("rows-column")
        views = [VIEW_STANDARD]
        from_arg, to_arg = c.args.get("from"), c.args.get("to")
        if from_arg or to_arg:
            quantum = f.options.time_quantum
            if not quantum:
                raise ExecutionError(
                    f"field {field_name!r} has no time quantum")
            from_time = tq.parse_time(from_arg) if from_arg \
                else datetime(1, 1, 1)
            to_time = tq.parse_time(to_arg) if to_arg \
                else datetime(9999, 1, 1)
            views = tq.views_by_time_range(VIEW_STANDARD, from_time,
                                           to_time, quantum)
        return {"kind": "rows", "gkey": None, "field": field_name,
                "views": views, "limit": c.args.get("limit"),
                "previous": c.args.get("previous")}

    def _wq_desc_group_by(self, index: str, c: Call) -> dict:
        from ..parallel.mesh_exec import MeshExecutor
        from ..parallel.wholequery import WholeQueryUnsupported
        names, rows_calls, filt_call, limit = self._group_by_parse(index,
                                                                   c)
        fields = self._group_by_grid(index, names, rows_calls)
        if fields is None:
            raise WholeQueryUnsupported(
                "group_counts", "children need Rows execution or the "
                                "grid bounds failed")
        prev_ids = self._group_by_previous(c, fields)
        filter_plan = (self._resolve(index, filt_call)
                       if filt_call is not None else None)
        slotted, params = (None, self._EMPTY_PARAMS) \
            if filter_plan is None else parametrize(filter_plan)
        prefix_fields = fields[:-1]
        last_field, last_ids = fields[-1]
        combos: list[tuple] = [()]
        for fname, ids in prefix_fields:
            combos = [cb + ((fname, rid),) for cb in combos
                      for rid in ids]
        if not combos or not last_ids:
            return {"kind": "const", "result": []}
        if len(combos) > MeshExecutor.GROUP_CHUNK:
            raise WholeQueryUnsupported(
                "group_counts",
                f"{len(combos)} prefix combos exceed one chunk")
        rids = np.asarray([[rid for _, rid in cb] for cb in combos],
                          dtype=np.int32).reshape(len(combos),
                                                  len(prefix_fields))
        pad_c = pow2_rows(len(combos))
        return {"kind": "groupby", "gkey": None, "slotted": slotted,
                "params": params, "rids": rids, "pad_c": pad_c,
                "prefix_keys": [(fname, VIEW_STANDARD)
                                for fname, _ in prefix_fields],
                "last_field": last_field, "last_ids": last_ids,
                "combos": combos, "prev_ids": prev_ids, "limit": limit}

    # -- dispatch (executor.go:274 executeCall) ----------------------------

    def _execute_call(self, index: str, c: Call, shards: list[int]):
        name = c.name
        if name == "Count":
            return self._execute_count(index, c, shards)
        if name == "Sum":
            return self._execute_sum(index, c, shards)
        if name in ("Min", "Max"):
            return self._execute_min_max(index, c, shards, name == "Max")
        if name in ("MinRow", "MaxRow"):
            return self._execute_min_max_row(index, c, shards, name == "MaxRow")
        if name == "TopN":
            return self._execute_topn(index, c, shards)
        if name == "Rows":
            return self._execute_rows(index, c, shards)
        if name == "GroupBy":
            return self._execute_group_by(index, c, shards)
        if name == "Options":
            return self._execute_options(index, c, shards)
        if name == "Set":
            return self._execute_set(index, c)
        if name == "Clear":
            return self._execute_clear(index, c)
        if name == "ClearRow":
            return self._execute_clear_row(index, c, shards)
        if name == "Store":
            return self._execute_store(index, c, shards)
        if name in ("SetRowAttrs", "SetColumnAttrs"):
            return self._execute_set_attrs(index, c)
        if name in BITMAP_CALLS:
            return self._execute_bitmap(index, c, shards)
        raise ExecutionError(f"unknown call: {name}")

    # -- bitmap calls ------------------------------------------------------

    def _resolve(self, index: str, c: Call):
        return Resolver(self.holder, index).resolve_bitmap(c)

    def _execute_bitmap(self, index: str, c: Call, shards) -> RowResult:
        plan = self._resolve(index, c)
        attrs = None
        if c.name in ("Row", "Range"):
            # a plain Row() result carries its row's attributes
            # (executor.go:651 executeBitmapCallShard -> row.Attrs)
            fa = c.field_arg()
            if fa is not None and isinstance(fa[1], int) \
                    and not isinstance(fa[1], bool):
                f = self.holder.field(index, fa[0])
                if f is not None:
                    attrs = f.row_attrs.attrs(fa[1]) or None
        return RowResult(self._plan_segments(plan, index, shards),
                         attrs=attrs)

    def _plan_segments(self, plan, index: str, shards) -> dict:
        if self.mesh_exec is not None:
            parts, groups = self._reduce(index, shards, "segments", plan)
            return _segments(fetch_parts(parts), 0, groups, shards)
        # host [W] words, as the mesh path returns them: the word tile
        # is flattened after the fetch, never in a program
        return {
            shard: bitset.from_tile(np.asarray(self.compiler.execute_shard(
                plan, self.holder, index, shard)))
            for shard in shards
        }

    # -- aggregations ------------------------------------------------------

    def _reduce(self, index: str, shards, kind: str, plan, primary=(),
                extra=()):
        """One call as one reducer node at B = 1, launched per stage
        through the dispatch batcher: (unfetched parts, their groups'
        shard lists) — every part's batch axis holds the one row."""
        node, params = self._node(kind, plan, primary, extra)
        return self.batcher.reduce(
            node, np.asarray(params, dtype=np.int32).reshape(1, -1),
            self.holder, index, shards)

    def _row_counts_now(self, index: str, shards, field_name: str,
                        view: str) -> np.ndarray:
        """Unfiltered per-row counts of (field, view) over ``shards``,
        fetched and merged now (Rows and MinRow/MaxRow answer from
        them on the spot)."""
        parts, _ = self._reduce(index, shards, "row_counts", None,
                                (field_name, view))
        return self.mesh_exec.merge_counts(
            p[0] for p in fetch_parts(parts))

    def _execute_count(self, index: str, c: Call, shards) -> int:
        """(executor.go:1790 executeCount)"""
        if len(c.children) != 1:
            raise ExecutionError("Count() requires one input")
        plan = self._resolve(index, c.children[0])
        if self.mesh_exec is not None:
            parts, _ = self._reduce(index, shards, "count", plan)
            return _Pending(parts, lambda hp: sum(int(x[0]) for x in hp))
        counts = [
            self.compiler.execute_shard(plan, self.holder, index, shard,
                                        reducer="count")
            for shard in shards
        ]
        return sum(int(x) for x in counts)

    def _bsi_field(self, index: str, c: Call):
        field_name, _ = c.string_arg("field")
        if not field_name:
            fa = c.field_arg()
            if fa is None:
                raise ExecutionError("field required")
            field_name = fa[0]
        f = self.holder.field(index, field_name)
        if f is None:
            raise ExecutionError(f"field not found: {field_name}")
        if f.options.type != FIELD_TYPE_INT:
            raise ExecutionError(f"field {field_name!r} is not an int field")
        return f

    def _filter_segments(self, index: str, c: Call, shards):
        """Evaluate the optional filter child of Sum/Min/Max/TopN."""
        if not c.children:
            return None
        plan = self._resolve(index, c.children[0])
        return self._plan_segments(plan, index, shards)

    @staticmethod
    def _filter_tile(filters, shard):
        """A shard's filter segment as the device kernels take it (the
        host's [W] words viewed as the word tile), or None."""
        seg = None if filters is None else filters.get(shard)
        return None if seg is None else bitset.to_tile(np.asarray(seg))

    def _filter_plan(self, index: str, c: Call):
        """Resolve the optional filter child to a plan (mesh path fuses it
        into the same shard_map computation instead of materialising
        per-shard segments first)."""
        if not c.children:
            return None
        return self._resolve(index, c.children[0])

    def _execute_sum(self, index: str, c: Call, shards) -> ValCount:
        """(executor.go:406 executeSum + fragment.go:1111 sum)"""
        f = self._bsi_field(index, c)
        view = f.bsi_view_name()
        if self.mesh_exec is not None:
            parts, _ = self._reduce(
                index, shards, "bsi_sum", self._filter_plan(index, c),
                (f.name, view))
            return _Pending(parts, lambda hp, base=f.options.base:
                            _sum_fin(hp, 0, base))
        filters = self._filter_segments(index, c, shards)
        total, n = 0, 0
        for shard in shards:
            frag = self.holder.fragment(index, f.name, view, shard)
            if frag is None or frag.n_rows < bsi.OFFSET_ROW + 1:
                continue
            filt = self._filter_tile(filters, shard)
            counts = np.asarray(bsi.sum_counts(frag.device(), filt))
            s, cnt = bsi.weighted_sum(counts)
            total += s
            n += cnt
        # values are stored base-offset: add base per set column
        # (field.go:1138 Sum: sum + count*base)
        return ValCount(total + n * f.options.base, n)

    def _execute_min_max(self, index: str, c: Call, shards,
                         want_max: bool) -> ValCount:
        """(executor.go:437 executeMin/:472 executeMax)"""
        f = self._bsi_field(index, c)
        view = f.bsi_view_name()
        if self.mesh_exec is not None:
            parts, groups = self._reduce(
                index, shards, "bsi_minmax", self._filter_plan(index, c),
                (f.name, view), ("max" if want_max else "min",))
            return _Pending(parts, lambda hp, base=f.options.base:
                            _minmax_fin(hp, groups, base, want_max))
        acc = ValCount()
        filters = self._filter_segments(index, c, shards)
        for shard in shards:
            frag = self.holder.fragment(index, f.name, view, shard)
            if frag is None or frag.n_rows < bsi.OFFSET_ROW + 1:
                continue
            filt = self._filter_tile(filters, shard)
            bits, neg, cnt = bsi.min_max_bits(frag.device(), filt,
                                              want_max=want_max)
            val, cnt = bsi.reconstruct_min_max(
                np.asarray(bits), int(neg), int(cnt))
            vc = ValCount(val + f.options.base if cnt else 0, cnt)
            acc = acc.larger(vc) if want_max else acc.smaller(vc)
        return acc

    def _execute_min_max_row(self, index: str, c: Call, shards,
                             want_max: bool) -> ValCount:
        """MinRow/MaxRow: extreme row id with any bit set
        (executor.go:506 executeMinRow)."""
        field_name, ok = c.string_arg("field")
        if not ok:
            raise ExecutionError(f"{c.name}(): field required")
        f = self.holder.field(index, field_name)
        if f is None:
            raise ExecutionError(f"field not found: {field_name}")
        if self.mesh_exec is not None:
            return _minrow_fin(
                [self._row_counts_now(index, shards, field_name,
                                      VIEW_STANDARD)[None]], want_max)
        best, best_count = None, 0
        v = f.view(VIEW_STANDARD)
        for shard in shards:
            frag = None if v is None else v.fragment(shard)
            if frag is None or frag.n_rows == 0:
                continue
            counts = np.asarray(bitset.row_counts(frag.device()))
            nz = np.nonzero(counts)[0]
            if nz.size == 0:
                continue
            rid = int(nz[-1] if want_max else nz[0])
            if best is None or (rid > best if want_max else rid < best):
                best, best_count = rid, int(counts[rid])
            elif rid == best:
                best_count += int(counts[rid])
        return ValCount(best or 0, best_count if best is not None else 0)

    # -- TopN (executor.go:860 executeTopN, fragment.go:1570 top) ----------

    @staticmethod
    def _topn_finalize(counts, row_tot, src_count, ids, n, tan_thresh,
                       attr_name, attr_values, field) -> list[Pair]:
        """Shared tail of TopN: tanimoto/attr row filtering + ranking.

        Tanimoto (fragment.go:1704 topBitmapPairs): keep rows where
        100*|row∩src| >= threshold*(|row|+|src|-|row∩src|).  Computed on
        GLOBAL counts (across all shards) rather than per shard — exact
        where the reference's per-shard cache heuristic is approximate.
        Attr filter (executor.go:942-995): keep rows whose row-attribute
        ``attr_name`` value is in ``attr_values``."""
        if tan_thresh:
            size = max(counts.size, row_tot.size)
            c_ = np.zeros(size, dtype=np.int64)
            c_[: counts.size] = counts
            t_ = np.zeros(size, dtype=np.int64)
            t_[: row_tot.size] = row_tot
            denom = t_ + src_count - c_
            ok = (denom > 0) & (100 * c_ >= tan_thresh * denom)
            counts = np.where(ok, c_, 0)
        if attr_name is None:
            # vectorized rank: only the returned n rows materialize Pairs
            return rank_counts(counts, n or None, ids)
        allowed = set(attr_values)
        pairs = [p for p in rank_counts(counts, None, ids)
                 if field.row_attrs.attrs(p.id).get(attr_name) in allowed]
        return pairs[: n or None]

    def _execute_topn(self, index: str, c: Call, shards) -> list[Pair]:
        field_name, ok = c.string_arg("_field")
        if not ok:
            raise ExecutionError("TopN() requires a field")
        f = self.holder.field(index, field_name)
        if f is None:
            raise ExecutionError(f"field not found: {field_name}")
        n, _ = c.uint_arg("n")
        ids = c.args.get("ids")
        tan_thresh, attr_name, attr_values = topn_extras(c)

        # Unfiltered TopN first consults the field's per-fragment rank
        # caches (cache/rank.py; the reference's fragment.go:1570 top →
        # cache.go rankCache hot path).  Candidate pruning stays EXACT:
        # the cache answers only when it can prove the pruned rows cannot
        # reach the top n, and otherwise this falls through to the full
        # scan below.  Not on a multi-process mesh: the caches are
        # host-side and hold this process's slice of the shards only,
        # so their answer would miss every other process's rows.
        if not c.children and ids is None and tan_thresh is None \
                and attr_name is None \
                and f.options.cache_type in ("ranked", "lru") \
                and not (self.mesh_exec is not None
                         and self.mesh_exec.multiprocess):
            from ..cache.rank import topn_from_rank
            pairs = topn_from_rank(f, shards, n, stats=self.stats)
            if pairs is not None:
                return pairs

        if self.mesh_exec is not None:
            # one shard_map computation: per-row popcounts masked by the
            # filter plan, psum'd over the shard axis (fragment.go:1570 top
            # collapsed into a single ICI all-reduce); tanimoto adds an
            # unfiltered pass + the src count, all dispatched before the
            # single blocking fetch
            filter_plan = self._filter_plan(index, c)
            primary = (field_name, VIEW_STANDARD)
            parts, _ = self._reduce(index, shards, "row_counts",
                                    filter_plan, primary)
            parts_u, parts_src = [], []
            if tan_thresh:
                parts_u, _ = self._reduce(index, shards, "row_counts",
                                          None, primary)
                parts_src, _ = self._reduce(index, shards, "count",
                                            filter_plan)
            k, ku = len(parts), len(parts_u)

            topn = bool(topn_extra(
                filter_plan, n, ids,
                tan_thresh is not None or attr_name is not None))

            def _fin(hp, ids=ids, n=n):
                merge = self.mesh_exec.merge_counts
                counts = _topn_counts(self.mesh_exec, hp[:k], 0, topn,
                                      False)
                row_tot = merge(p[0] for p in hp[k: k + ku]) \
                    if tan_thresh else None
                src = sum(int(x[0]) for x in hp[k + ku:]) \
                    if tan_thresh else 0
                return self._topn_finalize(
                    counts, row_tot, src, ids, n, tan_thresh, attr_name,
                    attr_values, f)

            return _Pending(parts + parts_u + parts_src, _fin)

        filters = self._filter_segments(index, c, shards)
        v = f.view(VIEW_STANDARD)
        counts = np.zeros(0, dtype=np.int64)
        row_tot = np.zeros(0, dtype=np.int64)
        src_count = 0
        if tan_thresh and filters is not None:
            # src is counted over ALL shards — including ones where the
            # TopN field has no fragment (the mesh path's count_async does
            # the same; skipping them would shrink the denominator)
            src_count = sum(
                int(np.asarray(bitset.count(seg)))
                for seg in filters.values())
        for shard in shards:
            frag = None if v is None else v.fragment(shard)
            if frag is None or frag.n_rows == 0:
                continue
            dev = frag.device()
            filt = self._filter_tile(filters, shard)
            if filt is not None:
                counts_dev = bitset.row_counts(
                    bitset.intersect(dev, filt[None]))
            else:
                counts_dev = bitset.row_counts(dev)
            counts = acc_counts(counts, np.asarray(counts_dev))
            if tan_thresh:
                row_tot = acc_counts(
                    row_tot, np.asarray(bitset.row_counts(dev)))
        return self._topn_finalize(counts, row_tot, src_count, ids, n,
                                   tan_thresh, attr_name, attr_values, f)

    # -- Rows (executor.go:1274 executeRows) -------------------------------

    def _execute_rows(self, index: str, c: Call, shards) -> RowIdentifiers:
        field_name, ok = c.string_arg("_field")
        if not ok:
            raise ExecutionError("Rows() requires a field")
        f = self.holder.field(index, field_name)
        if f is None:
            raise ExecutionError(f"field not found: {field_name}")
        limit = c.args.get("limit")
        previous = c.args.get("previous")
        column = c.args.get("column")

        views = [VIEW_STANDARD]
        from_arg, to_arg = c.args.get("from"), c.args.get("to")
        if from_arg or to_arg:
            quantum = f.options.time_quantum
            if not quantum:
                raise ExecutionError(
                    f"field {field_name!r} has no time quantum")
            from_time = tq.parse_time(from_arg) if from_arg \
                else datetime(1, 1, 1)
            to_time = tq.parse_time(to_arg) if to_arg else datetime(9999, 1, 1)
            views = tq.views_by_time_range(VIEW_STANDARD, from_time, to_time,
                                           quantum)

        row_ids: set[int] = set()
        for vname in views:
            v = f.view(vname)
            if v is None:
                continue
            if self.mesh_exec is not None and column is None:
                counts = self._row_counts_now(index, shards, field_name,
                                              vname)
                row_ids.update(int(i) for i in np.nonzero(counts)[0])
                continue
            for shard in shards:
                if column is not None and column // SHARD_WIDTH != shard:
                    continue
                frag = v.fragment(shard)
                if frag is None or frag.n_rows == 0:
                    continue
                dev = frag.device()
                if column is not None:
                    col_local = column % SHARD_WIDTH
                    w, bit = bitset.word_bit_np(col_local)
                    present = np.asarray(
                        dev[(slice(None),) + bitset.word_at(w)]) & bit > 0
                    ids = np.nonzero(present)[0]
                else:
                    counts = np.asarray(bitset.row_counts(dev))
                    ids = np.nonzero(counts)[0]
                row_ids.update(int(i) for i in ids)

        out = sorted(row_ids)
        if previous is not None:
            out = [r for r in out if r > previous]
        if limit is not None:
            out = out[:limit]
        return RowIdentifiers(rows=out)

    # -- GroupBy (executor.go:1068 executeGroupBy) -------------------------

    def _group_by_parse(self, index: str, c: Call):
        """(names, rows_calls, filt_call, limit) with the reference's
        argument validation — shared by the per-stage path and the
        whole-query lowering (_wq_desc_group_by)."""
        if not c.children:
            raise ExecutionError("GroupBy requires at least one Rows() child")
        limit = c.args.get("limit")
        filt_call = None
        rows_calls = []
        for ch in c.children:
            if ch.name == "Rows":
                rows_calls.append(ch)
            else:
                filt_call = ch
        if not rows_calls:
            raise ExecutionError("GroupBy requires Rows() children")
        names = []
        for rc in rows_calls:
            fname, ok = rc.string_arg("_field")
            if not ok:
                raise ExecutionError("Rows() requires a field")
            names.append(fname)
        return names, rows_calls, filt_call, limit

    def _group_by_grid(self, index: str, names, rows_calls):
        """Row-id grid fields when every child is a plain Rows(field)
        and the grid bounds hold; None otherwise (the caller executes
        Rows).  Plain Rows() children take a row-id GRID instead of
        executing Rows first: every (field, row<=max_row) combo is
        counted and zero-count groups drop out, which is the same
        answer without the per-child blocking device round trips (the
        odometer seeds of executor.go:3058, folded into the combo
        dispatch).  Only the PREFIX fields' product is dispatched (the
        last field rides each dispatch's per-row count vector), so the
        grid bounds are: prefix combos per wave (chunked to GROUP_CHUNK
        per executable call, all async) and the total combo count
        (which sizes the count fetch: total x 4 bytes).  The r4 cap of
        4096 TOTAL combos fell back to blocking per-child Rows round
        trips for e.g. a 128x128 two-field GroupBy whose dispatch cost
        is actually one 128-combo wave."""
        if not all(set(rc.args) == {"_field"} for rc in rows_calls):
            return None
        caps = []
        for fname in names:
            f = self.holder.field(index, fname)
            if f is None:
                raise ExecutionError(f"field not found: {fname}")
            v = f.view(VIEW_STANDARD)
            cap = 0 if v is None else max(
                (fr.max_row_id() + 1 for fr in v.fragments.values()
                 if fr.host_bytes()), default=0)
            caps.append(cap)
        total = 1
        for c_ in caps:
            total *= c_
        prefix_total = 1
        for c_ in caps[:-1]:
            prefix_total *= c_
        if 0 < total <= self.GROUP_GRID_MAX and \
                prefix_total <= self.GROUP_GRID_PREFIX_MAX:
            return [(fname, list(range(c_)))
                    for fname, c_ in zip(names, caps)]
        return None

    @staticmethod
    def _group_by_previous(c: Call, fields):
        """previous=[row per Rows child]: resume pagination strictly
        after that group (executor.go:1403, :3058 groupByIterator
        seek)."""
        previous = c.args.get("previous")
        if previous is None:
            return None
        if not isinstance(previous, list) or \
                len(previous) != len(fields):
            raise ExecutionError(
                "GroupBy previous= must list one row per Rows child")
        return tuple(int(p) for p in previous)

    def _execute_group_by(self, index: str, c: Call,
                          shards) -> list[GroupCount]:
        names, rows_calls, filt_call, limit = self._group_by_parse(index,
                                                                   c)
        fields = []
        if self.mesh_exec is not None:
            fields = self._group_by_grid(index, names, rows_calls) or []
        if not fields:
            for fname, rc in zip(names, rows_calls):
                ids = self._execute_rows(index, rc, shards).rows
                fields.append((fname, ids))

        prev_ids = self._group_by_previous(c, fields)

        # Count each combination: per shard, AND the group rows' segments +
        # optional filter, popcount.  The innermost field is batched on
        # device; on the mesh path the whole inner loop is ONE psum'd
        # shard_map call per combo with dynamic prefix row ids.
        results: list[GroupCount] = []
        last_field, last_ids = fields[-1]
        prefix_fields = fields[:-1]

        def prefix_combos(i=0, combo=()):
            if i == len(prefix_fields):
                yield combo
                return
            fname, ids = prefix_fields[i]
            for rid in ids:
                yield from prefix_combos(i + 1, combo + ((fname, rid),))

        if self.mesh_exec is not None:
            filter_plan = (self._resolve(index, filt_call)
                           if filt_call is not None else None)
            prefix_keys = [(fname, VIEW_STANDARD) for fname, _ in
                           prefix_fields]
            combos = list(prefix_combos())
            if not combos:
                return []
            mat = np.asarray(
                [[rid for _, rid in combo] for combo in combos],
                dtype=np.int32).reshape(len(combos), len(prefix_fields))
            # A handful of executable invocations cover every combo
            # (vmapped combo axis, chunked to bound device memory: one
            # group_counts node a chunk of GROUP_CHUNK combos, full
            # chunks sharing one executable) — the odometer's per-combo
            # round trips (executor.go:3058) collapse into one dispatch
            # per 256 combos, resolved by a single fetch
            slotted, params = (None, self._EMPTY_PARAMS) \
                if filter_plan is None else parametrize(filter_plan)
            chunk = self.mesh_exec.GROUP_CHUNK
            chunked = []
            for lo in range(0, len(combos), chunk):
                sub = mat[lo: lo + chunk]
                pad_c = pow2_rows(len(sub))
                parts, _ = self.batcher.reduce(
                    ReduceNode("group_counts", slotted,
                               (last_field, VIEW_STANDARD),
                               tuple(prefix_keys) + (pad_c,)),
                    (sub, params), self.holder, index, shards)
                chunked.append((lo, lo + len(sub), parts))
            all_parts = [p for _, _, ps in chunked for p in ps]

            def _fin(hp, combos=combos, last_ids=last_ids):
                out: list[GroupCount] = []
                i = 0
                for lo, hi, ps in chunked:
                    out += _group_rows(hp[i: i + len(ps)], combos[lo: hi],
                                       last_ids, last_field)
                    i += len(ps)
                return _group_page(out, prev_ids, limit)

            return _Pending(all_parts, _fin)

        filter_segs = None
        if filt_call is not None:
            plan = self._resolve(index, filt_call)
            filter_segs = {
                s: self.compiler.execute_shard(plan, self.holder, index, s)
                for s in shards
            }

        last_pos = {r: j for j, r in enumerate(last_ids)}
        for combo in prefix_combos():
            counts_acc = np.zeros(len(last_ids), dtype=np.int64)
            for shard in shards:
                prefix_seg = None
                empty = False
                for fname, rid in combo:
                    frag = self.holder.fragment(index, fname, VIEW_STANDARD,
                                                shard)
                    if frag is None or rid >= frag.n_rows:
                        empty = True
                        break
                    seg = frag.device()[rid]
                    prefix_seg = seg if prefix_seg is None else \
                        bitset.intersect(prefix_seg, seg)
                if empty:
                    continue
                if filter_segs is not None:
                    fseg = filter_segs[shard]
                    prefix_seg = fseg if prefix_seg is None else \
                        bitset.intersect(prefix_seg, fseg)
                frag = self.holder.fragment(index, last_field, VIEW_STANDARD,
                                            shard)
                if frag is None or frag.n_rows == 0:
                    continue
                dev = frag.device()
                valid = [r for r in last_ids if r < frag.n_rows]
                if not valid:
                    continue
                sel = dev[np.array(valid)]
                if prefix_seg is None:
                    cnts = np.asarray(bitset.row_counts(sel))
                else:
                    cnts = np.asarray(bitset.row_counts(
                        bitset.intersect(sel, prefix_seg[None])))
                for j, r in enumerate(valid):
                    counts_acc[last_pos[r]] += int(cnts[j])
            for j, rid in enumerate(last_ids):
                if counts_acc[j] > 0:
                    group = [FieldRow(fn, ri) for fn, ri in combo]
                    group.append(FieldRow(last_field, rid))
                    results.append(GroupCount(group, int(counts_acc[j])))

        return _group_page(results, prev_ids, limit)

    # -- Options (executor.go executeOptionsCall) --------------------------

    @staticmethod
    def _options_bool(c: Call, name: str) -> bool:
        v = c.args.get(name, False)
        if not isinstance(v, bool):
            raise ExecutionError(f"Options() {name} must be a bool")
        return v

    @staticmethod
    def attach_column_attrs(holder, index: str, result):
        """Stash [{"id", "attrs"}] for every result column that has column
        attributes onto the RowResult; the HTTP layer lifts them to the
        response's top-level "columnAttrs" (executor.go:163-192,
        :209 readColumnAttrSets)."""
        if not isinstance(result, RowResult):
            return result
        idx = holder.index(index)
        # one store snapshot + intersect: O(stored attrs), not O(result
        # columns) — results can span millions of columns
        all_attrs = idx.column_attrs.all()
        if not all_attrs:
            result.column_attrs = []
            return result
        attr_ids = np.fromiter(all_attrs.keys(), dtype=np.int64,
                               count=len(all_attrs))
        have = np.intersect1d(attr_ids, result.columns())
        result.column_attrs = [{"id": int(c), "attrs": all_attrs[int(c)]}
                               for c in np.sort(have)]
        return result

    def _execute_options(self, index: str, c: Call, shards):
        """(executor.go:340-403 executeOptionsCall)"""
        if len(c.children) != 1:
            raise ExecutionError("Options() requires exactly one child")
        if "shards" in c.args:
            arg = c.args["shards"]
            if not isinstance(arg, list):
                raise ExecutionError("Options() shards must be a list")
            shards = [int(s) for s in arg]
        column_attrs = self._options_bool(c, "columnAttrs")
        exclude_row_attrs = self._options_bool(c, "excludeRowAttrs")
        exclude_columns = self._options_bool(c, "excludeColumns")
        result = self._execute_call(index, c.children[0], shards)
        if not (column_attrs or exclude_row_attrs or exclude_columns):
            return result

        def _shape(r):
            if isinstance(r, RowResult):
                if exclude_columns:
                    r.segments = {}
                if column_attrs:
                    # after excludeColumns on purpose: both flags yield no
                    # attr sets, matching the reference's response shaping
                    self.attach_column_attrs(self.holder, index, r)
                if exclude_row_attrs:
                    r.attrs = {}
            return r

        if isinstance(result, _Pending):
            inner_fin = result.fin
            result.fin = lambda hp: _shape(inner_fin(hp))
            return result
        return _shape(result)

    # -- writes (executor.go:2067 executeSet etc.) -------------------------

    def _require_col(self, c: Call) -> int:
        col = c.args.get("_col")
        if not isinstance(col, int) or isinstance(col, bool):
            raise ExecutionError(
                f"{c.name}() column argument must be an integer id "
                f"(got {col!r})")
        return col

    def _execute_set(self, index: str, c: Call) -> bool:
        idx = self.holder.index(index)
        col = self._require_col(c)
        fa = c.field_arg()
        if fa is None:
            raise ExecutionError("Set() requires a field=<row> argument")
        field_name, row_val = fa
        f = self.holder.field(index, field_name)
        if f is None:
            raise ExecutionError(f"field not found: {field_name}")

        if f.options.type == FIELD_TYPE_INT:
            if not isinstance(row_val, int):
                raise ExecutionError("Set() int field requires integer value")
            changed = f.set_value(col, row_val)
        else:
            ts = None
            if "_timestamp" in c.args:
                ts = tq.parse_time(c.args["_timestamp"])
            row_val = self._coerce_row(f, row_val)
            changed = f.set_bit(row_val, col, ts=ts)
        idx.add_existence(np.array([col]))
        return changed

    @staticmethod
    def _coerce_row(f, row_val) -> int:
        if isinstance(row_val, bool):
            if f.options.type != FIELD_TYPE_BOOL:
                raise ExecutionError("bool row value on non-bool field")
            return int(row_val)
        if not isinstance(row_val, int):
            raise ExecutionError(
                f"row must be an integer id, got {row_val!r}")
        return row_val

    def _execute_clear(self, index: str, c: Call) -> bool:
        col = self._require_col(c)
        fa = c.field_arg()
        if fa is None:
            raise ExecutionError("Clear() requires a field=<row> argument")
        field_name, row_val = fa
        f = self.holder.field(index, field_name)
        if f is None:
            raise ExecutionError(f"field not found: {field_name}")
        return f.clear_bit(self._coerce_row(f, row_val), col)

    def _execute_clear_row(self, index: str, c: Call, shards) -> bool:
        """(executor.go:1825 executeClearRow)"""
        fa = c.field_arg()
        if fa is None:
            raise ExecutionError("ClearRow() requires a field=<row> argument")
        field_name, row_id = fa
        f = self.holder.field(index, field_name)
        if f is None:
            raise ExecutionError(f"field not found: {field_name}")
        changed = False
        for vname, v in list(f.views.items()):
            if vname.startswith("bsig_"):
                continue
            for shard in shards:
                frag = v.fragment(shard)
                if frag is not None and row_id < frag.n_rows:
                    if frag.row(row_id).any():
                        frag.set_row(row_id, None)
                        changed = True
        return changed

    def _execute_store(self, index: str, c: Call, shards) -> bool:
        """Store(Row(...), field=row) (executor.go:1979 executeSetRow)"""
        fa = c.field_arg()
        if fa is None:
            raise ExecutionError("Store() requires a field=<row> argument")
        field_name, row_id = fa
        f = self.holder.field(index, field_name)
        if f is None:
            f = self.holder.index(index).create_field_if_not_exists(field_name)
        if len(c.children) != 1:
            raise ExecutionError("Store() requires exactly one input row")
        src = self._execute_bitmap(index, c.children[0], shards)
        for shard in shards:
            seg = src.segments.get(shard)
            v = f._create_view_if_not_exists(VIEW_STANDARD)
            frag = v.create_fragment_if_not_exists(shard)
            frag.set_row(row_id, None if seg is None else np.asarray(seg))
        return True

    def _execute_set_attrs(self, index: str, c: Call):
        # Attribute storage arrives with the attrs subsystem (storage/attrs);
        # wired in the API layer.
        from ..storage.attrs import set_attrs_from_call
        return set_attrs_from_call(self.holder, index, c)
