"""Multi-device shard execution over a jax Mesh.

The reference fans per-shard jobs to a goroutine pool and a star reduce
(executor.go:2455 mapReduce, :2482 coordinator-side reduce).  Here shards
with identical plan input shapes are STACKED into [S, rows, 256, 128]
tensors (a row's words are a whole word tile, ops/bitset.py
"Representation": S and rows are untiled major dimensions, so a row take
inside a program is an offset, not a gather), sharded over a 1-d "shards"
mesh axis, and the whole batch executes as one
XLA computation under shard_map: each device runs the vmapped plan on its
local shard block and cross-shard reductions ride ICI collectives (psum)
instead of host gather — the star reduce becomes an all-reduce.

The reducers are the six node kinds of parallel/nodes.py (count,
segments, row_counts, bsi_sum, bsi_minmax, group_counts), each defined
there once: ``reduce_async`` below launches one node per stage — one
compiled executable per input-shape signature, the node's per-shard body
vmapped over the device's shards — and parallel/wholequery.py launches a
whole request's nodes as one program over the same body.

On a single device this degrades gracefully to one stacked call (one
dispatch instead of one per shard).

When a query's stacked working set exceeds the device budget, execution
STREAMS: the shard list is carved into slices of at most half the budget,
slices whose stacks are already resident are drained first, and while the
current slice's dispatch runs, a background uploader stages the next one
(sparse->dense expansion via the host staging cache + ``jax.device_put``
off the critical path) — double-buffering within the budget so over-budget
queries run at upload bandwidth instead of serialized miss latency (the
HBM analog of the reference's page-cache read-ahead over mmap'd fragments,
fragment.go:311).  In-use and prefetched slices are pinned in the budget
so concurrent staging cannot evict them mid-use (docs/memory-budget.md).

Compressed residency (ops/containers.py): fragments whose density
heuristic picks the packed container form stage as stacked
key/type/count/offset tables + payload words instead of dense tensors,
and the compiled executables decode them to dense tiles INSIDE the
vmapped per-shard body — decode-at-op-time, fused with the op.  The
stacked blocks register with the budget at their compressed bytes, so
residency, eviction, prefetch, and the slice planner are all sized by
the compressed footprint and an over-budget dense working set becomes a
resident compressed one.  The transient dense tiles a launch decodes are
bounded separately: the slice planner also cuts when a slice's decoded
bytes would exceed DECODE_WORKSPACE_BYTES, so the XLA temp buffer the
decode reuses per launch stays small even when the whole (compressed)
working set is resident.
"""

from __future__ import annotations

import functools
import itertools as _itertools
import time as _time
from concurrent import futures

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import CONTAINER_WORDS, SHARD_WORDS, WORD_TILE
from ..ops import bitset
from ..executor.plan import row_takes
from ..utils import devobs as _devobs
from ..utils import profile as qprof
from ..utils.deadline import check_current
from ..utils.faults import FAULTS
from ..utils.locks import make_lock, make_rlock
from ..utils.tracing import GLOBAL_TRACER, layer_span
from . import nodes as _nodes
from .nodes import PER_SHARD_KINDS, mat_rows, node_keys, pad_pow2_rows, \
    participates, row_totals, walk_order

SHARD_AXIS = "shards"

# Per-launch dense decode workspace ceiling (docs/memory-budget.md
# "Compressed residency"): a shard slice whose compressed stacks decode
# to more dense bytes than this is cut into smaller slices, bounding the
# transient dense tiles one executable materialises.  Process-wide, set
# from the server config (decode-workspace-mb) like DEFAULT_BUDGET.
DECODE_WORKSPACE_BYTES = 1 << 30


def _flatten_present(present):
    """Flatten present (key, placed, sig) entries into the device-arg
    list a compiled executable takes: a compressed entry contributes its
    seven stacked container arrays, a dense one a single tensor.  Returns
    (flat_args, layout); ``layout`` drives _unpack_frags inside the
    executable and is fully determined by the entries' sigs (which key
    the executable cache), so one compiled body always sees one layout."""
    flat, layout = [], []
    for k, a, s in present:
        if isinstance(a, tuple):
            flat.extend(a)
            layout.append((k, len(a), s))
        else:
            flat.append(a)
            layout.append((k, 1, s))
    return flat, tuple(layout)


class PackedRows:
    """A compressed fragment as a plan sees it where the plan only takes
    rows of it (``executor.plan.eval_plan``): the fragment's shape, and
    ``take_row``, which decodes the one row asked for
    (``containers.decode_row``) where ``_unpack_frags`` would decode
    them all — a filter over a 16-row field costs a launch one row's
    tiles, not sixteen.  ``arrays`` are one shard's packed arrays inside
    the vmapped per-shard body, ``stacked_idx`` the whole block's array
    entries, closed over and so NOT batched over the shards: whether any
    shard holds the row as array entries is one scalar a launch, and the
    scatter that sets them a real conditional."""

    __slots__ = ("arrays", "sig", "stacked_idx", "shape")

    def __init__(self, arrays, sig, stacked_idx):
        self.arrays, self.sig, self.stacked_idx = arrays, sig, stacked_idx
        self.shape = (sig[1],) + WORD_TILE

    def take_row(self, rid):
        from ..ops import containers
        s = self.sig
        return bitset.to_tile(containers.decode_row(
            *self.arrays, rid, rows=s[1], words=SHARD_WORDS,
            a_bucket=s[4], r_bucket=s[5],
            has_array=containers.row_has_entries(self.stacked_idx, rid)
            if s[4] else None))


def _unpack_frags(layout, arrays, rows_of=(), stacked=None):
    """Inside a per-shard (vmapped) body: decode compressed inputs to
    dense [rows, 256, 128] fragments — the decode-at-op-time step, fused
    into the op's own executable so dense tiles exist only as
    launch-local XLA temporaries; the decoder's [rows, W] output is
    viewed as the word tile here, the one funnel — and map every key to
    its dense fragment.  A compressed key in ``rows_of`` (the keys whose
    rows are all a launch takes: ``_row_keys``) is not decoded but
    handed on as ``PackedRows``; ``stacked`` is then the launch's flat
    argument list before the per-shard ``vmap``."""
    from ..ops import containers
    out = {}
    i = 0
    for k, n, s in layout:
        if n == 1:
            out[k] = arrays[i]
        elif k in rows_of:
            out[k] = PackedRows(arrays[i: i + n], s, stacked[i + 5])
        else:
            out[k] = bitset.to_tile(containers.decode_block(
                *arrays[i: i + n], rows=s[1], words=SHARD_WORDS,
                a_bucket=s[4], r_bucket=s[5]))
        i += n
    return out


def _row_keys(node, fused: bool) -> frozenset:
    """The keys of ``node`` that its per-shard body reads only through
    the plan's row takes, so that a compressed one need not be decoded
    whole: the plan's ``Row`` inputs, less what the body reads as a
    fragment — the primary (but where its rows are counted in the packed
    stream, ``fused``) and a GroupBy's prefix fields."""
    if node.plan is None:
        return frozenset()
    whole = set()
    if node.kind not in ("count", "segments") and not fused:
        whole.add(node.primary)
    if node.kind == "group_counts":
        whole.update(node.extra[:-1])
    return frozenset(row_takes(node.plan)) - whole


def _fused_entry(layout, key):
    """(flat-arg index, arrays, sig) of ``key``'s layout entry when it
    is a compressed entry — the condition under which a per-shard body
    counts the field's rows where they lie in the packed stream
    (kernels.fused_row_counts) instead of decode-then-op, by the backend
    the entry's signature names.  None for a dense entry.  Static per
    layout, so the per-shard body's branch is resolved at trace time."""
    i = 0
    for k, n, s in layout:
        if k == key:
            return (i, n, s) if n > 1 else None
        i += n
    return None

# Multi-device collective programs must be ENQUEUED in one consistent
# order across all device queues: two threads (concurrent server
# requests, or the prefetch uploader racing a dispatch) interleaving
# psum/all_gather program launches wedge the per-device queues into a
# circular rendezvous wait (reproduced on the 8-virtual-device CPU
# platform: rank k stuck on RunId A while the rest wait on RunId B —
# XLA collective_ops_utils "may be stuck").  One process-wide lock
# around every collective-program LAUNCH (shard_map executables and
# sharded-output indexing) restores a global enqueue order; execution
# itself stays async and overlapped, only the enqueue serializes.
_DISPATCH_LOCK = make_lock("dispatch")


def field_rows(holder, index: str, field: str, view: str) -> int:
    """Max fragment row count for (field, view) — the ``rows`` axis of
    a batched/fused row_counts launch's [B, rows, 256, 128] masked temp, fed
    into the batch-temp workspace sizing (executor.batch_chunk_size and
    the batcher's fusion cap).  0 when the view holds no fragments."""
    idx = holder.index(index)
    f = idx.field(field) if idx is not None else None
    v = f.view(view) if f is not None else None
    if v is None:
        return 0
    return max((fr.n_rows for fr in v.fragments.values()), default=0)


def form_tag(sigs) -> str:
    """The ``form`` tag of a ``dispatch.place`` annotation: ``dense``
    for a placement whose stacked inputs are all dense, ``z:<backend>``
    for one of compressed stacks, by the backend their signatures name
    (``z:jnp``, ``z:pallas``)."""
    from ..ops import kernels as _kernels
    backends = sorted({_kernels.sig_backend(s) for s in sigs
                       if s is not None and s[0] == "z"})
    return "z:" + "+".join(backends) if backends else "dense"


def _counts_in_place(layout, node, b_pad: int):
    """``_fused_entry`` of a ``row_counts`` node's primary where a
    launch of ``b_pad`` params rows counts it in the packed stream."""
    from ..ops import kernels as _kernels
    if node is None or node.kind != "row_counts" \
            or b_pad > _kernels.FUSED_PARAMS_MAX:
        return None
    return _fused_entry(layout, node.primary)


def launch_form(layout, node) -> str:
    """The ``form`` tag of a ``dispatch.enqueue`` annotation: ``dense``
    for a launch over dense stacks; ``z:pallas`` for one that runs the
    Pallas kernel (a ``row_counts`` node over a compressed primary whose
    signature names it); ``z:jnp`` for every other launch over
    compressed stacks: XLA decodes and counts."""
    from ..ops import kernels as _kernels
    if all(n == 1 for _, n, _ in layout):
        return "dense"
    fused = _counts_in_place(layout, node, 1)
    return "z:pallas" if fused is not None and fused[2][4] \
        and _kernels.sig_backend(fused[2]) == "pallas" else "z:jnp"


def launch_cost(layout, node, b_pad: int) -> tuple[int, int, int]:
    """What one launch of ``b_pad`` params rows costs a stacked shard:
    (dense tile bytes decoded from compressed stacks, Pallas kernel
    launches, container tiles those count) — the launch ledger's
    ``decodeBytesTotal`` / ``kernelLaunches`` / ``kernelTiles``.  A
    fragment decoded whole costs its rows, a row take of a compressed
    fragment one row a params row, and a field counted in the packed
    stream decodes nothing: where the Pallas kernel counts it, that is
    one kernel launch over the field's container tiles."""
    from ..ops import kernels as _kernels
    tile = SHARD_WORDS * 4
    fused = _counts_in_place(layout, node, b_pad)
    rows_of = _row_keys(node, fused is not None) if node else frozenset()
    takes = row_takes(node.plan) if rows_of else {}
    b = 1 if node is None or node.kind in ("bsi_minmax", "group_counts") \
        else b_pad
    decode = 0
    for k, n, s in layout:
        if n == 1:
            continue
        if k in rows_of:
            decode += takes[k] * b * tile
        elif fused is None or k != node.primary:
            decode += s[1] * tile
    if fused is not None and fused[2][4] \
            and _kernels.sig_backend(fused[2]) == "pallas":
        return decode, 1, fused[2][1] * (SHARD_WORDS // CONTAINER_WORDS)
    return decode, 0, 0


def node_over_layout(node, layout, mat, shard, stacked):
    """One reducer node's per-shard contribution over a shape group's
    ``layout`` — the body of both launchers' vmapped per-shard pass:
    ``shard`` are one shard's arrays, ``stacked`` the launch's before
    the ``vmap``.  Dense inputs go to ``nodes.node_shard`` as they are.
    Of compressed ones a plan's row takes decode their one row
    (``PackedRows``), and a ``row_counts`` node's compressed primary is
    not decoded at all — the headline fusion (ops/kernels.py): its rows
    are counted under the filters where they lie in the packed stream
    (of at most ``FUSED_PARAMS_MAX`` params rows: ``nodes.plan_rows``
    unrolls as many)."""
    fused = _counts_in_place(layout, node, mat_rows(mat))
    frags = _unpack_frags(layout, shard, _row_keys(node, fused is not None),
                          stacked)
    if fused is None:
        return _nodes.node_shard(node, mat, frags)
    from ..ops import kernels
    i0, n0, fs = fused
    filts = None if node.plan is None \
        else _nodes.plan_rows(node.plan, frags, mat)
    counts = kernels.fused_row_counts(
        *shard[i0: i0 + n0], filts, rows=fs[1], words=SHARD_WORDS,
        a_bucket=fs[4], r_bucket=fs[5],
        backend=kernels.sig_backend(fs))                   # [B, rows]
    return jnp.broadcast_to(counts, (mat.shape[0],) + counts.shape[1:])


def slice_tags() -> dict:
    """``slice`` and ``slices`` of the same two annotations: the
    streaming schedule's position (``_ShardSchedule``), 0 of 1 for a
    launch outside one."""
    pos = _devobs.current_slice() or (0, 1)
    return {"slice": pos[0], "slices": pos[1]}


class _InstrumentedExec:
    """One compiled shard_map executable plus its device-runtime
    telemetry (utils/devobs.py, docs/observability.md "Device runtime").

    The wrapped block_fn marks the compile registry whenever jax TRACES
    it (the python body only runs while tracing), so every call knows
    whether it compiled; a signature tracing more than once is the
    retrace red flag the PR 7 bug never raised.  Every invocation also
    lands in the launch ledger: padded sizes read off the args
    themselves, the actual stacked shard count passed by the call site
    as ``_launch_meta``, queue/ticket context installed by the dispatch
    batcher, and the streaming slice position installed by
    _ShardSchedule."""

    __slots__ = ("fn", "sig", "kind", "detail", "devices", "form",
                 "layout", "node")

    def __init__(self, fn, key, layout, devices: int, node=None):
        self.fn = fn
        self.devices = devices      # of the mesh the program runs over
        self.kind = key[0] if key and isinstance(key[0], str) else "exec"
        self.sig = _devobs.sig_of(key)
        self.detail = repr(key[1])[:120] if len(key) > 1 else ""
        # what a launch decodes and which kernel it runs follow from the
        # layout, the node and the params rows: ``launch_cost``
        self.layout, self.node = layout, node
        self.form = launch_form(layout, node)

    def __call__(self, *args, _launch_meta=None):
        # call-site meta: actual shard count, or (shards, actual batch
        # rows) where the launcher pads the batch axis itself
        # (group_counts' pow-2 combo padding).  args: the node's params
        # matrix (replicated), then the stacked fragment arrays
        meta_rows = None
        if isinstance(_launch_meta, tuple):
            _launch_meta, meta_rows = _launch_meta
        b_pad = mat_rows(args[0])
        stacked = args[1] if len(args) > 1 else None
        shards_pad = stacked.shape[0] if stacked is not None else 0
        shards = _launch_meta if _launch_meta is not None else shards_pad
        ctx = _devobs.launch_ctx() or {}
        rows = ctx.get("rows")
        if rows is None:
            rows = meta_rows if meta_rows is not None else b_pad
        tickets = ctx.get("tickets", 1)
        decode, kernels_n, tiles = (
            c * shards for c in launch_cost(self.layout, self.node, b_pad))
        reg = _devobs.COMPILES
        reg.begin_call()
        # dispatch.enqueue: host time to hand this program to the
        # runtime; its seconds reach /debug/vars through the ledger
        # (dispatchSecondsTotal)
        with layer_span("dispatch.enqueue", kind=self.kind, sig=self.sig,
                        rows=rows, rows_padded=b_pad, tickets=tickets,
                        shards=shards, shards_padded=shards_pad,
                        temp_bytes=ctx.get("temp_bytes", 0),
                        devices=self.devices, form=self.form,
                        **slice_tags()) as span:
            t0 = _time.perf_counter()
            out = self.fn(*args)
            dt = _time.perf_counter() - t0
            compiled = reg.traced()
            span.tag(compiled=compiled)
        if compiled:  # fingerprinting is only paid on compiles
            reg.note_call(self.sig, self.kind, dt,
                          _devobs.fingerprint(args), detail=self.detail)
        _devobs.LEDGER.record(
            sig=self.sig, kind=self.kind, shards=shards,
            shards_padded=shards_pad,
            batch_rows=rows, batch_rows_padded=b_pad,
            queue_s=ctx.get("queue_s", 0.0), tickets=tickets,
            dispatch_s=dt, compiled=compiled,
            decode_bytes=decode,
            slice_pos=_devobs.current_slice(),
            kernel_launches=kernels_n, kernel_tiles=tiles)
        prof = qprof.current()
        if prof is not None:
            # rows/padding/decode tags feed the EXPLAIN launches section
            # (utils/explain.py) — the same numbers the ledger records,
            # so an explain record cross-checks the ledger by sig
            prof.event("device.launch", dt, kind=self.kind, sig=self.sig,
                       shards=shards, shardsPadded=shards_pad,
                       batchRows=rows, batchRowsPadded=b_pad,
                       decodeBytes=decode,
                       compiled=compiled)
        return out


def default_mesh(devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices), axis_names=(SHARD_AXIS,))


_EXEC_SEQ = _itertools.count()
_BLOCK_SEQ = _itertools.count()


class _Block:
    """One (field, view)'s stacked block over one shard list — the unit
    of stacked residency: resident once, registered with the device
    budget once (``skey``, unique to this block, so a late unregister
    can never hit its successor), shared by every stack-cache entry
    whose program reads it.  ``bkey`` is (index, (field, view), shard
    list), ``token`` its signature and fragments' device generations,
    ``arrays`` the placed block (the five packed tables of a compressed
    one), ``epochs`` the ingest epochs it reflects; the last two move
    together (``move``), under the executor's stack-cache lock, and
    ``walk`` — the TopN walk's plan from these very arrays' row totals
    (``MeshExecutor.walk_plan``), None until a top-n node first reads
    the block — goes when they move.  ``nbytes`` is what
    it holds over all of this process's devices, ``device_bytes`` what
    the fullest of those ``devices`` holds, read off the arrays' own
    shards: the budget's limit is one device's (storage/membudget.py)."""

    __slots__ = ("bkey", "skey", "token", "arrays", "epochs", "walk",
                 "nbytes", "compressed", "device_bytes", "devices")

    def __init__(self, exec_id, bkey, token, arrays, epochs):
        self.bkey, self.token = bkey, token
        self.skey = ("block", exec_id, next(_BLOCK_SEQ))
        self.move(arrays, epochs)
        per_device: dict = {}
        for a in arrays if isinstance(arrays, tuple) else (arrays,):
            for sh in a.addressable_shards:
                per_device[sh.device] = \
                    per_device.get(sh.device, 0) + sh.data.nbytes
        self.nbytes = sum(per_device.values())
        self.device_bytes = max(per_device.values())
        self.devices = len(per_device)
        self.compressed = self.nbytes if token[0][0] == "z" else 0

    def move(self, arrays, epochs):
        """The block's words changed (stacked, or an ingest overlay
        rewrote them): what was derived from the old ones goes."""
        self.arrays, self.epochs, self.walk = arrays, epochs, None


class MeshExecutor:
    """Executes resolved plans over stacked shard groups on a device mesh."""

    def __init__(self, mesh: Mesh | None = None):
        self.mesh = mesh or default_mesh()
        # monotonic per-process instance number: disambiguates this
        # executor's plan keys (and thus compile-registry signatures)
        # from any earlier executor's — see _plan_key
        self._exec_seq = next(_EXEC_SEQ)
        _devobs.COMPILES.listen()  # eager compiles count from here on
        self.n_devices = self.mesh.devices.size
        # A mesh spanning >1 jax process (multihost mode 2,
        # parallel/multihost.py): shard-axis-sharded OUTPUTS are not
        # addressable from any single process, so executables that
        # return per-shard results gather them over the shard axis
        # (all_gather rides ICI/DCN) and replicate — aggregations
        # (psum) are replicated already.
        self.multiprocess = len(
            {d.process_index for d in self.mesh.devices.flat}) > 1
        # Fragment mirrors must live on the mesh's platform (e.g. a virtual
        # CPU mesh while the default backend is a TPU).  When the mesh IS on
        # the default platform we stage with target=None so the mesh path
        # and the per-shard executor share one cached upload per fragment
        # instead of holding two copies in device memory.
        stage = self.mesh.devices.flat[0]
        cfg_default = jax.config.jax_default_device
        default_platform = (cfg_default.platform if cfg_default is not None
                            else jax.devices()[0].platform)
        self.stage_device = None if stage.platform == default_platform \
            else stage
        self._cache: dict = {}
        # (index, keys, shards) -> (mirror-id token, groups) — the stacked
        # + mesh-placed input blocks, rebuilt only when a fragment's device
        # mirror changes (a write re-uploads it).  Without this every query
        # would re-stack its input fragments on device.  LRU-bounded: a
        # stale entry (shard set grew, index deleted) pins a full stacked
        # copy of its fragments in device memory until evicted.
        from collections import OrderedDict
        from ..storage.membudget import DEFAULT_BUDGET
        self._stack_cache: OrderedDict = OrderedDict()
        self.stack_cache_max = 64
        # (index, (field, view), shards) -> _Block: a (field, view)'s
        # rows are resident ONCE however many programs stack them (Q1.1,
        # Q1.2 and Q1.3 all read lo_extdisc; the load check's and the
        # mix's key lists differ).  The block is what the device budget
        # registers, touches, pins and evicts; a stack-cache entry only
        # refers to its blocks.  Exactly the blocks some entry holds are
        # in here (``_store_entry`` and the eviction callback keep it
        # so), read and written under ``_sc_lock``.
        import weakref
        self._blocks: dict = {}
        # /debug/vars stackCache.fastHits / .walks: lookups validated by
        # the device epoch alone, and every other lookup (a walk over
        # the fragments).  Plain ints, bumped without a lock on the
        # dispatcher thread and read racily by the handler.
        self.stack_fast_hits = 0
        self.stack_walks = 0
        # /debug/vars batchTemp.splits: packs the batcher cut short,
        # batches chunked and launches that walked their shards in
        # blocks, each for the batch-temp bound's sake.  A plain int
        # like the two above.
        self.temp_splits = 0
        # /debug/vars topnPrune.*: top-n nodes the walk answered
        # (nodes.topn_walk), the rows it visited of the rows stacked,
        # and top-n nodes that took the full pass (a per-stage launch,
        # several shape groups, a compressed or shard-blocked stack,
        # more params rows than the walk unrolls).
        # Plain ints like the above, bumped by the finishers.
        self.topn_queries = 0
        self.topn_rows_visited = 0
        self.topn_rows_stacked = 0
        self.topn_full_scans = 0
        # /debug/vars stackCache.scheduleFastHits / .scheduleWalks: slice
        # plans of an over-budget set served while the device epoch
        # stood, and those that walked the fragments for their bytes
        # (``shard_schedule``; a set that fits counts in neither: it is
        # one slice, unplanned).  Plain ints like the above.
        self.schedule_fast_hits = 0
        self.schedule_walks = 0
        # (index, keys, shards) -> (device epoch, limit, workspace,
        # slices): the cuts of an over-budget working set, kept while
        # no fragment's device form can have moved
        self._slice_plans: dict = {}
        self._budget = DEFAULT_BUDGET
        # what the budget's limit is one device's share of: a stacked
        # block lies over this mesh (most recent executor wins, like
        # the limit itself)
        self._budget.spread = self.n_devices
        # single-worker background uploader for streamed shard slices
        # (created on first over-budget query; one worker serializes
        # prefetch transfers so they never contend with each other)
        self._uploader = None
        # Leaf lock for _stack_cache dict ops ONLY (never held across any
        # other lock acquisition): budget-eviction callbacks and query
        # threads race on the dict, and a callback taking the main
        # executor lock could deadlock two executors evicting each other's
        # entries.
        self._sc_lock = make_lock("stack-cache")
        self._finalizer = weakref.finalize(
            self, MeshExecutor._cleanup_budget, self._budget,
            self._stack_cache, self._blocks)
        # Concurrent request threads share this executor (the server
        # overlaps in-flight query batches to hide the dispatch round
        # trip); the lock covers the python-side cache bookkeeping only —
        # device dispatch runs outside it.
        self._lock = make_rlock("mesh-exec")

    # -- compiled executables ---------------------------------------------

    def _jit_shard_map(self, key, block_fn, in_specs, out_specs,
                       check_vma: bool = True, layout=(), node=None):
        """``check_vma=False`` for multiprocess gather executables: their
        P() outputs ARE replicated (all_gather over the shard axis), but
        shard_map's static varying-axes checker cannot infer that.
        ``layout`` (from _flatten_present) and ``node`` size the launch
        ledger's decode and kernel attribution; the cached object is the
        executable wrapped in its telemetry hooks (_InstrumentedExec)."""
        fn = self._cache.get(key)
        if fn is None:
            if any(n > 1 for _, n, _ in layout):
                # shard_map's replication checker has no rule for
                # pallas_call and trips over a conditional under vmap
                # (``PackedRows.take_row``); jax suggests
                # check_vma=False as the workaround.  These bodies'
                # outputs follow the same psum/P(SHARD_AXIS) patterns
                # the checker validates on the dense layouts
                check_vma = False

            def traced_body(*a, _fn=block_fn):
                # runs ONLY while jax traces: an exact compile detector
                _devobs.COMPILES.mark_traced()
                return _fn(*a)

            # the program's name in a profiler trace and in XLA's dumps
            # (jit_ptpu_<kind>): a function of the kind alone, so the
            # persistent compile cache keys do not move with a digest,
            # a shape or this executor's sequence number
            traced_body.__name__ = f"ptpu_{key[0]}"
            fn = _InstrumentedExec(
                jax.jit(jax.shard_map(
                    traced_body, mesh=self.mesh,
                    in_specs=in_specs, out_specs=out_specs,
                    check_vma=check_vma)),
                key, layout, self.n_devices, node)
            self._cache[key] = fn
        return fn

    def _plan_key(self, kind, plan, input_keys, shapes, extra=()):
        # _exec_seq, not id(self.mesh): a GC'd mesh's id can be REUSED by
        # the next one, and a byte-identical key would then make the
        # process-global compile registry read a fresh executor's first
        # compile as a PR-7-class retrace (a false alarm on the one
        # signal that must stay trustworthy)
        return (kind, repr(plan), tuple(input_keys), tuple(shapes),
                tuple(extra), self._exec_seq)

    # -- shard grouping ----------------------------------------------------

    def _placed_groups(self, keys, holder, index, shards):
        with layer_span("dispatch.place", _devobs.LEDGER,
                        devices=self.n_devices, **slice_tags()) as span:
            groups = self._place_groups(keys, holder, index, shards)
            if span.recording:      # the launch path pays for no tag
                span.tag(form=form_tag(
                    s for _, _, sig in groups for s in sig))
            return groups

    def _place_groups(self, keys, holder, index, shards):
        """Group shards by input-shape signature over fragment keys
        [(field, view), ...] and stack+place each group's fragments over
        the mesh axis.  Returns [(shard_list, placed_per_key, shapes)];
        ``placed_per_key[i]`` is None when key i's fragment is absent in
        the whole group.

        Results are cached against the fragments' data-generation stamps
        (fragment.gen) so repeat queries reuse the resident stacked blocks
        without touching (or pinning) the per-fragment mirrors at all; each
        block's bytes register with the DeviceBudget so HBM pressure can
        evict it, and with it every entry that reads it (r3 advisor).  A
        budget-eviction callback may pop entries concurrently from outside
        ``self._lock`` (it must not lock: two executors evicting each
        other's entries would deadlock), so every cache op here tolerates
        a vanished key.

        An entry is ``(token, out, epochs, epoch, blocks)``; ``blocks``
        are the ``_Block``s whose arrays ``out`` places.  ``epoch`` is the
        device epoch (holder.device_epoch, fragment.py _DEVICE_EPOCH)
        read BEFORE the walk that validated the entry; while the epoch
        read at the top of a later call equals it, no input of the
        token can have moved and the entry is served without looking at
        a fragment.  The order is the safety argument: a write bumps the
        epoch after it changed the fragment and before it is
        acknowledged, so a reader that sees the old epoch may serve the
        old stack (the write is not acknowledged yet), and a reader that
        walks stores an epoch no newer than the state it saw.  Reading
        the epoch after the walk would be wrong: a write landing between
        the two would be stamped as seen."""
        epoch = holder.device_epoch(index, keys)
        ckey = (index, tuple(keys), tuple(shards))
        with self._sc_lock:
            cached = self._stack_cache.get(ckey)
            if cached is not None:
                self._stack_cache.move_to_end(ckey)
        if cached is not None and cached[3] == epoch:
            self.stack_fast_hits += 1
            self._budget.touch(*[b.skey for b in cached[4]])
            return cached[1]
        self.stack_walks += 1
        frags, token, epochs = self._stack_token(keys, holder, index, shards)
        if cached is not None and cached[0] == token:
            if cached[2] != epochs:
                # ingest delta overlay (docs/ingest.md): the stack is
                # current at its device_gen token but member fragments
                # have journaled flushes since — OR the missing chunks
                # into the resident stacked blocks on device instead of
                # rebuilding/re-uploading them.  Multi-process meshes
                # rebuild instead (their staging must stay deterministic
                # across processes).
                if self.multiprocess:
                    cached = None
                else:
                    self._refresh_overlays(ckey, token, frags, shards,
                                           keys, epochs, epoch)
            else:
                # still current: stamp the entry with the epoch read at
                # the top so the next lookup takes the fast check
                with self._sc_lock:
                    if self._stack_cache.get(ckey) is cached:
                        self._stack_cache[ckey] = \
                            cached[:3] + (epoch,) + cached[4:]
            if cached is not None:
                self._budget.touch(*[b.skey for b in cached[4]])
                return cached[1]

        groups: dict[tuple, list[tuple[int, list]]] = {}
        for shard, row, sig in zip(shards, frags,
                                   self._group_sigs(frags, len(keys))):
            groups.setdefault(sig, []).append((shard, row))
        nk = len(keys)
        row_of = {s: i for i, s in enumerate(shards)}
        held = list(epochs)     # the ingest epochs the placed blocks hold
        blocks, fresh = [], []
        out = []
        for sig, members in groups.items():
            shard_list = [m[0] for m in members]
            placed = []
            for i, shape in enumerate(sig):
                if shape is None:
                    placed.append(None)
                    continue
                frs = [m[1][i] for m in members]
                bkey = (index, keys[i], tuple(shard_list))
                btoken = (shape,) + tuple(fr.device_gen for fr in frs)
                at = tuple(epochs[row_of[shard] * nk + i]
                           for shard in shard_list)
                with self._sc_lock:
                    blk = self._blocks.get(bkey)
                    arrays, b_at = (blk.arrays, blk.epochs) \
                        if blk is not None else (None, None)
                if blk is None or blk.token != btoken or \
                        (self.multiprocess and b_at != at):
                    # (a multi-process mesh overlays nothing: a block
                    # behind its fragments' flushes is stacked anew)
                    blk = _Block(id(self), bkey, btoken,
                                 self._place_block(frs, shape), at)
                    fresh.append(blk)
                    arrays, b_at = blk.arrays, at
                # another program may have stacked these rows: they are
                # shared, and the ingest epochs they were stacked at
                for shard, ep in zip(shard_list, b_at):
                    held[row_of[shard] * nk + i] = ep
                blocks.append(blk)
                placed.append(arrays)
            out.append((shard_list, placed, sig))
        held = tuple(held)
        self._store_entry(ckey, (token, out, held, epoch, tuple(blocks)),
                          fresh)
        if held != epochs:
            # a shared block was stacked before flushes this entry's
            # fragments have journaled since: overlay them now
            self._refresh_overlays(ckey, token, frags, shards, keys,
                                   epochs, epoch)
        return out

    def _group_sigs(self, frags, nk: int) -> list:
        """Each shard's tuple of signatures over the ``nk`` keys, with
        the compressed fragments of a key (of one row capacity) brought
        to ONE signature: the largest container, payload, array and run
        buckets among them (``_place_packed_block`` pads every member to
        the group's buckets anyway).  Left apart, a key whose fragments
        straddle a bucket's edge — ``dist_miles`` at 510–513 containers
        a shard, or the odd fragment with a run container — splits every
        slice into several shape groups of a few shards each, every one
        a launch and, at every new stacked length, a compile."""
        from ..ops import kernels
        sigs = [[None if fr is None else self._frag_sig(fr) for fr in row]
                for row in frags]
        for i in range(nk):
            largest: dict = {}      # row capacity -> the largest buckets
            for row in sigs:
                s = row[i]
                if s is not None and s[0] == "z":
                    top = largest.get(s[1])
                    largest[s[1]] = s[2:6] if top is None else \
                        tuple(map(max, top, s[2:6]))
            for row in sigs:
                s = row[i]
                if s is not None and s[0] == "z" and \
                        s[2:6] != largest[s[1]]:
                    c, p, a, r = largest[s[1]]
                    row[i] = ("z", s[1], c, p, a, r,
                              kernels.backend_for(s[1]))
        return [tuple(row) for row in sigs]

    def _store_entry(self, ckey, entry, fresh):
        """Store a stack-cache entry and the blocks it placed, and give
        the device budget exactly the blocks that are held.  A fresh
        block takes its key's place in ``_blocks``; entries that still
        read the block it displaced are stale (a fragment of it has
        moved on) and go, as does the least recently used entry past
        ``stack_cache_max``; blocks no entry holds any more are
        unregistered, fresh ones registered — outside the leaf lock,
        since registering may evict (and call back into
        ``_evict_block``).  An entry that shares a block which left
        ``_blocks`` while it was being built (evicted, displaced) is
        not stored: its launch runs on what it placed and the next
        lookup builds anew."""
        with self._sc_lock:
            if any(self._blocks.get(b.bkey) is not b
                   for b in entry[4] if b not in fresh):
                return
            gone = []
            for blk in fresh:
                old = self._blocks.get(blk.bkey)
                if old is not None:
                    gone.append(old)
                    self._drop_readers_locked(old)
                self._blocks[blk.bkey] = blk
            self._stack_cache[ckey] = entry
            while len(self._stack_cache) > self.stack_cache_max:
                self._stack_cache.popitem(last=False)
            gone += self._sweep_blocks_locked()
        for blk in gone:
            self._budget.unregister(blk.skey)
        import weakref
        wself = weakref.ref(self)  # the budget must not pin the executor
        for blk in fresh:
            self._budget.register(
                blk.skey, blk.nbytes, functools.partial(
                    MeshExecutor._evict_block, wself, blk.bkey, blk.skey),
                compressed_bytes=blk.compressed,
                device_bytes=blk.device_bytes, devices=blk.devices)
            with self._sc_lock:
                held = self._blocks.get(blk.bkey) is blk
            if not held:    # swept or displaced before it was registered
                self._budget.unregister(blk.skey)

    @staticmethod
    def _evict_block(wself, bkey, skey):
        """The budget evicted a block: it goes, with every entry that
        reads it and every block those entries alone held.  Guarded on
        the block's own budget key, under the leaf lock: a deferred
        callback that lost a race with a rebuild after a data change
        must not drop the fresh block."""
        s = wself()
        if s is None:
            return
        with s._sc_lock:
            cur = s._blocks.get(bkey)
            if cur is None or cur.skey != skey:
                return
            del s._blocks[bkey]
            s._drop_readers_locked(cur)
            unheld = s._sweep_blocks_locked()
        for blk in unheld:
            s._budget.unregister(blk.skey)

    def _drop_readers_locked(self, blk):
        """Drop every stack-cache entry that reads ``blk``."""
        for ck in [ck for ck, e in self._stack_cache.items()
                   if blk in e[4]]:
            del self._stack_cache[ck]

    def _sweep_blocks_locked(self) -> list:
        """Take out of ``_blocks`` those no entry holds; returns them
        for the caller to unregister outside the lock."""
        held = {id(b) for e in self._stack_cache.values() for b in e[4]}
        dead = [b for b in self._blocks.values() if id(b) not in held]
        for blk in dead:
            del self._blocks[blk.bkey]
        return dead

    def _place_block(self, frs, shape):
        """Stack one (field, view)'s fragments ``frs`` of one signature
        and place the block over the mesh axis."""
        if shape[0] == "z":
            # compressed staging: the resident form IS the packed
            # stream; the bytes registered are the compressed footprint
            return self._place_packed_block(frs, shape)
        # Two staging paths.  Warm (mirrors already resident, one
        # device): stack on device — no host transfer at all.  Cold:
        # build the dense [S, rows, W] block on host and ship it (viewed
        # as [S, rows, 256, 128]) as ONE
        # sharded transfer instead of one upload per fragment.  On a
        # mesh of several devices the warm path would first build the
        # whole stack on the default device, where every mirror lives,
        # and then move all but one device's share off it; the host
        # block goes to each device directly.  The 4/5 residency
        # threshold below was chosen against a remote device whose
        # per-transfer cost no longer applies; it awaits re-derivation
        # on the chip (ROADMAP.md S9).
        resident = sum(
            1 for fr in frs
            if not fr._device_dirty
            and fr._mirrors.get(self.stage_device) is not None)
        if self.multiprocess:
            # per-process staging: each process supplies only its
            # addressable shards (device_put would assert the whole
            # host block equal across processes)
            return self._place_host_block(frs, shape)
        if self.n_devices == 1 and 5 * resident >= 4 * len(frs):
            arrs = [fr.device(self.stage_device) for fr in frs]
            if all(a.shape == shape for a in arrs):
                return self._pad_and_place(arrs, shape, len(frs))
            # a concurrent write grew a fragment's capacity after the
            # shape signature was read — the host path slices to the
            # signature's shape
        return self._place_host_block(frs, shape)

    def _stack_token(self, keys, holder, index, shards):
        """(per-shard fragment rows, device-generation token, ingest
        epochs) for a stacked block.  The token keys cache validity
        against ``fr.device_gen`` — the generation the device-resident
        form reflects — so an ingest flush (which bumps ``gen`` but
        journals its delta instead of invalidating device state,
        docs/ingest.md) does NOT rebuild the stack; the epochs vector
        tells ``_placed_groups`` which journal chunks to overlay in.
        Any non-ingest mutation re-anchors device_gen = gen and the
        token mismatch rebuilds as before.  The FULL signature rides
        along: a budget-limit change can flip a fragment between dense
        and compressed residency, and a container-kernels flip changes
        the compressed signature's backend axis — either way a stale
        stack would feed plans keyed on signatures the current config
        no longer produces, so the token mismatch rebuilds it."""
        frags = [[holder.fragment(index, field, view, shard)
                  for field, view in keys] for shard in shards]
        token = tuple(
            -1 if fr is None else (fr.device_gen, self._frag_sig(fr))
            for row in frags for fr in row)
        epochs = tuple(
            0 if fr is None else fr.ingest_epoch
            for row in frags for fr in row)
        return frags, token, epochs

    def stack_block_bytes(self) -> int:
        """Bytes of the stacked blocks held now (/debug/vars
        stackCache.blockBytes): each block once, as it is resident and
        registered once, however many entries read it."""
        with self._sc_lock:
            return sum(b.nbytes for b in self._blocks.values())

    def _is_resident(self, keys, holder, index, shards) -> bool:
        """Whether this (keys, shards) stack is cached AND current — the
        residency signal the streaming scheduler orders slices by."""
        with self._sc_lock:
            cached = self._stack_cache.get(
                (index, tuple(keys), tuple(shards)))
        if cached is None:
            return False
        if cached[3] == holder.device_epoch(index, keys):
            return True
        _, token, _epochs = self._stack_token(keys, holder, index, shards)
        # an epoch lag still counts as resident: the overlay scatter is
        # a few KB of device work, not a re-stage
        return cached[0] == token

    # -- ingest delta overlay (docs/ingest.md) -----------------------------

    def _refresh_overlays(self, ckey, token, frags, shards, keys,
                          new_epochs, epoch):
        """OR journaled ingest flushes into the resident stacked blocks
        of a token-valid cache entry.  Per dense group/key: gather every
        member fragment's unseen journal chunks, dedupe host-side, and
        run one scatter-OR shard_map program over the stacked array —
        KBs of overlay transfer instead of a full re-stage.  Compressed
        ('z') entries never appear here (their fragments fold instead
        of journaling).  Serialized under the executor lock; a racing
        duplicate application is harmless (OR of already-present bits
        contributes nothing).  ``epoch`` is the device epoch the caller
        read before it walked ``new_epochs`` off the fragments."""
        from ..ingest.delta import merge_chunks
        nk = len(keys)
        row_of = {s: i for i, s in enumerate(shards)}
        with self._lock:
            with self._sc_lock:
                cur = self._stack_cache.get(ckey)
            if cur is None or cur[0] != token or cur[2] == new_epochs:
                return
            out, old_epochs = cur[1], cur[2]
            for shard_list, placed, sig in out:
                for ki in range(nk):
                    s_k = sig[ki]
                    if s_k is None or s_k[0] == "z":
                        continue
                    members, idxs, vals = [], [], []
                    for j, shard in enumerate(shard_list):
                        fr = frags[row_of[shard]][ki]
                        if fr is None:
                            continue
                        ep = old_epochs[row_of[shard] * nk + ki]
                        di, dv = merge_chunks(fr.delta_chunks(ep))
                        if di.size:
                            members.append(
                                np.full(di.size, j, dtype=np.int32))
                            idxs.append(di)
                            vals.append(dv)
                    if not members:
                        continue
                    at = tuple(new_epochs[row_of[shard] * nk + ki]
                               for shard in shard_list)
                    with self._sc_lock:
                        blk = self._blocks.get(
                            (ckey[0], keys[ki], tuple(shard_list)))
                        arrays, b_at = (blk.arrays, blk.epochs) \
                            if blk is not None else (None, None)
                    if blk is not None and arrays is not placed[ki] \
                            and b_at == at:
                        # another entry overlaid the shared block
                        # already: take its array
                        placed[ki] = arrays
                        continue
                    placed[ki] = self._overlay_stack(
                        placed[ki], np.concatenate(members),
                        np.concatenate(idxs), np.concatenate(vals))
                    if blk is not None and blk in cur[4]:
                        with self._sc_lock:
                            blk.move(placed[ki], at)
            with self._sc_lock:
                cur2 = self._stack_cache.get(ckey)
                if cur2 is not None and cur2[0] == token:
                    self._stack_cache[ckey] = \
                        (token, out, new_epochs, epoch) + cur2[4:]

    def _overlay_stack(self, stacked, member, flat_idx, vals):
        """One scatter-OR launch: ``stacked`` is the mesh-sharded
        [S, rows, 256, 128] block; (member, flat_idx, vals) name the
        overlay words.  Indices ship as (member, row, word) int32 triples
        (a flattened int64 offset would exceed jax's default index width
        on large fragments), the program finds word ``w`` at tile position
        ``[w // 128, w % 128]``, and the add-of-missing-bits formulation keeps
        padding collisions harmless (ingest/delta.py).  Not routed
        through _InstrumentedExec: its shard/padding attribution reads
        reducer-shaped args, and a KB-scale maintenance scatter would
        only pollute the launch ledger."""
        from ..ingest.delta import pad_overlay
        m, r, w, v = pad_overlay(flat_idx, vals, SHARD_WORDS,
                                 member=member)
        key = ("overlay", tuple(stacked.shape), m.size)
        fn = self._cache.get(key)
        if fn is None:
            def block_fn(block, m_, r_, w_, v_):
                s_local = block.shape[0]
                base = jax.lax.axis_index(SHARD_AXIS) * s_local
                loc = m_ - base
                ok = (loc >= 0) & (loc < s_local)
                loc = jnp.where(ok, loc, 0)
                at = (loc, r_) + bitset.word_at(w_)
                contrib = jnp.where(ok, v_ & ~block[at], jnp.uint32(0))
                return block.at[at].add(contrib)

            block_fn.__name__ = "ptpu_overlay"
            fn = jax.jit(jax.shard_map(
                block_fn, mesh=self.mesh,
                in_specs=(P(SHARD_AXIS), P(), P(), P(), P()),
                out_specs=P(SHARD_AXIS)))
            self._cache[key] = fn
        with _DISPATCH_LOCK:
            return fn(stacked, m, r, w, v)

    def walk_plan(self, index, key, shard_list, stacked):
        """The TopN walk's plan over one (field, view)'s dense stacked
        block: ``nodes.walk_order`` of the block's unfiltered row totals
        (summed over its shards and the mesh), two small replicated
        device arrays that ride into the program as arguments.  Made
        from ``stacked`` itself — the array the launch is about to read,
        so the walk's bound holds for exactly those words — in one
        device pass, and kept on the ``_Block`` while ``stacked`` is
        its ``arrays``: an ingest overlay or a re-stage moves the block
        and the next top-n launch counts anew."""
        bkey = (index, key, tuple(shard_list))
        with self._sc_lock:
            blk = self._blocks.get(bkey)
            walk = blk.walk if blk is not None else None
        if walk is not None and walk[0] is stacked:
            return walk[1]
        ckey = ("walkplan", tuple(stacked.shape))
        fn = self._cache.get(ckey)
        if fn is None:
            def block_fn(block):
                return walk_order(row_totals(block, SHARD_AXIS))

            block_fn.__name__ = "ptpu_walkplan"
            fn = self._cache[ckey] = jax.jit(jax.shard_map(
                block_fn, mesh=self.mesh, in_specs=P(SHARD_AXIS),
                out_specs=P()))
        with _DISPATCH_LOCK:
            plan = fn(stacked)
        if blk is not None:
            with self._sc_lock:
                if blk.arrays is stacked:
                    blk.walk = (stacked, plan)
        return plan

    @staticmethod
    def _cleanup_budget(budget, stack_cache, blocks):
        """Drop this executor's budget accounting (runs on close() or GC —
        without it, accounting-only budgets would grow phantom resident
        bytes for every discarded executor)."""
        for blk in list(blocks.values()):
            budget.unregister(blk.skey)
        blocks.clear()
        stack_cache.clear()

    def close(self):
        """Unregister budget entries and drop cached device state (also
        runs automatically when an un-closed executor is GC'd)."""
        with self._lock:
            if self._uploader is not None:
                self._uploader.shutdown(wait=True, cancel_futures=True)
                self._uploader = None
            self._finalizer()
            self._cache.clear()

    def _uploader_pool(self):
        with self._lock:
            if self._uploader is None:
                from concurrent.futures import ThreadPoolExecutor
                self._uploader = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="ptpu-prefetch")
            return self._uploader

    def _bucket(self, n: int) -> int:
        """Stacked shard counts round UP to a bucket: executables are
        keyed by shape, and a one-shard difference between two shard
        sets (resize, Options(shards=...), working-set rotation) must
        not pay a multi-second XLA recompile.  A device's share of the
        shards goes up to a power of two as far as 8, and beyond to a
        multiple of an eighth of the power of two below it, at least 8
        (33 -> 40, 58 -> 64, 172 -> 176, 256 -> 256, 954 -> 960): the
        steps keep growing with the count, and the padding stays under
        an eighth of it where doubling cost up to half.  Padding shards
        are zero blocks — they contribute nothing to counts/reductions,
        but every kernel reads them and every temporary holds them."""
        per_dev = -(-max(n, 1) // self.n_devices)
        if per_dev <= 8:
            return self.n_devices * (1 << (per_dev - 1).bit_length())
        step = max(8, (1 << ((per_dev - 1).bit_length() - 1)) // 8)
        return self.n_devices * (-(-per_dev // step) * step)

    def stacked_per_device(self, n_shards: int) -> int:
        """Per-device rows of a stacked dispatch after _bucket padding —
        the multiplier batched-dispatch chunk sizing must use (padded
        zero shards still materialize gather temps)."""
        return self._bucket(max(1, n_shards)) // self.n_devices

    def _pad_and_place(self, arrays_list, shape, n: int):
        """Stack n member arrays (fragment mirrors, [rows, 256, 128]
        each), pad the shard axis to its bucket, and place sharded over
        the mesh axis."""
        pad = self._bucket(n) - n
        mats = list(arrays_list)
        if pad:
            zero = jax.device_put(
                np.zeros(shape, dtype=np.uint32), self.stage_device)
            mats += [zero] * pad
        stacked = jnp.stack(mats)
        sharding = NamedSharding(self.mesh, P(SHARD_AXIS))
        return jax.device_put(stacked, sharding)

    def _place_host_block(self, frs, shape):
        """Cold-path staging: densify the group's fragments into one host
        block and place it mesh-sharded in a single transfer (bypassing
        per-fragment mirrors entirely).  On a multi-process mesh each
        process materializes ONLY the shard rows jax asks it for (its
        addressable devices) — the per-host import pipeline fills just
        the local slice (multihost.import_process_slice), and remote
        shards' placeholder fragments densify to zeros that are never
        consulted."""
        n = len(frs)
        sharding = NamedSharding(self.mesh, P(SHARD_AXIS))
        bucket = self._bucket(n)

        def fill(n_block, lo):
            """Shards [lo, lo + n_block) as the host has them,
            [n_block, rows, W], handed over as the device's word tile
            (a view)."""
            block = np.zeros((n_block, shape[0], SHARD_WORDS), np.uint32)
            for i in range(lo, min(lo + n_block, n)):
                # staged_dense: re-stages after an HBM eviction copy from
                # the host staging cache instead of re-expanding the
                # sparse store (read-only — the slice-assign copies)
                dense = frs[i].staged_dense()
                r = min(dense.shape[0], shape[0])  # cap may race a grow
                block[i - lo, :r] = dense[:r]
            return bitset.to_tile(block)

        if self.multiprocess:
            def cb(index):
                s = index[0]
                lo = s.start or 0
                hi = s.stop if s.stop is not None else bucket
                return fill(hi - lo, lo)

            return jax.make_array_from_callback(
                (bucket,) + shape, sharding, cb)
        return jax.device_put(fill(bucket, 0), sharding)

    def _frag_sig(self, fr) -> tuple:
        """Per-fragment group-signature entry.  Multi-process meshes pin
        the dense form — their staging must stay deterministic across
        processes, and remote placeholder fragments have no packed data
        to ship."""
        if self.multiprocess:
            return (fr.n_rows,) + WORD_TILE
        return fr.device_sig()

    def _place_packed_block(self, frs, sig):
        """Compressed staging: pad each member fragment's packed
        container stream to the group's shape buckets and place the seven
        stacked table/payload/entry arrays mesh-sharded (ops/containers.py).
        Transfers move compressed bytes, so there is no warm-mirror
        stacking variant — re-shipping a packed stream is already far
        cheaper than a dense stack ever was."""
        from ..ops.containers import ARRAY_CLASSES, ARRAY_PAD
        cb, pb, ab = sig[2], sig[3], sig[4]
        n = len(frs)
        bucket = self._bucket(n)
        keys = np.full((bucket, cb), -1, dtype=np.int32)
        types = np.full((bucket, cb), -1, dtype=np.int32)
        counts = np.zeros((bucket, cb), dtype=np.int32)
        offsets = np.zeros((bucket, cb), dtype=np.int32)
        payload = np.zeros((bucket, pb), dtype=np.uint32)
        a_idx = np.full((bucket, ARRAY_CLASSES, ab), ARRAY_PAD,
                        dtype=np.int32)
        a_val = np.zeros((bucket, ARRAY_CLASSES, ab), dtype=np.uint32)
        for i, fr in enumerate(frs):
            p = fr.packed_host()
            # a concurrent write may race the signature; clamping to the
            # signature's buckets mirrors the dense path's slice-to-shape
            # (the stale token rebuilds the stack on the next query)
            c = min(p.keys.size, cb)
            pw = min(p.payload.size, pb)
            aw = min(p.a_len, ab)
            keys[i, :c] = p.keys[:c]
            types[i, :c] = p.types[:c]
            counts[i, :c] = p.counts[:c]
            offsets[i, :c] = p.offsets[:c]
            payload[i, :pw] = p.payload[:pw]
            a_idx[i, :, :aw] = p.a_idx[:, :aw]
            a_val[i, :, :aw] = p.a_val[:, :aw]
        sharding = NamedSharding(self.mesh, P(SHARD_AXIS))
        return tuple(jax.device_put(a, sharding) for a in (
            keys, types, counts, offsets, payload, a_idx, a_val))

    @staticmethod
    def _present(keys, placed, sig):
        return [(k, a, s) for k, a, s in zip(keys, placed, sig)
                if s is not None]

    # -- out-of-core shard streaming --------------------------------------

    # Slice target as a fraction of the budget: half, so the next slice
    # can stage (double-buffered) while the current one computes without
    # the pair exceeding the limit.
    STREAM_SLICE_FRACTION = 0.5

    def _estimate_shard_bytes(self, keys, holder, index, shards):
        """Per-shard (resident, decode-workspace) byte estimates over
        ``keys`` (bucket padding excluded: this sizes slices, padding is
        zeros shared across them).  Resident counts each fragment's
        device-resident form — compressed bytes for compressed-form
        fragments, the dense tensor otherwise — which is what occupies
        the budget between launches; decode counts the transient dense
        tiles a launch materialises while decoding compressed inputs
        (bounded separately by DECODE_WORKSPACE_BYTES)."""
        res, dec = [], []
        for shard in shards:
            b = d = 0
            for field, view in keys:
                fr = holder.fragment(index, field, view, shard)
                if fr is not None:
                    dense = fr.n_rows * SHARD_WORDS * 4
                    nb = fr.device_nbytes() if not self.multiprocess \
                        else dense
                    b += nb
                    if nb < dense:
                        d += dense
            res.append(b)
            dec.append(d)
        return res, dec

    # slice plans kept (``_slice_plans``): a plan is a few KB, a program
    # and a shard list a key
    SLICE_PLANS_MAX = 256

    def shard_schedule(self, holder, index, key_lists, shards):
        """Residency-aware shard-group schedule for a dispatch that will
        stack ``key_lists`` (one key list per distinct stacked block) over
        ``shards``.

        While the node's dense set fits the device budget
        (``DeviceBudget.dense_fits``: no limit, or every open fragment's
        dense form inside it), and on a multi-process mesh, whose staging
        must stay deterministic across processes, the answer is ONE slice
        — the whole shard list, with cache keys identical to the
        pre-streaming path — and no fragment is looked at.  A set that
        does not fit is carved into contiguous slices of at most
        STREAM_SLICE_FRACTION of the budget; slices already resident are
        ordered FIRST so a batch drains all work against staged data
        before rotating the budget, and iteration prefetches slice k+1
        while slice k dispatches.  The cuts come from the fragments'
        device forms, so they are kept per (index, keys, shards) while
        the device epoch of those keys stands, as ``_placed_groups``
        keeps a stack: a repeat launch walks nothing."""
        shards = list(shards)
        limit = self._budget.limit_bytes
        slices = [shards]
        if limit and not self._budget.dense_fits() and \
                not self.multiprocess and len(shards) > self.n_devices:
            # bytes are estimated over the union of the lists' keys: a
            # (field, view) that two lists stack is one resident block
            all_keys = list(dict.fromkeys(
                k for kl in key_lists for k in kl))
            # the limit is one device's, and a stacked block lies over
            # the mesh in equal shares (one shape a group, the bucket a
            # multiple of the devices): the shards of a slice may hold
            # the limit times the devices between them
            slices = self._slice_plan(holder, index, all_keys, shards,
                                      limit * self.n_devices)
            if len(slices) > 1:
                # drain resident slices first (stable within each
                # class so rotation order stays deterministic)
                res = [all(self._is_resident(kl, holder, index, sl)
                           for kl in key_lists) for sl in slices]
                slices = [sl for sl, r in zip(slices, res) if r] + \
                    [sl for sl, r in zip(slices, res) if not r]
        return _ShardSchedule(self, holder, index, key_lists, slices)

    def _slice_plan(self, holder, index, keys, shards, limit) -> list:
        """The contiguous cuts of ``shards`` for a working set over
        ``limit`` bytes, from the per-shard estimates; served from
        ``_slice_plans`` while the device epoch read BEFORE the walk
        that made them stands (the order ``_place_groups`` argues for)
        and the limit and the workspace are what they were."""
        ws = max(1, DECODE_WORKSPACE_BYTES)
        epoch = holder.device_epoch(index, keys)
        pkey = (index, tuple(keys), tuple(shards))
        plan = self._slice_plans.get(pkey)
        if plan is not None and plan[:3] == (epoch, limit, ws):
            self.schedule_fast_hits += 1
            return plan[3]
        self.schedule_walks += 1
        per, dec = self._estimate_shard_bytes(keys, holder, index, shards)
        slices = [shards]
        if sum(per) > limit or sum(dec) > ws:
            target = max(1, int(limit * self.STREAM_SLICE_FRACTION))
            # contiguous cuts, deterministic for a given (shards,
            # limit) so repeat queries hit the same slice cache keys;
            # never below n_devices shards per slice — _bucket would
            # pad a smaller slice back to a full mesh width of zero
            # blocks, re-inflating the memory the cut tried to save.
            # Two ceilings: resident bytes against the streaming
            # target (rotating the budget) and decoded dense bytes
            # against the per-launch workspace — a fully-resident
            # compressed working set still slices by the latter, so
            # one launch never materialises more dense tiles than
            # the workspace allows (rotation is then free: every
            # slice's compressed stack stays resident).
            slices, cur, cur_b, cur_d = [], [], 0, 0
            for s, b, d in zip(shards, per, dec):
                if (cur_b + b > target or cur_d + d > ws) and \
                        len(cur) >= self.n_devices:
                    slices.append(cur)
                    cur, cur_b, cur_d = [], 0, 0
                cur.append(s)
                cur_b += b
                cur_d += d
            if slices and len(cur) < self.n_devices:
                slices[-1].extend(cur)  # tail can't fill the mesh
            elif cur:
                slices.append(cur)
            slices = self._even_cut(slices, per, dec, target, ws)
        if len(self._slice_plans) >= self.SLICE_PLANS_MAX:
            self._slice_plans.clear()
        self._slice_plans[pkey] = (epoch, limit, ws, slices)
        return slices

    def _even_cut(self, slices, per, dec, target, ws) -> list:
        """As many slices as the greedy cut made, of one length or two
        that differ by a shard: they go to one shard bucket and run one
        compiled program, where a short tail slice compiled its own for
        every program and batch size (ROADMAP S6).  Kept only where
        every even slice holds the two ceilings and fills the mesh;
        otherwise the greedy cut stands."""
        k = len(slices)
        n = sum(len(sl) for sl in slices)
        q, r = divmod(n, k)
        if k < 2 or q < self.n_devices:
            return slices
        shards = [s for sl in slices for s in sl]
        even, lo = [], 0
        for i in range(k):
            hi = lo + q + (i < r)
            if sum(per[lo:hi]) > target or sum(dec[lo:hi]) > ws:
                return slices
            even.append(shards[lo:hi])
            lo = hi
        return even

    def _pin_stack(self, keys, index, shard_slice) -> list:
        """Pin the blocks of this (keys, shard slice) stack; returns
        the budget keys pinned, for the caller to unpin."""
        with self._sc_lock:
            cached = self._stack_cache.get(
                (index, tuple(keys), tuple(shard_slice)))
        return [b.skey for b in (cached[4] if cached else ())
                if self._budget.pin(b.skey)]

    def _stream_groups(self, keys, holder, index, shards):
        """``_placed_groups`` over the streaming schedule: the default
        iteration surface for every dispatch entry point.  Single-slice
        schedules (the common, fits-in-budget case) behave exactly like a
        direct ``_placed_groups`` call."""
        for sl in self.shard_schedule(holder, index, [keys], shards):
            yield from self._placed_groups(keys, holder, index, sl)

    # -- the per-stage launcher -------------------------------------------

    # Max combos per group_counts launch: bounds the [S_local, chunk,
    # rows] int32 intermediate (8 stacked shards x 256 combos x 1024 rows
    # = 8 MB) so a large odometer cannot OOM HBM; full chunks share one
    # executable.  A GroupBy past one chunk is cut by the executor
    # (``_execute_group_by``), one node a chunk.
    GROUP_CHUNK = 256

    def reduce_async(self, node, mat, holder, index, shards,
                     scheduled: bool = False) -> tuple[list, list]:
        """Launch one reducer node (parallel/nodes.py) over ``shards``,
        per stage: one executable invocation per shape group (and per
        shard slice of an over-budget working set).  ``mat`` is the
        node's int32 params matrix [B, P] — a single call is B = 1, B
        same-shape calls ride one vmapped invocation — or, for
        group_counts, the pair (prefix row ids [C, Pk], filter params
        [P]); the combo axis is padded to a power of two here.

        Walks ``_stream_groups`` unless the caller owns the slice
        schedule (``scheduled``: _run_batched_groups and the batcher's
        fused launch pass pre-scheduled shard slices — re-scheduling
        would re-walk the holder per (group x chunk)).

        Returns (parts, groups): the unfetched device outputs of the
        groups that contribute, batch axis leading — [B, ...] summed
        over the shards (count, row_counts, bsi_sum, group_counts), or
        per shard: segments [S, B, 256, 128], bsi_minmax three arrays
        (bits, neg, cnt) a group — and those groups' shard lists, in the
        same order.  jax's async dispatch lets a batch of calls overlap
        on device; ``Executor.execute`` fetches all calls' parts once."""
        keys = node_keys(node)
        rows = None
        if node.kind == "group_counts":
            rids = np.asarray(mat[0], dtype=np.int32)
            rows = rids.shape[0]
            mat = (jnp.asarray(pad_pow2_rows(rids, repeat=False)),
                   jnp.asarray(mat[1]))
        else:
            mat = jnp.asarray(mat)
        walk = self._placed_groups if scheduled else self._stream_groups
        parts, groups = [], []
        for shard_list, placed, sig in walk(keys, holder, index, shards):
            if not participates(node, dict(zip(keys, sig))):
                continue
            present = self._present(keys, placed, sig)
            flat, layout = _flatten_present(present)
            key = self._plan_key(node.kind, node.plan,
                                 (k for k, _, _ in present),
                                 (s for _, _, s in present),
                                 extra=node.extra)
            fn = self._cache.get(key)
            if fn is None:
                fn = self._build(key, node, layout)
            # (shards, C): group_counts' pow-2 combo padding must count
            # as padding waste, not actual work
            meta = len(shard_list) if rows is None \
                else (len(shard_list), rows)
            with _DISPATCH_LOCK:
                out = fn(mat, *flat, _launch_meta=meta)
            if isinstance(out, tuple):
                parts.extend(out)
            else:
                parts.append(out)
            groups.append(shard_list)
        return parts, groups

    def _build(self, key, node, layout):
        """The per-stage executable of ``node`` over one shape group:
        ``nodes.node_shard`` — the body the whole-query program traces —
        over the group's layout (``node_over_layout``), vmapped over the
        device's shards under one of two combines, by
        the kind: summed over them and ``psum``ed to every device, or
        left per shard (gathered over the shard axis on a multi-process
        mesh, where no process can address them all).  Everything the
        traced closures read is an argument or a single assignment of
        this call, so a re-trace (another batch size, another shard
        bucket) reads what the first trace read."""
        n_flat = sum(n for _, n, _ in layout)
        per_shard_out = node.kind in PER_SHARD_KINDS
        gather = per_shard_out and self.multiprocess

        def block_fn(mat, *arrays):
            def per_shard(mat, *shard):
                return node_over_layout(node, layout, mat, shard, arrays)

            outs = jax.vmap(per_shard, in_axes=(None,) + (0,) * n_flat)(
                mat, *arrays)                          # [S_local, ...]
            if gather:
                return jax.tree_util.tree_map(
                    lambda o: jax.lax.all_gather(o, SHARD_AXIS,
                                                 tiled=True), outs)
            if per_shard_out:
                return outs
            return jax.lax.psum(jnp.sum(outs, axis=0),
                                axis_name=SHARD_AXIS)

        return self._jit_shard_map(
            key, block_fn, (P(),) + (P(SHARD_AXIS),) * n_flat,
            P(SHARD_AXIS) if per_shard_out and not gather else P(),
            check_vma=not gather, layout=layout, node=node)

    @staticmethod
    def merge_counts(parts) -> np.ndarray:
        """Sum per-group count vectors of differing lengths (shape groups
        have different row capacities)."""
        from ..executor.results import acc_counts
        acc = np.zeros(0, dtype=np.int64)
        for p in parts:
            acc = acc_counts(acc, np.asarray(p, dtype=np.int64))
        return acc


class _ShardSchedule:
    """Iterable of shard slices with prefetch + pinning.

    While the consumer stages and dispatches against slice k, a background
    uploader stages slice k+1 (host dense expansion + device placement off
    the critical path).  Both the in-use and the prefetched slices' budget
    entries are pinned so concurrent staging cannot evict them mid-use;
    pins release as each slice's dispatch completes (jax holds its own
    references to enqueued computations from then on)."""

    def __init__(self, mexec, holder, index, key_lists, slices):
        self.mexec = mexec
        self.holder = holder
        self.index = index
        self.key_lists = key_lists
        self.slices = slices

    @property
    def max_slice_len(self) -> int:
        return max((len(s) for s in self.slices), default=0)

    def _stage(self, shard_slice, pos: int) -> list[tuple]:
        """Stage every key list's stack for slice ``pos`` and pin the
        entries; returns the pinned budget keys (for the iterator to
        release after the slice's dispatch).  The launch ledger's and
        the annotations' slice position is set here, in whichever
        thread stages (the prefetch's has a context of its own): what
        that thread places and launches from now on is slice ``pos``'s.
        On a mid-stage failure (device OOM, fragment closed
        concurrently) every pin taken so far is released before
        re-raising — a leaked pin would shrink the effective budget for
        the process lifetime."""
        _devobs.set_slice(pos, len(self.slices))
        pinned = []
        try:
            for kl in self.key_lists:
                self.mexec._placed_groups(kl, self.holder, self.index,
                                          shard_slice)
                pinned += self.mexec._pin_stack(kl, self.index,
                                                shard_slice)
        except BaseException:
            for k in pinned:
                self.mexec._budget.unpin(k)
            raise
        return pinned

    def _slice_event(self, prof, i, sl, t0, up0, ev0):
        """One per-shard-slice profile stage: dispatch wall time plus the
        device-budget upload/evict deltas the slice drove — the
        streaming half of the EXPLAIN ANALYZE tree
        (docs/observability.md)."""
        budget = self.mexec._budget
        prof.event("device.slice", _time.perf_counter() - t0,
                   slice=i, shards=len(sl),
                   uploadBytes=budget.upload_bytes - up0,
                   evictions=budget.evictions - ev0)

    def __iter__(self):
        # Deadline + failpoint gate per slice: an expired query aborts
        # BETWEEN shard slices (check_current raises DeadlineExceeded;
        # the finally below releases pins, so partial device work is
        # freed, docs/robustness.md) instead of running to completion.
        prof = qprof.current()
        budget = self.mexec._budget
        if len(self.slices) <= 1:
            try:
                for sl in self.slices:
                    FAULTS.hit("mesh.slice", key=self.index)
                    check_current("mesh shard slice")
                    _devobs.set_slice(0, 1)
                    if prof is None:
                        yield sl
                    else:
                        t0, up0, ev0 = (_time.perf_counter(),
                                        budget.upload_bytes,
                                        budget.evictions)
                        yield sl
                        self._slice_event(prof, 0, sl, t0, up0, ev0)
            finally:
                _devobs.set_slice(None)
            return
        pool = self.mexec._uploader_pool()
        fut = None   # in-flight prefetch of the slice about to be served
        pins: list = []
        try:
            for i, sl in enumerate(self.slices):
                FAULTS.hit("mesh.slice", key=self.index)
                check_current("mesh shard slice")
                t0, up0, ev0 = (_time.perf_counter(), budget.upload_bytes,
                                budget.evictions)
                if fut is not None:
                    # prefetch-hit means the uploader finished BEFORE the
                    # consumer got here (checked via done() — result()
                    # blocks, so checking afterwards would report a hit
                    # even when streaming serialized on the upload) and
                    # the stacks are still token-valid
                    done = fut.done()
                    try:
                        pins.extend(fut.result())
                        budget.note_prefetch(done and all(
                            self.mexec._is_resident(kl, self.holder,
                                                    self.index, sl)
                            for kl in self.key_lists))
                    except (Exception, futures.CancelledError):
                        # CancelledError (a BaseException since 3.8):
                        # close() cancelling queued prefetches mid-query
                        # must degrade to inline staging, not abort
                        budget.note_prefetch(False)
                    fut = None
                # cold slices stage here; prefetched ones hit the cache
                pins.extend(self._stage(sl, i))
                if i + 1 < len(self.slices):
                    # the trace context crosses the uploader-pool
                    # boundary with the prefetch (orphan staging work
                    # would otherwise be untraceable)
                    fut = pool.submit(
                        GLOBAL_TRACER.task(self._stage,
                                           name="mesh.prefetch_slice"),
                        self.slices[i + 1], i + 1)
                yield sl
                # the consumer dispatched against this slice between the
                # yield and here — safe to let the budget rotate it out
                if prof is not None:
                    self._slice_event(prof, i, sl, t0, up0, ev0)
                for k in pins:
                    budget.unpin(k)
                pins = []
        finally:
            _devobs.set_slice(None)
            for k in pins:
                budget.unpin(k)
            if fut is not None:
                try:
                    for k in fut.result():
                        budget.unpin(k)
                # lint: allow(swallowed-exception) — unpin cleanup in a
                # finally; a failed prefetch already surfaces as a stage
                # miss (budget.prefetch_misses) and a re-upload
                except (Exception, futures.CancelledError):
                    pass

