"""Whole-query pjit programs: ONE XLA computation per PQL request.

PAPER.md's stated design is that "PQL calls (Intersect/Union/TopN/
GroupBy/Count) compile to a single XLA computation per request" — the
pjit/PartitionSpec pattern of SNIPPETS.md [1][3].  The per-stage path
(``MeshExecutor.reduce_async``) launches one shard_map executable per
reducer node per shape group, with a Python hop between every PQL
stage; this module compiles the ENTIRE parsed request — every call,
every shape group, the PR 7 container decode, and the cross-shard
reductions — into one jitted program over global mesh-sharded arrays
(docs/whole-query.md).  Both trace the same node bodies: what a
reducer computes on a shard, which keys it stacks and which shape
groups it skips are defined once, in parallel/nodes.py.

Mechanics: the executor lowers a read query to a tuple of
``plan.ReduceNode``s (Count popcount-sums, TopN/Rows row-count
accumulations, BSI slice counts, Min/Max extremum scans, GroupBy combo
grids, raw segments) plus one params matrix per node.  ``run`` stacks
the request's fragment inputs with the SAME residency machinery the
per-stage path uses — ``MeshExecutor._placed_groups`` with its stack
cache, device-budget accounting, compressed staging, and ingest
overlays all compose unchanged — and places them sharded over the
named ``shards`` mesh axis (``PartitionSpec(SHARD_AXIS)``); params ride
replicated (``P()``).  The whole program is ONE ``shard_map`` over
that axis: the body decodes compressed stacks once per shape group,
evaluates every node's per-shard contribution in one vmapped pass over
the device-local block, and reduces IN PROGRAM — local sums +
``lax.psum`` over the shard axis, the shape groups combined in the
program where the per-stage path returns a part a group.  (Manual partitioning on purpose:
auto-partitioned jit replicates the vmapped row-gathers — a 4096-wide
Count batch allocated a 279 GB gather temp — while shard_map pins the
per-device shapes the batch-temp bound is held against.)  Where the
compiler's own figure for a program's temporaries
(``memory_analysis().temp_size_in_bytes`` of the executable about to
run, read once per compiled shape) would pass the bound over all of a
device's stacked shards, the body walks them in blocks (``lax.map``
over a shard block, the per-shard parts laid end to end as the whole
pass lays them), so the temporaries are one block's however many shards
are stacked.  One
launch per request — the launch ledger (utils/devobs.py) records it as
kind ``wholequery``.

Shapes the program cannot express raise ``WholeQueryUnsupported`` and
the executor reroutes to the per-stage dispatch, counting
``wholequery.fallback`` (docs/whole-query.md has the fallback matrix):
multi-process meshes (per-process staging must stay deterministic),
over-budget working sets (the streaming slice planner owns those),
params batches whose temporaries do not fit the bound over one stacked
shard, and GroupBy grids beyond one combo chunk.

Batching (docs/batching.md): concurrent requests whose programs share a
shape fuse in the dispatch batcher by concatenating each node's params
matrix along the batch axis — the batched parameter axis rides the same
compiled program, so the PR 4 fused-launch economics carry over
unchanged.
"""

from __future__ import annotations

import time as _time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..executor.plan import eval_plan
from ..ops import bsi
from ..utils import devobs as _devobs
from ..utils import profile as qprof
from ..utils.deadline import check_current
from ..utils.faults import FAULTS
from ..utils.tracing import layer_span
from . import nodes
from .mesh_exec import _DISPATCH_LOCK, _flatten_present, _unpack_frags, \
    SHARD_AXIS, form_tag, launch_cost, node_over_layout, slice_tags


class WholeQueryUnsupported(Exception):
    """A request (or runtime shape) the whole-query program cannot
    express.  The executor counts ``wholequery.fallback``, emits a
    structured log event naming the unsupported node, and reroutes to
    the per-stage dispatch — never a silent slow path."""

    def __init__(self, node: str, detail: str = ""):
        super().__init__(f"{node}: {detail}" if detail else node)
        self.node = node
        self.detail = detail


def program_keys(program) -> list[tuple[str, str]]:
    """Union of every node's keys, order-deterministic — the single
    stacked key list the whole request stages (and the shard schedule
    prefetches) once."""
    return list(dict.fromkeys(
        k for node in program for k in nodes.node_keys(node)))


PROGRAM_NAME_NODES = 6   # node kinds spelled out in a program's name


def program_name(program) -> str:
    """``ptpu_wq_<node kinds joined>``: the whole-query program's name
    in a profiler trace (``jit_ptpu_wq_count``).  A function of the node
    kinds alone — never of the digest, the shapes or the executor — so
    the persistent compile cache sees one key per program."""
    kinds = [n.kind for n in program]
    name = "ptpu_wq_" + "_".join(kinds[:PROGRAM_NAME_NODES])
    if len(kinds) > PROGRAM_NAME_NODES:
        name += f"_n{len(kinds)}"
    return name


def walk_nodes(program, live, sched, pad_mats) -> dict:
    """{node index: (live group, position of the primary's stack among
    the group's arrays)} of the top-n nodes this launch may answer by
    ``nodes.topn_walk``: the program reduces every shard the call was
    given, so what is left to ask is that the node reads ONE shape
    group, that every stack of the group is dense (a compressed one is
    decoded a shard at a time, inside the per-shard pass), and that the
    params rows are few enough to unroll.  Static per program and
    shapes, as the compile key is."""
    out = {}
    for ni, node in enumerate(program):
        if node.extra != nodes.TOPN_EXTRA or len(sched[ni]) != 1 \
                or pad_mats[ni].shape[0] > nodes.TOPN_WALK_ROWS:
            continue
        layout = live[sched[ni][0]][3]
        if all(n == 1 for _k, n, _sig in layout):
            out[ni] = (sched[ni][0],
                       [k for k, _n, _sig in layout].index(node.primary))
    return out


def _divisor_at_most(n: int, m: int) -> int:
    """The largest divisor of ``n`` that is at most ``m`` (m >= 1)."""
    return next(d for d in range(min(n, m), 0, -1) if n % d == 0)


class WholeOut:
    """One whole-query launch's unfetched device outputs.

    ``parts[i]`` is node i's device arrays (unfetched, so the executor
    keeps its dispatch-all-then-fetch-once pipeline; to a ticket of a
    fused launch, views of the launch's one shared fetch); ``meta[i]``
    carries the host-assembly facts the finalizers need (per-group
    shard lists, fragment-less shards, actual batch rows)."""

    __slots__ = ("parts", "meta", "sig", "compiled")

    def __init__(self, parts, meta, sig: str | None = None,
                 compiled: bool = False):
        self.parts = parts
        self.meta = meta
        # compiled program signature (devobs.sig_of of the executable
        # cache key — the SAME id the compile registry and launch ledger
        # record), surfaced on the request thread for the EXPLAIN plan
        # section; None for the no-live-groups empty launch
        self.sig = sig
        # True when THIS launch traced+compiled (a cold program); the
        # EXPLAIN plan section surfaces it as plan: warm|cold so a
        # post-deploy compile is visible per request (docs/warmup.md)
        self.compiled = compiled

    def for_ticket(self, parts, spans):
        """A fused launch's output as one of its tickets sees it:
        ``parts`` are the ticket's own (``DispatchBatcher._scatter``:
        views of the launch's shared fetch, a device slice for a
        per-shard kind), ``spans[ni]`` its (first row, rows) on node
        ni's batch axis."""
        meta = [dict(m, B=b) for m, (_lo, b) in zip(self.meta, spans)]
        return WholeOut(parts, meta, self.sig, self.compiled)


class _InstrumentedWhole:
    """One compiled whole-query program plus its device-runtime
    telemetry — the wholequery analog of mesh_exec._InstrumentedExec:
    the traced body marks the compile registry (exact retrace
    detection), and every invocation lands in the launch ledger with
    the call site's actual-vs-padded shard and batch rows."""

    __slots__ = ("fn", "sig", "detail", "out_index", "devices", "form",
                 "_temps")

    def __init__(self, fn, key, out_index, devices: int):
        self.fn = fn
        self.devices = devices      # of the mesh the program runs over
        self.sig = _devobs.sig_of(key)
        self.detail = repr(key[1])[:120]
        # ``dense`` | ``z:<backend>``, off the groups' signatures in the key
        self.form = form_tag(s for _, sigs in key[2] for s in sigs)
        self.out_index = out_index
        # device-local stacked shards -> the compiler's temp bytes
        self._temps: dict = {}

    def temp_bytes(self, local, mats, flat) -> tuple[int, bool]:
        """The compiler's own figure for this program's temporaries on
        a device, at ``local`` stacked shards a group:
        ``memory_analysis().temp_size_in_bytes`` of the executable a
        call with these arguments runs.  jax keeps one trace, lowering
        and executable for ``lower().compile()`` and for the call, so
        the figure costs the compile the first launch of the shape
        would have paid and that launch then compiles nothing.  Returns
        (bytes, whether this call traced); a traced one is folded into
        the compile registry here."""
        temp = self._temps.get(local)
        if temp is not None:
            return temp, False
        reg = _devobs.COMPILES
        reg.begin_call()
        t0 = _time.perf_counter()
        analysis = self.fn.lower(mats, *flat).compile().memory_analysis()
        traced = reg.traced()
        if traced:
            leaves = jax.tree_util.tree_leaves(mats)
            reg.note_call(self.sig, "wholequery",
                          _time.perf_counter() - t0,
                          _devobs.fingerprint(list(leaves) + list(flat)),
                          detail=self.detail)
        temp = self._temps[local] = int(
            getattr(analysis, "temp_size_in_bytes", 0) or 0)
        return temp, traced

    def __call__(self, mats, *flat, _launch_meta=None):
        m = _launch_meta or {}
        ctx = _devobs.launch_ctx() or {}
        rows = ctx.get("rows")
        if rows is None:
            rows = m.get("rows", 1)
        rows_padded = m.get("rows_padded", 1)
        tickets = ctx.get("tickets", 1)
        reg = _devobs.COMPILES
        reg.begin_call()
        # dispatch.enqueue, as in mesh_exec._InstrumentedExec
        with layer_span("dispatch.enqueue", kind="wholequery",
                        sig=self.sig, rows=rows, rows_padded=rows_padded,
                        tickets=tickets, shards=m.get("shards", 0),
                        shards_padded=m.get("shards_padded", 0),
                        temp_bytes=m.get("temp_bytes", 0),
                        devices=self.devices, form=self.form,
                        **slice_tags()) as span:
            t0 = _time.perf_counter()
            out = self.fn(mats, *flat)
            dt = _time.perf_counter() - t0
            traced = reg.traced()
            # the launch's program was built when its temporaries were
            # read (``temp_bytes``), just before this call
            compiled = traced or m.get("compiled", False)
            span.tag(compiled=compiled)
        if traced:  # fingerprinting is only paid on compiles
            leaves = jax.tree_util.tree_leaves(mats)
            reg.note_call(self.sig, "wholequery", dt,
                          _devobs.fingerprint(list(leaves) + list(flat)),
                          detail=self.detail)
        _devobs.LEDGER.record(
            sig=self.sig, kind="wholequery",
            shards=m.get("shards", 0),
            shards_padded=m.get("shards_padded", 0),
            batch_rows=rows, batch_rows_padded=rows_padded,
            queue_s=ctx.get("queue_s", 0.0), tickets=tickets,
            dispatch_s=dt, compiled=compiled,
            decode_bytes=m.get("decode_bytes", 0),
            slice_pos=_devobs.current_slice(),
            kernel_launches=m.get("kernel_launches", 0),
            kernel_tiles=m.get("kernel_tiles", 0),
            kernel_steps=m.get("kernel_steps", 0),
            kernel_gathers=m.get("kernel_gathers", 0))
        prof = qprof.current()
        if prof is not None:
            # rows/padding/decode tags feed the EXPLAIN launches section
            # (utils/explain.py), mirroring the ledger entry
            prof.event("device.launch", dt, kind="wholequery",
                       sig=self.sig, shards=m.get("shards", 0),
                       shardsPadded=m.get("shards_padded", 0),
                       batchRows=rows, batchRowsPadded=rows_padded,
                       decodeBytes=m.get("decode_bytes", 0),
                       compiled=compiled)
        return out


def _over_shards(per_shard, arrs, block: int | None):
    """``per_shard`` over the leading (device-local shard) axis of
    ``arrs``: one vmapped pass over whatever that axis is where
    ``block`` is None (a bucket change re-traces it), else a ``lax.map``
    over blocks of ``block`` shards, a divisor of the axis, each a
    vmapped pass, their outputs laid end to end — the same
    [S_local, ...] parts with the temporaries of one block."""
    s_local = arrs[0].shape[0]
    if block is None or block == s_local:
        return jax.vmap(per_shard)(*arrs)
    blocked = tuple(a.reshape((s_local // block, block) + a.shape[1:])
                    for a in arrs)
    outs = jax.lax.map(lambda blk: jax.vmap(per_shard)(*blk), blocked)
    return jax.tree_util.tree_map(
        lambda o: o.reshape((s_local,) + o.shape[2:]), outs)


class WholeQueryRunner:
    """Compiles + launches whole-query programs over a MeshExecutor's
    mesh, reusing its stacked-input staging (stack cache, device
    budget, compressed residency, ingest overlays) and executable
    cache verbatim."""

    def __init__(self, mesh):
        self.mesh = mesh
        # (program repr, index) -> what a batch row of the program's
        # last launch cost, for the batcher's packer (``row_temp_bytes``)
        self._row_temp: dict = {}

    # -- shape probes ------------------------------------------------------

    ROW_TEMP_MAX = 1024     # programs the packer's figures are kept for

    def row_temp_bytes(self, program_repr: str, index: str) -> int:
        """Temp bytes one batch row of the program cost at its last
        launch, by the compiler's figure, had the device's shards been
        walked one at a time (the fewest a launch can take at once): the
        figure over the padded batch rows and the shards taken at once.
        0 before its first launch (a first pack fuses unweighed: its
        launch still sizes its blocks, or sends every ticket to the
        chunked path)."""
        return self._row_temp.get((program_repr, index), 0)

    def program_keys(self, program):
        return program_keys(program)

    def fusible(self, program) -> bool:
        return all(n.kind in nodes.BATCH_KINDS for n in program)

    def precheck(self, program, holder, index, shards):
        """Raise WholeQueryUnsupported for shapes the single-program
        path cannot take; returns the program's stacked key list."""
        mesh = self.mesh
        if mesh.multiprocess:
            raise WholeQueryUnsupported(
                "multiprocess-mesh",
                "per-process staging must stay deterministic")
        keys = self.program_keys(program)
        if keys and shards:
            sched = mesh.shard_schedule(holder, index, [keys], shards)
            if len(sched.slices) > 1:
                raise WholeQueryUnsupported(
                    "streamed-working-set",
                    f"{len(sched.slices)} shard slices")
        return keys

    # -- execution ---------------------------------------------------------

    def run(self, program, mats, holder, index, shards) -> WholeOut:
        """Stage the request's inputs and launch the whole program as
        one device computation.  ``mats`` is one int32 params matrix
        per node ([B, P]; group_counts nodes carry (rids[C, Pk],
        params[Pf])).  Returns unfetched device parts per node."""
        mesh = self.mesh
        keys = self.precheck(program, holder, index, shards)
        FAULTS.hit("mesh.slice", key=index)
        check_current("whole-query dispatch")
        groups = mesh._placed_groups(keys, holder, index, list(shards)) \
            if keys and shards else []

        live = []           # (shard_list, sig_map, flat, layout, pk, ps)
        empty_shards: list[int] = []
        for shard_list, placed, sig in groups:
            if all(s is None for s in sig):
                empty_shards.extend(shard_list)
                continue
            present = mesh._present(keys, placed, sig)
            flat_g, layout_g = _flatten_present(present)
            live.append((shard_list, dict(zip(keys, sig)), flat_g,
                         layout_g, tuple(k for k, _, _ in present),
                         tuple(s for _, _, s in present)))

        pad_mats = []
        actual_b = []
        for node, mat in zip(program, mats):
            if node.kind == "group_counts":
                rids, params = mat
                actual_b.append(rids.shape[0])
                pad_mats.append((nodes.pad_pow2_rows(
                    np.asarray(rids, dtype=np.int32), repeat=False),
                    np.asarray(params, dtype=np.int32)))
            else:
                m = np.ascontiguousarray(mat, dtype=np.int32)
                actual_b.append(m.shape[0])
                pad_mats.append(nodes.pad_pow2_rows(m))
        pad_mats = tuple(pad_mats)

        # per-node schedule: which live groups contribute (static)
        sched = tuple(
            tuple(gi for gi, g in enumerate(live)
                  if nodes.participates(node, g[1]))
            for node in program)
        meta = self._node_meta(program, actual_b, live, sched,
                               empty_shards)
        if not live:
            return WholeOut([[] for _ in program], meta)  # no launch

        # The shard-bucket (stacked leading dim) is deliberately NOT in
        # the key: like every mesh executable, a bucket change re-traces
        # the cached program — the compile registry's retrace red flag
        # (PR 8 convention; everything the body reads is frozen static
        # structure, so the re-trace is correct by construction).
        buckets = tuple(g[2][0].shape[0] for g in live)
        key = ("wholequery", repr(program),
               tuple((g[4], g[5]) for g in live),
               tuple(jax.tree_util.tree_map(lambda a: a.shape,
                                            pad_mats)),
               mesh._exec_seq)
        flat_all = [a for g in live for a in g[2]]
        # a top-n node's walk plan rides behind the stacks, replicated:
        # made from the very array the launch reads (mesh.walk_plan)
        walks = walk_nodes(program, live, sched, pad_mats)
        for ni, (gi, pos) in walks.items():
            flat_all.extend(mesh.walk_plan(
                index, program[ni].primary, live[gi][0], live[gi][2][pos]))
        local = tuple(b // mesh.n_devices for b in buckets)
        fn, blocks, temp_bytes, fresh = self._fit(
            key, local, program, live, sched, pad_mats, flat_all)
        if blocks is None:      # a shard-blocked program takes the full pass
            for ni in walks:
                meta[ni]["walk"] = True
        rows_padded = sum(nodes.mat_rows(m) for m in pad_mats)
        if len(self._row_temp) >= self.ROW_TEMP_MAX:
            self._row_temp.clear()      # the packer fuses unweighed once
        self._row_temp[key[1], index] = temp_bytes // (
            sum(blocks or local) * max(rows_padded, 1))
        if blocks is not None:
            mesh.temp_splits += 1

        # what each node's pass over each group decodes and which kernel
        # it runs: the per-stage launcher's own reckoning
        costs = [launch_cost(g[3], program[ni],
                             nodes.mat_rows(pad_mats[ni]), bucket, bucket)
                 for gi, (bucket, g) in enumerate(zip(buckets, live))
                 for ni in range(len(program)) if gi in sched[ni]]
        (decode_bytes, kernel_launches, kernel_tiles, kernel_steps,
         kernel_gathers) = (sum(c[k] for c in costs) for k in range(5))
        launch_meta = {
            "shards": sum(len(g[0]) for g in live),
            "shards_padded": sum(buckets),
            "rows": sum(actual_b),
            "rows_padded": rows_padded,
            "decode_bytes": decode_bytes,
            "kernel_launches": kernel_launches,
            "kernel_tiles": kernel_tiles,
            "kernel_steps": kernel_steps,
            "kernel_gathers": kernel_gathers,
            "temp_bytes": temp_bytes,
            "compiled": fresh,
        }
        # the params ride with the launch: jit places the host matrices
        # replicated (the program's in_specs say P()) as part of the
        # call, with no transfer or sync of their own
        with _DISPATCH_LOCK:
            flat_out = fn(pad_mats, *flat_all, _launch_meta=launch_meta)
        parts = [[flat_out[j] for j in idxs] for idxs in fn.out_index]
        # tracing is synchronous on this thread (CompileRegistry's
        # thread-local protocol), so the flag read here is exactly
        # whether THIS launch compiled — even when run() executes on the
        # batcher's dispatcher thread for a fused launch
        return WholeOut(parts, meta, fn.sig,
                        fresh or _devobs.COMPILES.traced())

    def _fit(self, key, local, program, live, sched, pad_mats, flat):
        """The executable this launch runs: the program over each
        group's whole share of the device's shards where the compiler's
        figure for its temporaries (``_InstrumentedWhole.temp_bytes``)
        fits the batch-temp bound, else over blocks of at most half the
        largest group's shards, a quarter, ... down to one (each group
        its largest divisor under the rung: every block has one shape),
        the first rung the figure fits at.  The ladder does not move
        with the bound, so no more than log2(shards) programs exist per
        shape; the whole figure only says where to start on it.  A
        blocked program's key carries ``local`` and its blocks (it
        cannot be re-traced at another bucket); a whole one's is what
        it always was.  Returns (fn, blocks or None, temp bytes, whether
        a program was built); raises ``batch-chunks`` where not even
        one shard at a time fits (the chunked path cuts the batch
        axis)."""
        mesh, bound = self.mesh, nodes.batch_temp_bound()
        blocks, fresh, top = None, False, max(local)
        while True:
            ckey = key if blocks is None else \
                key + (("blocks", local, blocks),)
            with mesh._lock:
                fn = mesh._cache.get(ckey)
                if fn is None:
                    fn = mesh._cache[ckey] = self._compile(
                        ckey, program, live, sched, pad_mats, blocks)
            temp, traced = fn.temp_bytes(local, pad_mats, flat)
            fresh |= traced
            if temp <= bound:
                return fn, blocks, temp, fresh
            rung = top // 2 if blocks is None else max(blocks) // 2
            if blocks is None:
                # temporaries grow about with the shards taken at once
                while rung > 1 and temp * rung > bound * top:
                    rung //= 2
            if rung < 1:
                raise WholeQueryUnsupported(
                    "batch-chunks",
                    f"B={max(nodes.mat_rows(m) for m in pad_mats)}")
            blocks = tuple(_divisor_at_most(s, rung) for s in local)

    def _node_meta(self, program, actual_b, live, sched, empty_shards):
        meta = []
        for ni, node in enumerate(program):
            m = {"B": actual_b[ni]}
            if node.kind == "segments":
                m["groups"] = [live[gi][0] for gi in sched[ni]]
                m["empty"] = list(empty_shards)
            elif node.kind == "bsi_minmax":
                m["groups"] = [live[gi][0] for gi in sched[ni]]
            meta.append(m)
        return meta

    # -- compilation -------------------------------------------------------

    def _compile(self, key, program, live, sched, pad_mats, blocks):
        """Build + jit the program body.  Everything consulted inside
        the traced body is frozen static structure (program nodes,
        layouts, participation schedule, combine shapes, shard blocks)
        — the body takes only (mats, *stacked arrays).  ``blocks`` is
        None where every group's per-shard pass takes the device's whole
        share of it, else ``blocks[gi]`` is the divisor of that share
        group gi is walked in."""
        groups_static = tuple((g[3], len(g[2])) for g in live)
        n_flat_all = sum(n for _, n in groups_static)
        sig_maps = tuple(g[1] for g in live)
        walk_at = walk_nodes(program, live, sched, pad_mats)
        # where each walked node's plan sits among the arguments
        walk_arg = {ni: n_flat_all + 2 * i for i, ni in enumerate(walk_at)}
        # the walk is outside the per-shard pass, which ``blocks`` cuts
        walks = walk_at if blocks is None else {}

        # per-node static combine targets (max rows / max BSI depth);
        # single-assignment so the traced body's closure cell can never
        # change under a re-trace (the PR 7 bug class)
        def _combine_info(ni, node):
            if node.kind in ("row_counts", "group_counts"):
                return {"rows": max(
                    (nodes.sig_rows(sig_maps[gi][node.primary])
                     for gi in sched[ni]), default=0)}
            if node.kind == "bsi_sum":
                return {"depth": max(
                    (nodes.sig_rows(sig_maps[gi][node.primary])
                     - bsi.OFFSET_ROW for gi in sched[ni]), default=0)}
            return {}

        combine = tuple(_combine_info(ni, node)
                        for ni, node in enumerate(program))

        def body(mats, *flat):
            # Inside shard_map: ``flat`` are the per-device LOCAL blocks
            # of the stacked arrays ([S_local, ...]); mats are
            # replicated.  Reductions sum locally and psum over the
            # named shard axis — the in-program collective that replaces
            # the per-stage path's host merge of a part a group.
            per_group_raw: list[dict] = [dict() for _ in groups_static]
            group_arrs = []
            i = 0
            for gi, (layout_g, n_g) in enumerate(groups_static):
                arrs = flat[i:i + n_g]
                group_arrs.append(arrs)
                i += n_g
                # a walked node's filter segments, one per-shard pass a
                # params row (unrolled: a row take under a batch axis
                # reads the whole stack); its counts are taken outside
                for ni, (wgi, _pos) in walks.items():
                    if wgi == gi:
                        per_group_raw[gi][ni] = [
                            _over_shards(
                                lambda *arrays, _l=layout_g, _n=ni, _b=b:
                                eval_plan(program[_n].plan,
                                          _unpack_frags(_l, arrays),
                                          mats[_n][_b]), arrs, None)
                            for b in range(mats[ni].shape[0])]
                node_ids = tuple(
                    ni for ni in range(len(program))
                    if gi in sched[ni] and ni not in walks)
                if not node_ids:
                    continue

                def per_shard(*arrays, _layout=layout_g,
                              _nis=node_ids, _arrs=arrs):
                    return tuple(
                        node_over_layout(program[ni], _layout, mats[ni],
                                         arrays, _arrs)
                        for ni in _nis)

                outs_g = _over_shards(
                    per_shard, arrs, blocks[gi] if blocks else None)
                for slot, ni in enumerate(node_ids):
                    per_group_raw[gi][ni] = outs_g[slot]

            flat_outs: list = []
            for ni, node in enumerate(program):
                parts = [per_group_raw[gi][ni] for gi in sched[ni]]
                if node.kind == "segments":
                    # [S_local, B, 256, 128] per group: the host
                    # flattens the tile after the fetch
                    flat_outs.extend(parts)
                elif node.kind == "bsi_minmax":
                    for p in parts:                 # (bits, neg, cnt)
                        flat_outs.extend(p)
                elif not parts:
                    pass                            # no contributing group
                elif ni in walks:
                    # [B, R] counts as far as they can matter, and the
                    # rows visited as one more column of the same output
                    gi, pos = walks[ni]
                    w0 = walk_arg[ni]
                    counts, visited = nodes.topn_walk(
                        group_arrs[gi][pos], parts[0], mats[ni][:, -1],
                        flat[w0], flat[w0 + 1], SHARD_AXIS)
                    flat_outs.append(jnp.concatenate(
                        [counts, jnp.broadcast_to(
                            visited, (counts.shape[0], 1))], axis=1))
                elif node.kind == "count":
                    total = parts[0].sum(axis=0)
                    for p in parts[1:]:
                        total = total + p.sum(axis=0)
                    flat_outs.append(
                        jax.lax.psum(total, axis_name=SHARD_AXIS))  # [B]
                elif node.kind == "bsi_sum":
                    D = combine[ni]["depth"]
                    B = mats[ni].shape[0]
                    acc = jnp.zeros((B, 2, D + 1), dtype=jnp.int32)
                    for p in parts:
                        s = p.sum(axis=0)           # [B, 2, d+1]
                        d = s.shape[-1] - 1
                        # magnitude counts and the trailing TOTAL column
                        # land separately: groups of different bit depth
                        # must not add a total into a magnitude slot
                        acc = acc.at[:, :, :d].add(s[:, :, :d])
                        acc = acc.at[:, :, D].add(s[:, :, d])
                    flat_outs.append(
                        jax.lax.psum(acc, axis_name=SHARD_AXIS))
                else:  # row_counts / group_counts
                    R = combine[ni]["rows"]
                    B = nodes.mat_rows(mats[ni])
                    acc = jnp.zeros((B, R), dtype=jnp.int32)
                    for p in parts:
                        s = p.sum(axis=0)           # [B, rows_g]
                        acc = acc.at[:, :s.shape[1]].add(s)
                    flat_outs.append(
                        jax.lax.psum(acc, axis_name=SHARD_AXIS))
            return tuple(flat_outs)

        # flat-output index map + per-output PartitionSpec, computed
        # statically from the schedule (mirrors body's append order):
        # reduced outputs are replicated (psum), per-shard outputs keep
        # the shard axis
        out_index: list[list[int]] = []
        out_specs: list = []
        n_out = 0
        for ni, node in enumerate(program):
            if node.kind in nodes.PER_SHARD_KINDS:
                n_here = len(sched[ni]) * (3 if node.kind == "bsi_minmax"
                                           else 1)
                out_specs.extend([P(SHARD_AXIS)] * n_here)
            else:
                n_here = 1 if sched[ni] else 0
                out_specs.extend([P()] * n_here)
            out_index.append(list(range(n_out, n_out + n_here)))
            n_out += n_here

        def traced(mats, *flat):
            # runs ONLY while jax traces: an exact compile detector
            _devobs.COMPILES.mark_traced()
            return body(mats, *flat)

        traced.__name__ = program_name(program)

        fn = jax.jit(jax.shard_map(
            traced, mesh=self.mesh.mesh,
            in_specs=(P(),) + (P(SHARD_AXIS),) * n_flat_all
            + (P(),) * (2 * len(walk_at)),
            out_specs=tuple(out_specs),
            # the replication checker has no rule for pallas_call and
            # trips over a conditional under vmap: off over compressed
            # stacks, as ``mesh_exec._jit_shard_map`` has it
            check_vma=not any(n > 1 for layout_g, _ in groups_static
                              for _, n, _ in layout_g)))
        return _InstrumentedWhole(fn, key, out_index, self.mesh.n_devices)
