"""Project invariant analyzer tests (docs/static-analysis.md).

Golden bad-snippet fixtures per AST rule — each rule must catch its
motivating historical bug SHAPE (the PR 7 traced-closure loop capture,
the PR 6 anti-entropy swallow), reject the fixed spelling, and honor a
reasoned inline suppression — plus the lock-order detector's seeded
inversion (must report) and benign nesting (must not), and the
whole-tree invariant that the analyzer exits clean on this checkout.
"""

import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from pilosa_tpu.analysis import lockcheck
from pilosa_tpu.analysis.astlint import (
    Suppressions,
    lint_source,
    run as run_analysis,
)

REPO_ROOT = Path(__file__).resolve().parents[1]


def lint(src, *rules, rel="pilosa_tpu/executor/snippet.py"):
    return lint_source(textwrap.dedent(src), list(rules), rel=rel)


# -- traced-closure (the PR 7 silent-retrace bug shape) ---------------------

PR7_BUG = """
    import jax

    def segments_batch(self, groups):
        out = {}
        for shard_list, layout in groups:
            def per_shard(params, *arrays):
                # BUG: `layout` is read from the closure; a re-trace
                # after the loop moved on decodes with the WRONG buckets
                return unpack(layout, arrays)
            out[shard_list] = jax.jit(per_shard)
        return out
"""

PR7_FIXED = """
    import jax

    def segments_batch(self, groups):
        out = {}
        for shard_list, layout in groups:
            def per_shard(params, *arrays, _layout=layout):
                return unpack(_layout, arrays)
            out[shard_list] = jax.jit(per_shard)
        return out
"""


def test_traced_closure_catches_pr7_loop_capture():
    findings = lint(PR7_BUG, "traced-closure")
    assert len(findings) == 1
    assert "layout" in findings[0].message
    assert "loop-carried" in findings[0].message


def test_traced_closure_frozen_default_is_clean():
    assert lint(PR7_FIXED, "traced-closure") == []


def test_traced_closure_reassigned_local():
    src = """
        import jax
        def build(xs):
            acc = 0
            acc = prep(xs)
            fn = jax.jit(lambda p: p + acc)
            return fn
    """
    findings = lint(src, "traced-closure")
    assert len(findings) == 1
    assert "reassigned" in findings[0].message


def test_traced_closure_single_assignment_is_clean():
    src = """
        import jax
        def build(xs):
            table = prep(xs)
            return jax.jit(lambda p: p + table)
    """
    assert lint(src, "traced-closure") == []


def test_traced_closure_name_passed_to_wrapper():
    src = """
        import jax
        def build(groups):
            for layout in groups:
                def body(p):
                    return decode(layout, p)
                fn = jax.vmap(body)
            return fn
    """
    assert len(lint(src, "traced-closure")) == 1


def test_traced_closure_suppressed():
    src = PR7_BUG.replace(
        "return unpack(layout, arrays)",
        "# lint: allow(traced-closure) — executable never cached\n"
        "                return unpack(layout, arrays)")
    assert lint(src, "traced-closure") == []


# -- wall-clock -------------------------------------------------------------


def test_wallclock_flags_time_time():
    src = """
        import time
        def span_start():
            return time.time()
    """
    assert len(lint(src, "wall-clock")) == 1


def test_wallclock_catches_aliased_imports_the_grep_missed():
    src = """
        from time import time as now
        import time as t
        def f():
            return now() + t.time()
    """
    assert len(lint(src, "wall-clock")) == 2


def test_wallclock_perf_counter_and_wall_stamp_clean():
    src = """
        import time
        def _wall_stamp():
            return time.time()
        def dur():
            return time.perf_counter()
    """
    assert lint(src, "wall-clock") == []


def test_inline_allow_does_not_leak_to_next_line():
    src = """
        import time
        def f():
            a = time.time()  # lint: allow(wall-clock) — display stamp
            b = time.time()
            return a, b
    """
    findings = lint(src, "wall-clock")
    assert len(findings) == 1  # only the un-suppressed second call


def test_wallclock_suppressed_with_reason():
    src = """
        import time
        def f():
            # lint: allow(wall-clock) — uptime display only
            return time.time()
    """
    assert lint(src, "wall-clock") == []


# -- bare-except / swallowed-exception (the PR 6 AE-swallow shape) ----------

PR6_BUG = """
    def sync_shard(self, nid):
        try:
            self.fetch_blocks(nid)
        except Exception:
            pass  # a failed poll now LOOKS like a clean pass
"""


def test_swallow_catches_pr6_shape():
    findings = lint(PR6_BUG, "swallowed-exception")
    assert len(findings) == 1
    assert "swallows" in findings[0].message


def test_swallow_logged_counted_or_raised_is_clean():
    src = """
        def f(self):
            try:
                work()
            except Exception as e:
                self.logger.event("sync.failed", err=str(e))
        def g(self):
            try:
                work()
            except Exception:
                self.stats.count("errors")
        def h(self):
            try:
                work()
            except Exception:
                raise RuntimeError("wrapped")
        def k(self):
            try:
                work()
            except Exception as e:
                return None, e
    """
    assert lint(src, "swallowed-exception") == []


def test_swallow_matches_word_stems_not_substrings():
    # 'down' ⊄ shutdown, list.count is not a stat — both still swallow
    src = """
        def f(sock):
            try:
                work()
            except Exception:
                sock.shutdown()
        def g(xs):
            try:
                work()
            except Exception:
                n = xs.count(1)
    """
    assert len(lint(src, "swallowed-exception")) == 2


def test_bare_except_flagged_and_named_clean():
    assert len(lint("try:\n    x()\nexcept:\n    pass\n",
                    "bare-except")) == 1
    assert lint("try:\n    x()\nexcept OSError:\n    pass\n",
                "bare-except") == []


def test_swallow_suppressed_with_reason():
    src = """
        def close_all(conns):
            for c in conns:
                try:
                    c.close()
                # lint: allow(swallowed-exception) — teardown close
                except Exception:
                    pass
    """
    assert lint(src, "swallowed-exception") == []


# -- batcher-bypass ---------------------------------------------------------


def test_batcher_bypass_direct_dispatch_flagged():
    src = """
        def run(self, plan):
            return self.executor.mesh.reduce_async(node, mat)
    """
    assert len(lint(src, "batcher-bypass")) == 1


def test_batcher_bypass_alias_tracking_beats_the_grep():
    src = """
        def run(self, plan):
            m = MeshExecutor()
            return m.reduce_async(node, mat)
    """
    assert len(lint(src, "batcher-bypass")) == 1


def test_batcher_bypass_allowed_inside_parallel_and_via_batcher():
    src = """
        def run(self, plan):
            return self.mesh.reduce_async(node, mat)
    """
    assert lint(src, "batcher-bypass",
                rel="pilosa_tpu/parallel/batcher.py") == []
    via = """
        def run(self, plan):
            return self.batcher.reduce(node, mat)
    """
    assert lint(via, "batcher-bypass") == []


# -- thread-context ---------------------------------------------------------


def test_thread_context_unattached_target_flagged():
    src = """
        def fan_out(self, pool):
            def work(shard):
                with qprof.stage("slice"):
                    return run(shard)
            return pool.submit(work, 1)
    """
    assert len(lint(src, "thread-context")) == 1


def test_thread_context_attached_target_clean():
    src = """
        def fan_out(self, pool, tracer):
            ctx = tracer.capture()
            def work(shard):
                with tracer.attach(ctx):
                    with qprof.stage("slice"):
                        return run(shard)
            return pool.submit(work, 1)
    """
    assert lint(src, "thread-context") == []


def test_thread_context_task_wrapped_callsite_clean():
    src = """
        def fan_out(self, pool, tracer):
            def work(shard):
                with qprof.stage("slice"):
                    return run(shard)
            return pool.submit(tracer.task(work), 1)
    """
    assert lint(src, "thread-context") == []


# -- suppression hygiene ----------------------------------------------------


def test_suppression_without_reason_is_recorded():
    sup = Suppressions("x = 1  # lint: allow(wall-clock)\n")
    assert sup.missing_reason and sup.missing_reason[0][0] == 1


def test_docstring_text_is_not_a_suppression():
    sup = Suppressions('"""docs: # lint: allow(wall-clock) — nope"""\n')
    assert sup.by_line == {}


# -- project rules on a synthetic tree --------------------------------------


def _mini_tree(tmp_path, extra_test="", catalog="| `a.b` | x |"):
    pkg = tmp_path / "pilosa_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(
        'FAULTS.hit("fragment.wal", key="k")\n'
        'stats.count("a.b")\n')
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "observability.md").write_text(
        "<!-- metrics-catalog:begin -->\n"
        f"{catalog}\n"
        "<!-- metrics-catalog:end -->\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_x.py").write_text(extra_test)
    return tmp_path


def test_failpoint_typo_flagged_and_real_name_clean(tmp_path):
    root = _mini_tree(
        tmp_path,
        # the bad spec is split with a `+` so THIS file's constants
        # can't match the spec shape; the generated mini-tree file
        # still contains the full typo'd literal
        extra_test='FAULTS.arm("fragment.waal")\n'
                   'FAULTS.arm("fragment.wal")\n'
                   'SPEC = "fragment.wall' + '=kill:2"\n')
    findings = [f for f in run_analysis(root, ["failpoint-names"])]
    names = {f.message.split("'")[1] for f in findings}
    assert names == {"fragment.waal", "fragment.wall"}


def test_metrics_docs_two_way(tmp_path):
    root = _mini_tree(tmp_path, catalog="| `a.b` | x |\n| `dang.ling` | y |")
    (root / "pilosa_tpu" / "mod2.py").write_text(
        'mystats.count("un.documented")\n')
    findings = run_analysis(root, ["metrics-docs"])
    msgs = " | ".join(f.message for f in findings)
    assert "un.documented" in msgs
    assert "dang.ling" in msgs
    assert "a.b" not in msgs


# -- the tree itself is clean (the analyzer-exits-0 acceptance gate) --------


def test_repo_tree_is_clean():
    findings = run_analysis(REPO_ROOT)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_unknown_rule_id_errors():
    # a typo'd --rule must not silently analyze nothing and exit 0
    with pytest.raises(ValueError, match="traced-closur "):
        run_analysis(REPO_ROOT, ["traced-closur"])


# -- lockcheck: runtime lock-order race detector ----------------------------


@pytest.fixture
def clean_graph():
    lockcheck.GRAPH.reset()
    yield
    lockcheck.GRAPH.reset()


def _abba(lock_a, lock_b):
    import threading
    import time as _t
    bar = threading.Barrier(2)

    def one(x, y):
        with x:
            bar.wait()
            _t.sleep(0.01)
            if y.acquire(timeout=0.5):
                y.release()

    t1 = threading.Thread(target=one, args=(lock_a, lock_b))
    t2 = threading.Thread(target=one, args=(lock_b, lock_a))
    t1.start(), t2.start()
    t1.join(), t2.join()


def test_seeded_inversion_is_reported(clean_graph):
    _abba(lockcheck.CheckedLock("alpha"), lockcheck.CheckedLock("beta"))
    rep = lockcheck.report()
    kinds = {v["kind"] for v in rep["violations"]}
    assert "order-inversion" in kinds
    detail = next(v["detail"] for v in rep["violations"]
                  if v["kind"] == "order-inversion")
    assert "alpha" in detail and "beta" in detail


def test_benign_consistent_nesting_is_not_reported(clean_graph):
    import threading
    a, b = lockcheck.CheckedRLock("holder"), lockcheck.CheckedRLock("frag")

    def nest():
        with a:
            with b:
                pass

    ts = [threading.Thread(target=nest) for _ in range(4)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    rep = lockcheck.report()
    assert rep["violations"] == []
    assert any(e["from"] == "holder" and e["to"] == "frag"
               for e in rep["edges"])


def test_same_class_nesting_flagged_unless_declared(clean_graph):
    f1, f2 = lockcheck.CheckedRLock("fragment"), \
        lockcheck.CheckedRLock("fragment")
    with f1:
        with f2:
            pass
    kinds = {v["kind"] for v in lockcheck.report()["violations"]}
    assert "same-class-nesting" in kinds

    lockcheck.GRAPH.reset()
    s1, s2 = lockcheck.CheckedLock("stats"), lockcheck.CheckedLock("stats")
    with s1:
        with s2:
            pass
    assert lockcheck.report()["violations"] == []


def test_rlock_reentrancy_and_condition_bookkeeping(clean_graph):
    import threading
    rl = lockcheck.CheckedRLock("holder")
    with rl:
        with rl:
            pass
    assert lockcheck.report()["violations"] == []

    cond = lockcheck.checked_condition("committer")
    hits = []

    def waiter():
        with cond:
            cond.wait(timeout=2)
            hits.append(1)

    t = threading.Thread(target=waiter)
    t.start()
    import time as _t
    _t.sleep(0.05)
    with cond:
        cond.notify_all()
    t.join()
    assert hits == [1]


def test_cross_thread_handoff_does_not_fabricate_edges(clean_graph):
    import threading
    a = lockcheck.CheckedLock("handoff")
    b = lockcheck.CheckedLock("other")
    a.acquire()
    t = threading.Thread(target=a.release)  # legal for threading.Lock
    t.start()
    t.join()
    with b:  # the stale 'handoff' stack entry must be pruned, not held
        pass
    rep = lockcheck.report()
    assert rep["violations"] == []
    assert not any(e["from"] == "handoff" for e in rep["edges"])


def test_unarmed_factories_return_plain_primitives():
    import threading
    from pilosa_tpu.utils import locks
    if locks.ARMED:
        pytest.skip("process runs with PILOSA_TPU_LOCKCHECK armed")
    assert isinstance(locks.make_lock("x"), type(threading.Lock()))
    rep = locks.report()
    assert rep["armed"] is False


STRICT_SCRIPT = """
import threading, time
from pilosa_tpu.utils import locks

a = locks.make_lock("alpha")
b = locks.make_lock("beta")
bar = threading.Barrier(2)

def one(x, y):
    with x:
        bar.wait()
        time.sleep(0.01)
        if y.acquire(timeout=0.5):
            y.release()

t1 = threading.Thread(target=one, args=(a, b))
t2 = threading.Thread(target=one, args=(b, a))
t1.start(); t2.start(); t1.join(); t2.join()
print("body done")
"""


def test_strict_mode_fails_process_on_seeded_inversion():
    """The CI contract: a strict-armed process with an inversion dies
    loudly at exit (after the test body itself passed)."""
    proc = subprocess.run(
        [sys.executable, "-c", STRICT_SCRIPT],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
             "PILOSA_TPU_LOCKCHECK": "strict"})
    assert "body done" in proc.stdout
    assert proc.returncode == 70, proc.stderr
    assert "order-inversion" in proc.stderr


def test_observe_mode_reports_but_exits_zero():
    proc = subprocess.run(
        [sys.executable, "-c", STRICT_SCRIPT],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
             "PILOSA_TPU_LOCKCHECK": "1"})
    assert proc.returncode == 0, proc.stderr
    assert "order-inversion" in proc.stderr
