"""Each host thread's CPU time by role, from the kernel's per-thread
accounting (docs/observability.md "Host threads").

``/debug/vars`` → ``hostThreads`` sums, per role, what
``/proc/self/task/<tid>/schedstat`` says of every thread of the process:
nanoseconds on a CPU and nanoseconds runnable but waiting for one.  A
thread that waits for the GIL, a lock or a sleeping C call is asleep,
so it adds to neither.  Where a kernel keeps no ``schedstat`` (gVisor,
for one) ``stat``'s utime + stime stand in, with no runqueue figure;
there, handing the GIL from thread to thread is itself booked as CPU
time (docs/observability.md).  The roles:

- ``request``: HTTP connection threads, registered by the handler as it
  sets a connection up;
- ``dispatcher``: the dispatch batcher's thread, registered by its loop;
- ``python``: every other thread ``threading.enumerate()`` knows;
- ``native``: tasks that belong to no Python thread (XLA, the runtime).

Nothing on the request or dispatch path reads a clock for this: a thread
registers once and retires once, and the kernel's counters are read only
when ``/debug/vars`` is.  A role's totals never go backwards: a
connection thread that ends hands its final figures to its role as it
retires, and a thread that is gone by the next read leaves its last
figures behind.  The arithmetic (``RoleTotals``) takes plain numbers.
"""

from __future__ import annotations

import ctypes
import os
import threading
import time

from .locks import make_lock

ROLES = ("request", "dispatcher", "python", "native")
TASKS = "/proc/self/task"


class RoleTotals:
    """Per-role cumulative CPU and runqueue nanoseconds, from readings of
    the live threads and the final figures of threads that retire.

    A thread counts ``figure - base`` towards its role.  A thread whose
    role changes (a connection thread seen before it registered, or
    after it retired) leaves what it had counted with the old role, and
    counts on from there in the new one; a thread that is gone, or whose
    counters went backwards (its id reused), leaves its last figures."""

    def __init__(self):
        self.retired = {r: [0, 0] for r in ROLES}
        # tid -> [role, base_cpu, base_rq, cpu, rq]
        self._seen: dict[int, list] = {}

    def _leave(self, tid: int):
        role, b_cpu, b_rq, cpu, rq = self._seen.pop(tid)
        acc = self.retired[role]
        acc[0] += cpu - b_cpu
        acc[1] += rq - b_rq

    def _note(self, tid: int, role: str, cpu: int, rq: int):
        e = self._seen.get(tid)
        if e is not None and (cpu < e[3] or rq < e[4]):
            self._leave(tid)                # another thread with its id
            e = None
        if e is None:
            self._seen[tid] = [role, 0, 0, cpu, rq]
        elif e[0] != role:
            self._leave(tid)
            self._seen[tid] = [role, e[3], e[4], cpu, rq]
        else:
            e[3], e[4] = cpu, rq

    def retire(self, tid: int, role: str, cpu: int, rq: int):
        """A thread of ``role`` hands over its final figures: they stay
        with the role, and anything the thread does after counts
        elsewhere."""
        e = self._seen.get(tid)
        if e is not None and e[0] == role:
            cpu, rq = max(cpu, e[3]), max(rq, e[4])
        self._note(tid, role, cpu, rq)
        self._leave(tid)
        self._seen[tid] = [role, cpu, rq, cpu, rq]

    def observe(self, readings: dict) -> dict:
        """``readings``: {tid: (role, cpu_ns, rq_ns)} of the live threads.
        Returns {role: [threads, cpu_ns, rq_ns]}."""
        for tid in [t for t in self._seen if t not in readings]:
            self._leave(tid)
        for tid, (role, cpu, rq) in readings.items():
            self._note(tid, role, cpu, rq)
        out = {r: [0, acc[0], acc[1]] for r, acc in self.retired.items()}
        for role, b_cpu, b_rq, cpu, rq in self._seen.values():
            o = out[role]
            o[0] += 1
            o[1] += cpu - b_cpu
            o[2] += rq - b_rq
        return out


def _libc_reader():
    """A whole-file read through libc called with the GIL held
    (``ctypes.PyDLL``), or None where libc cannot be loaded.  Python's
    own file calls release the GIL around each system call, and on a
    busy server the reader then queues for it again after every one of
    the three a task takes, so the tasks would be read far apart in
    time.  Held, the read is one short stretch (about 30 ms for 199
    tasks under load on a TPU host) that the other threads wait out."""
    try:
        libc = ctypes.PyDLL(None, use_errno=True)
        c_open, c_read, c_close = libc.open, libc.read, libc.close
    except (OSError, AttributeError):
        return None
    c_open.argtypes, c_open.restype = (ctypes.c_char_p, ctypes.c_int), \
        ctypes.c_int
    c_read.argtypes, c_read.restype = (
        ctypes.c_int, ctypes.c_void_p, ctypes.c_size_t), ctypes.c_ssize_t
    c_close.argtypes, c_close.restype = (ctypes.c_int,), ctypes.c_int

    def read(path: str) -> bytes:
        fd = c_open(path.encode(), os.O_RDONLY)
        if fd < 0:
            raise OSError(ctypes.get_errno(), "open", path)
        try:
            buf = ctypes.create_string_buffer(_READ_BYTES)
            n = c_read(fd, buf, _READ_BYTES)
        finally:
            c_close(fd)
        if n < 0:
            raise OSError(ctypes.get_errno(), "read", path)
        return buf.raw[:n]
    return read


def _py_read(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read(_READ_BYTES)


_READ_BYTES = 4096      # a task's stat or schedstat line is a few hundred
_read_file = _libc_reader() or _py_read


def _schedstat(tid: int):
    cpu, rq = _read_file(f"{TASKS}/{tid}/schedstat").split()[:2]
    return int(cpu), int(rq)


_TICK_NS = 1e9 / os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") \
    else 1e7


def _stat(tid: int):
    """utime + stime of ``stat`` (clock ticks), with no runqueue figure."""
    fields = _read_file(f"{TASKS}/{tid}/stat").rsplit(b")", 1)[1].split()
    return int((int(fields[11]) + int(fields[12])) * _TICK_NS), 0


def _source():
    if not os.path.isdir(TASKS):
        return None, None
    if os.path.exists(f"{TASKS}/{threading.get_native_id()}/schedstat"):
        return "schedstat", _schedstat
    return "stat", _stat


class HostThreads:
    """The process's registry of thread roles and its ``RoleTotals``."""

    def __init__(self):
        self._lock = make_lock("hostthreads")
        self._roles: dict[int, str] = {}
        self._totals = RoleTotals()
        self.source, self._read = _source()

    def register(self, role: str):
        """The calling thread takes ``role``."""
        with self._lock:
            self._roles[threading.get_native_id()] = role

    def retire(self):
        """The calling thread, as it ends, hands its figures to its
        role: one read of its counters."""
        tid = threading.get_native_id()
        fig = self._figures(tid)
        with self._lock:
            role = self._roles.pop(tid, None)
            if role is not None and fig is not None:
                self._totals.retire(tid, role, *fig)

    def _figures(self, tid: int):
        if self._read is None:
            return None
        try:
            return self._read(tid)
        except (OSError, ValueError, IndexError):
            return None             # ended between listing and reading

    def snapshot(self) -> dict | None:
        """``/debug/vars`` → ``hostThreads``; None without ``/proc``."""
        if self._read is None:
            return None
        t0 = time.perf_counter()
        with self._lock:
            python = {t.native_id for t in threading.enumerate()}
            readings = {}
            for name in os.listdir(TASKS):
                tid = int(name)
                fig = self._figures(tid)
                if fig is None:
                    continue
                role = self._roles.get(tid) or (
                    "python" if tid in python else "native")
                readings[tid] = (role, *fig)
            # registrations of threads that ended without retiring
            for tid in [t for t in self._roles if t not in readings]:
                del self._roles[tid]
            totals = self._totals.observe(readings)
        wall = time.perf_counter()
        rq = self.source == "schedstat"
        return {
            "wallSeconds": wall,
            "readSeconds": wall - t0,
            "source": self.source,
            "roles": {r: {"threads": n, "cpuSeconds": cpu / 1e9,
                          "runqueueSeconds": rq_ns / 1e9 if rq else None}
                      for r, (n, cpu, rq_ns) in totals.items()},
        }


HOST_THREADS = HostThreads()
