"""Result fetch: a launch's device outputs cross to the host.

A lone launch's parts are device arrays and its request thread fetches
them in one ``jax.device_get`` (``fetch_parts``).  A fused launch
(parallel/batcher.py) answers many tickets from one set of outputs; its
reduced outputs — ``count`` [B], ``bsi_sum`` [B, 2, D + 1],
``row_counts`` [B, R]: a few KiB for the whole pack — are fetched ONCE
(``SharedFetch``) and every ticket's part is a ``HostView``, the numpy
slice ``host[lo:lo + b]`` of that copy.  Handing the tickets their
views (``DispatchBatcher._scatter``) enqueues nothing on the device: no
eager slice a ticket, no transfer a ticket, and nothing for the
collective-launch lock to order.

The one rule (docs/batching.md): kinds in ``nodes.PER_SHARD_KINDS``
keep a device slice per ticket — a ``segments`` output is
[S, B, 256, 128], and a shared fetch would make each ticket wait for,
and pin, every other ticket's rows; reduced kinds are fetched once.
"""

from __future__ import annotations

import jax
import numpy as np

from ..utils import devobs
from ..utils.locks import make_lock


class SharedFetch:
    """A fused launch's reduced outputs, fetched to the host once.

    The first request thread to ask performs the one ``device_get``
    under the launch's own once-lock; the others block on that lock
    (the GIL released) and find the host copy.  The dispatcher thread
    never asks: it only starts the copy behind the program, so that it
    stays free to enqueue the next launch.  A fetch that raises is kept
    and raised to every ticket of the launch."""

    __slots__ = ("_arrays", "_host", "_error", "_once")

    def __init__(self, arrays):
        self._arrays = arrays
        self._host = None
        self._error = None
        self._once = make_lock("shared-fetch")
        for a in self._arrays:
            # the copy trails its own program; asked for when a request
            # thread resolves, it would queue behind the next launch
            a.copy_to_host_async()

    def host(self) -> list[np.ndarray]:
        """The outputs' host copies, in the order given."""
        if self._host is None and self._error is None:
            with self._once:
                if self._host is None and self._error is None:
                    devobs.FETCHES.transfers += 1
                    try:
                        host = jax.device_get(self._arrays)
                    except Exception as e:
                        self._error, self._arrays = e, None
                        raise
                    # the device arrays go: theirs is the device's to free
                    self._host, self._arrays = host, None
                    return host
        if self._error is not None:
            raise self._error
        devobs.FETCHES.shared_tickets += 1
        return self._host


class HostView:
    """One ticket's rows of one shared output: ``host[lo:lo + b]`` of
    output ``j`` once its launch's ``SharedFetch`` has crossed."""

    __slots__ = ("shared", "j", "lo", "b")

    def __init__(self, shared: SharedFetch, j: int, lo: int, b: int):
        self.shared = shared
        self.j = j
        self.lo = lo
        self.b = b


def fetch_parts(parts) -> list[np.ndarray]:
    """Host copies of ``parts``, in order: the device arrays among them
    in one ``jax.device_get`` (one transfer round trip where N serial
    fetches pay N), each ``HostView`` as its slice of its launch's
    shared copy — fetched here if no other ticket's thread has yet,
    waited for if one is."""
    device = [p for p in parts if not isinstance(p, HostView)]
    if device:
        devobs.FETCHES.transfers += 1
        device = iter(jax.device_get(device))
    shared: dict[int, list[np.ndarray]] = {}
    out = []
    for p in parts:
        if isinstance(p, HostView):
            host = shared.get(id(p.shared))
            if host is None:
                host = shared[id(p.shared)] = p.shared.host()
            out.append(host[p.j][p.lo:p.lo + p.b])
        else:
            out.append(next(device))
    return out
