"""Multi-host mode 2 across REAL processes (r4 verdict item 5).

Launches two jax.distributed CPU processes (4 virtual devices each) that
form one 8-device engine: each imports only its own shard slice, and the
full distributed query set — Count/Intersect/Row/TopN/Sum/Min/Max/Rows/
GroupBy — executes in SPMD lockstep with psum/all_gather collectives
crossing the process boundary.  See tests/multihost_worker.py for the
worker body (reference role: gossip/gossip.go + http/client.go node-to-
node engine)."""

import os
import socket
import subprocess
import sys

import pytest

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "multihost_worker.py")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_engine():
    port = _free_port()
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(port), str(i), "2"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env)
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out.decode())
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    # Environment sandbox (ROADMAP item 3): jaxlib builds whose CPU
    # backend implements no cross-process collectives make this test
    # un-runnable, not failing — the worker probes with a trivial psum
    # right after distributed init and exits 42 with an UNSUPPORTED
    # marker.  Skip with the real error so the reason is visible.
    for out in outs:
        for line in out.splitlines():
            if "MULTIHOST UNSUPPORTED" in line:
                pytest.skip(
                    "XLA CPU multiprocess collectives unsupported by "
                    f"this jaxlib: {line.split(':', 1)[-1].strip()}")
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-4000:]}"
        assert f"MULTIHOST OK proc={i}" in out, out[-2000:]
