"""The system under test, opened as its users open it, and a plain HTTP
client of it.  Copied from ``chip_smoke.py`` (``open_server``, ``Client``,
the device check), not imported: the yardstick lives under ``benchmark/``.
"""

from __future__ import annotations

import http.client
import json
import threading
import time


def device_info() -> dict:
    """Platform, kind and count as jax reports them."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Client:
    """Plain HTTP client of one server: a keep-alive connection per
    thread, JSON in and out.  No jax."""

    # the server drops a keep-alive connection idle for 120 s; a client
    # redials long before that rather than retrying a request
    IDLE_REDIAL_S = 30.0

    def __init__(self, port: int):
        self.port = port
        self._local = threading.local()

    def connection(self) -> http.client.HTTPConnection:
        conn = getattr(self._local, "conn", None)
        now = time.monotonic()
        if conn is not None and \
                now - self._local.used > self.IDLE_REDIAL_S:
            conn.close()
            conn = None
        if conn is None:
            conn = self._local.conn = http.client.HTTPConnection(
                "localhost", self.port, timeout=600)
        self._local.used = now
        return conn

    def request(self, method: str, path: str, body=None,
                ctype: str = "application/json"):
        conn = self.connection()
        if isinstance(body, (dict, list)):
            body = json.dumps(body).encode()
        elif isinstance(body, str):
            body = body.encode()
        conn.request(method, path, body=body,
                     headers={"Content-Type": ctype} if body else {})
        resp = conn.getresponse()
        data = resp.read()
        if resp.status != 200:
            raise RuntimeError(
                f"{method} {path} -> {resp.status}: {data[:2000]!r}")
        return json.loads(data) if data.strip() else {}

    def query(self, index: str, pql: str) -> list:
        return self.request("POST", f"/index/{index}/query", pql,
                            ctype="text/plain")["results"]

    def debug_vars(self) -> dict:
        return self.request("GET", "/debug/vars")


def open_server(data_dir: str, device: dict):
    """A ``Server`` exactly as ``python -m pilosa_tpu server`` builds it
    (``Config.from_env``), every option at its default, on a free port.
    Returns (server, client) once every node reports READY and the
    server names the device jax named."""
    from pilosa_tpu.server.server import Config, Server
    srv = Server(Config.from_env(data_dir=data_dir, bind="localhost:0"))
    srv.open()
    client = Client(srv.port)
    deadline = time.monotonic() + 300
    while True:
        nodes = client.request("GET", "/status")["nodes"]
        if all(n["state"] == "READY" for n in nodes):
            break
        if time.monotonic() > deadline:
            srv.close()
            raise RuntimeError(f"server never reported READY: {nodes}")
        time.sleep(0.05)
    dev = client.debug_vars()["device"]
    served = {"platform": dev["platform"], "kind": dev["deviceKind"],
              "count": dev["deviceCount"]}
    if served != device:
        srv.close()
        raise RuntimeError(f"/debug/vars names {served}, jax {device}")
    return srv, client
