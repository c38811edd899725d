"""The load generator: one child process of the benchmark, a closed loop
per client thread over plain ``http.client``.  It never imports jax (nor
the program, nor numpy), so the clients do not share the server's GIL.

Protocol, over stdin/stdout, one JSON object per line:
  in   {"port", "path", "bodies": [str], "sequences": [[int]]}
  out  {"ready": true}                      every connection dialled
  in   {"start": t, "end": t}               time.monotonic() of this host
  out  {"threads": [{"start": [], "end": [], "status": [], "request": [],
        "answer": []}], "answers": [str]}   one per phase
  in   {"quit": true}
Each client sends its next request when the last is answered, until
``end``; the request in flight at ``end`` is finished and reported.
"""

import http.client
import json
import sys
import threading
import time


def client_loop(port, path, bodies, seq, start, end, answers, lock, out):
    conn = out.get("conn")
    rec = out["rec"] = {"start": [], "end": [], "status": [], "request": [],
                        "answer": []}
    time.sleep(max(0.0, start - time.monotonic()))
    i = out.get("next", 0)
    while True:
        t0 = time.monotonic()
        if t0 >= end:
            break
        req = seq[i % len(seq)]
        i += 1
        try:
            if conn is None:
                conn = http.client.HTTPConnection("localhost", port,
                                                  timeout=120)
            conn.request("POST", path, body=bodies[req],
                         headers={"Content-Type": "text/plain"})
            resp = conn.getresponse()
            data = resp.read().decode("utf-8", "replace")
            status = resp.status
        except (OSError, http.client.HTTPException) as e:
            status, data = -1, repr(e)
            conn.close()
            conn = None
        t1 = time.monotonic()
        with lock:
            a = answers.setdefault(data, len(answers))
        rec["start"].append(t0)
        rec["end"].append(t1)
        rec["status"].append(status)
        rec["request"].append(req)
        rec["answer"].append(a)
    out["conn"], out["next"] = conn, i


def main() -> int:
    job = json.loads(sys.stdin.readline())
    port, path = job["port"], job["path"]
    bodies = [b.encode() for b in job["bodies"]]
    state = [{} for _ in job["sequences"]]
    # dial one at a time: the server listens with a backlog of 5
    for st in state:
        st["conn"] = http.client.HTTPConnection("localhost", port,
                                                timeout=120)
        st["conn"].connect()
    print(json.dumps({"ready": True}), flush=True)
    for line in sys.stdin:
        phase = json.loads(line)
        if phase.get("quit"):
            break
        answers: dict = {}
        lock = threading.Lock()
        threads = [threading.Thread(
            target=client_loop,
            args=(port, path, bodies, seq, phase["start"], phase["end"],
                  answers, lock, st))
            for seq, st in zip(job["sequences"], state)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        print(json.dumps({"threads": [st.pop("rec") for st in state],
                          "answers": list(answers)}), flush=True)
    for st in state:
        if st.get("conn") is not None:
            st["conn"].close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
