"""The closed-loop clients of a decode cell, run as a child process that never
touches a chip (`JAX_PLATFORMS=cpu` in its environment), so that they do not
share the interpreter lock with the server's scheduler thread.

Protocol, lines on stdin and stdout: the parent writes one JSON line (`host`,
`port`, `clients`, `requests`); the child connects its clients and answers
`ready`; the parent writes `go`, later `stop`; the child lets the requests in
flight finish, prints one JSON line of results and exits.  Each client sends
its next request when the reply to the last has arrived.  Times are
`time.monotonic()`, which parent and child share on one host.
"""

import json
import sys
import threading
import time


def main():
    from tensorframes_tpu.bridge import BridgeClient

    job = json.loads(sys.stdin.readline())
    requests = job["requests"]
    clients = [BridgeClient(job["host"], job["port"]) for _ in range(job["clients"])]
    lock, state, results = threading.Lock(), {"next": 0, "stop": False}, []

    def loop(client):
        while True:
            with lock:
                if state["stop"] or state["next"] >= len(requests):
                    return
                i = state["next"]
                state["next"] += 1
            rec = {"i": i, "sent": time.monotonic()}
            try:
                rec["tokens"] = client.decode(requests[i]["prompt"], requests[i]["max_new"])["tokens"]
            except Exception as e:  # a refused or failed request is a result, not a crash
                rec["error"] = f"{type(e).__name__}: {e}"[:200]
            rec["done"] = time.monotonic()
            with lock:
                results.append(rec)

    threads = [threading.Thread(target=loop, args=(c,), daemon=True) for c in clients]
    print("ready", flush=True)
    if sys.stdin.readline().strip() != "go":
        return 1
    for t in threads:
        t.start()
    sys.stdin.readline()  # "stop", or end of file if the parent died
    with lock:
        state["stop"] = True
    for t in threads:
        t.join()
    for c in clients:
        c.close()
    print(json.dumps(results), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
