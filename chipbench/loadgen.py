"""Open-loop HTTP load generator, run as a child process that never imports
JAX, so the process that serves keeps the chip to itself.

It reads one job from stdin (JSON):

    {"port": 8080, "connections": 64, "timeout_s": 60,
     "requests": [[phase, offset_s, path, body], ...],
     "starts": {"warmup": <monotonic s>, "window": <monotonic s>}}

and sends each request when it is due (its phase's start plus its offset),
on the first free keep-alive connection.  A request waits only while every
connection is busy.  When all have answered, or ``timeout_s`` after the last
was due, it writes one JSON object to stdout:
``{"records": [[i, due, sent, done, status, body], ...]}`` with monotonic
seconds, ``status`` 0 for a transport error and ``null`` for no answer.
After ``ready`` on stdout it waits for the job's ``starts`` on stdin.
"""

from __future__ import annotations

import http.client
import json
import queue
import sys
import threading
import time


def _worker(port: int, jobs: "queue.Queue", records: list, timeout_s: float) -> None:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
    conn.connect()
    while True:
        item = jobs.get()
        if item is None:
            break
        i, due, path, body = item
        sent = time.monotonic()
        try:
            conn.request("POST", path, body=body.encode(),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read().decode()
            records[i] = [i, due, sent, time.monotonic(), resp.status, data]
        except (OSError, http.client.HTTPException) as e:
            records[i] = [i, due, sent, time.monotonic(), 0, repr(e)]
            conn.close()
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
    conn.close()


def run(job: dict, starts: dict) -> list:
    reqs = job["requests"]
    records: list = [None] * len(reqs)
    jobs: "queue.Queue" = queue.Queue()
    threads = [threading.Thread(target=_worker, daemon=True,
                                args=(job["port"], jobs, records, job["timeout_s"]))
               for _ in range(job["connections"])]
    for t in threads:
        t.start()
    order = sorted(range(len(reqs)), key=lambda i: starts[reqs[i][0]] + reqs[i][1])
    last_due = 0.0
    for i in order:
        phase, offset, path, body = reqs[i]
        due = starts[phase] + offset
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        jobs.put((i, due, path, body))
        last_due = due
    for _ in threads:
        jobs.put(None)
    deadline = last_due + job["timeout_s"]
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    return [r if r is not None else [i, starts[reqs[i][0]] + reqs[i][1], None, None, None, ""]
            for i, r in enumerate(records)]


def main() -> int:
    job = json.loads(sys.stdin.readline())
    print("ready", flush=True)
    starts = json.loads(sys.stdin.readline())
    out = run(job, starts)
    sys.stdout.write(json.dumps({"records": out}) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
