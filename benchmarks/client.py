"""The load generator: streamed HTTP requests through the proxy, one
thread a stream, a host-clock stamp for every token. Runs in the
command's own process, which never touches jax.

`post_stream` is chip_smoke.py's `_post_stream` (PR 21), copied so that
the yardstick lives under the benchmark's paths.
"""

from __future__ import annotations

import http.client
import json
import threading
import time

from benchmarks.traffic import Request


class Stream:
    """One request's client-side record. Times are perf_counter seconds."""
    __slots__ = ("req", "due", "sent", "t", "tokens", "status",
                 "request_id", "error", "done")

    def __init__(self, req: Request, due: float):
        self.req = req
        self.due = due          # when it should have been sent
        self.sent = None
        self.t: list = []       # arrival of each token
        self.tokens: list = []
        self.status = None
        self.request_id = None
        self.error = None
        self.done = None


def post_stream(port: int, app: str, s: Stream, timeout: float = 900.0):
    s.sent = time.perf_counter()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", f"/{app}?stream=1", body=json.dumps(
            {"tokens": s.req.tokens,
             "max_new_tokens": s.req.max_new_tokens}),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        s.status = resp.status
        s.request_id = resp.getheader("X-Rayt-Request-Id")
        while True:
            line = resp.readline()
            if not line:
                break
            if line.startswith(b"data:"):
                item = json.loads(line[5:])
                if isinstance(item, dict) and "token" in item:
                    s.t.append(time.perf_counter())
                    s.tokens.append(item["token"])
                else:
                    s.error = item
        if s.status != 200 and s.error is None:
            s.error = f"http {s.status}"
    except Exception as e:  # recorded; the request counts as failed
        s.error = repr(e)
    finally:
        conn.close()
        s.done = time.perf_counter()


class OpenLoop:
    """Sends each request at start + due_s whether or not earlier ones
    have finished. `stop()` sends nothing more; streams in flight are
    left to their daemon threads."""

    def __init__(self, port: int, app: str, requests: list):
        self.port, self.app = port, app
        self.requests = requests
        self.streams: list = []
        self.start = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def begin(self):
        self.start = time.perf_counter()
        self._thread.start()

    def _run(self):
        for r in self.requests:
            due = self.start + r.due_s
            wait = due - time.perf_counter()
            if wait > 0 and self._stop.wait(wait):
                return
            if self._stop.is_set():
                return
            s = Stream(r, due)
            self.streams.append(s)
            threading.Thread(target=post_stream,
                             args=(self.port, self.app, s),
                             daemon=True).start()

    def stop(self):
        self._stop.set()
        self._thread.join(5)


class ClosedLoop:
    """`clients` callers, each sending its next request when the last
    one has ended. Requests come from one shared iterator."""

    def __init__(self, port: int, app: str, requests, clients: int):
        self.port, self.app = port, app
        self._it = requests
        self._lock = threading.Lock()
        self.streams: list = []
        self.start = None
        self._stop = threading.Event()
        self._threads = [threading.Thread(target=self._client, daemon=True)
                         for _ in range(clients)]

    def begin(self):
        self.start = time.perf_counter()
        for t in self._threads:
            t.start()

    def _client(self):
        while not self._stop.is_set():
            with self._lock:
                r = next(self._it)
                s = Stream(r, time.perf_counter())
                self.streams.append(s)
            post_stream(self.port, self.app, s)

    def stop(self):
        self._stop.set()
