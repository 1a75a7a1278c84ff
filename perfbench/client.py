"""HTTP clients of the served path: streamed `POST /v1/chat/completions`.

Client threads never touch JAX. Each request is logged as a `Record`: when it
was sent, the arrival time of every streamed event and the text it
carried. Times are `time.perf_counter()` of this process. Parsing the text
back into token ids is left until the run is over.
"""

from __future__ import annotations

import http.client
import json
import socket
import threading
import time
from dataclasses import dataclass, field


@dataclass
class Record:
    req: object  # traffic.Request
    due: float  # perf_counter instant the caller had the request ready
    sent: float = 0.0
    status: int = 0
    events: list = field(default_factory=list)  # (arrival, raw "data: {...}" line)
    done: float = 0.0  # arrival of the closing event; 0 = never finished
    error: str = ""
    cancelled: bool = False
    # filled by `parse`
    token_times: list = field(default_factory=list)
    ids: list = field(default_factory=list)
    finish_reason: str = ""


class Clients:
    """Runs a schedule against 127.0.0.1:`port`."""

    def __init__(self, port: int, timeout_s: float = 600.0):
        self.port = port
        self.timeout_s = timeout_s
        self.records: list = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._conns: set = set()
        self._threads: list = []
        self._closed = 0  # closed-loop callers started

    # -- one request ---------------------------------------------------------

    def _body(self, req, max_tokens: int) -> bytes:
        body = {"messages": req.messages, "max_tokens": max_tokens, "stream": True}
        if req.greedy:
            body["temperature"] = 0.0
        else:
            body["seed"] = req.sample_seed
        return json.dumps(body).encode()

    def send(self, req, due: float) -> Record:
        rec = Record(req=req, due=due)
        with self._lock:
            self.records.append(rec)
        body = self._body(req, req.max_tokens)
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=self.timeout_s)
        sock = None
        try:
            # the server answers with `Connection: close`, so `conn` lets go of
            # its socket once the headers are in: `stop` needs the socket itself
            conn.connect()
            sock = conn.sock
            with self._lock:
                self._conns.add(sock)
            rec.sent = time.perf_counter()
            conn.request("POST", "/v1/chat/completions", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            rec.status = resp.status
            if resp.status != 200:
                rec.error = resp.read(300).decode("utf-8", "replace")
                return rec
            while True:
                line = resp.readline()
                if not line:
                    break
                if line.startswith(b"data: {"):
                    now = time.perf_counter()
                    rec.events.append((now, line))
                elif line.startswith(b"data: [DONE]"):
                    rec.done = time.perf_counter()
                    break
            if not rec.done and not self._stop.is_set():
                rec.error = "stream ended without [DONE]"
        except Exception as e:  # a failed request is a result
            if self._stop.is_set():
                rec.cancelled = True
            else:
                rec.error = f"{type(e).__name__}: {e}"
        finally:
            with self._lock:
                self._conns.discard(sock)
            try:
                conn.close()
            except Exception:
                pass
        if self._stop.is_set() and not rec.done:
            rec.cancelled = True
        return rec

    # -- loops -----------------------------------------------------------------

    def start_closed(self, client_lists: list) -> None:
        self._closed = len(client_lists)

        def loop(c: int, reqs: list):
            i = 0
            while not self._stop.is_set():
                self.send(reqs[i % len(reqs)], time.perf_counter())
                i += 1

        for c, reqs in enumerate(client_lists):
            t = threading.Thread(target=loop, args=(c, reqs), daemon=True)
            t.start()
            self._threads.append(t)

    def waiting_for_first_token(self) -> bool:
        """Closed loop: has some caller not yet had a streamed event?"""
        with self._lock:
            served = {r.req.client for r in self.records if r.events or r.done}
        return len(served) < self._closed

    def stop(self, join_s: float = 20.0) -> None:
        """Cancel what is in flight: close every socket, wait for the threads."""
        self._stop.set()
        with self._lock:
            conns = list(self._conns)
        for sock in conns:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass  # it closed on its own meanwhile
        deadline = time.perf_counter() + join_s
        for t in list(self._threads):
            t.join(timeout=max(0.0, deadline - time.perf_counter()))


def parse(records: list, vocab) -> None:
    """Events -> per-token arrival times and ids, finish reason."""
    for rec in records:
        for arrival, line in rec.events:
            try:
                choice = json.loads(line[6:])["choices"][0]
            except (ValueError, KeyError, IndexError):
                rec.error = rec.error or "unparseable event"
                continue
            text = (choice.get("delta") or {}).get("content")
            if text:
                try:
                    ids = vocab.ids(text)
                except (ValueError, KeyError) as e:
                    rec.error = rec.error or f"text is not in the vocabulary: {e}"
                    continue
                rec.ids.extend(ids)
                rec.token_times.extend([arrival] * len(ids))
            if choice.get("finish_reason"):
                rec.finish_reason = choice["finish_reason"]
        rec.events = []


def get_json(port: int, path: str, timeout: float = 120.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        raw = r.read()
        return r.status, (json.loads(raw) if r.status == 200 else raw[:300])
    finally:
        conn.close()
