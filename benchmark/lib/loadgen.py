"""Load generator: drives ``POST /v1/completions`` with ``"stream": true``
and stamps every token with the host's monotonic clock as the client reads
it. Copied in spirit from ``paddle_tpu/serving/workload.py`` (the runners) and
``soak._http_submit`` (the SSE adapter), bounded by a window, not by a count.

A closed loop keeps ``clients`` callers each waiting for its answer; an open
loop sends on the schedule whatever the system does, and records how late
each send ran. When the window closes, live connections are shut: the gateway
cancels their engine work, and they count as cancelled, never as failed.

The generator runs in a process of its own (``LoadGenProcess`` starts this
file as a script; it imports no JAX and never touches the chip), so that its
threads do not take the interpreter lock from the served stack: the clients
of a real deployment are not in the server's process either. Both processes
read ``time.monotonic()``, which is one clock for the whole machine.
"""
from __future__ import annotations

import http.client
import json
import os
import socket
import subprocess
import sys
import threading
import time


class LoadGen:
    def __init__(self, host, port, source, *, clock=time.monotonic):
        self.host, self.port = host, port
        self.source = source
        self.clock = clock
        self.records = []
        self._lock = threading.Lock()
        self._live = set()
        self._stop = threading.Event()
        self._threads = []
        self._first_tokens = 0
        self._first_cv = threading.Condition()

    # -- one request -------------------------------------------------------
    def _run_one(self, req, t_due):
        req.t_due = t_due
        body = json.dumps({"prompt": req.prompt, "max_tokens": req.max_tokens,
                           "temperature": 0.0, "seed": 0, "stream": True})
        conn = http.client.HTTPConnection(self.host, self.port, timeout=120)
        with self._lock:
            self.records.append(req)
        sock = None
        try:
            req.t_send = self.clock()
            conn.request("POST", "/v1/completions", body,
                         {"Content-Type": "application/json"})
            # a streamed response takes the socket over from the connection:
            # keep hold of it so that stop() can shut it
            sock = conn.sock
            with self._lock:
                self._live.add(sock)
            if self._stop.is_set():
                raise OSError("window closed before the request was read")
            resp = conn.getresponse()
            if resp.status != 200:
                req.status = "failed"
                req.error = f"HTTP {resp.status}: {resp.read()[:200]!r}"
                return
            while True:
                line = resp.readline()
                if not line:
                    break
                if not line.startswith(b"data: "):
                    continue
                payload = line[6:].strip()
                if payload == b"[DONE]":
                    break
                now = self.clock()
                doc = json.loads(payload)
                ch = doc["choices"][0]
                for tok in ch.get("token_ids") or ():
                    req.tokens.append(int(tok))
                    req.t_tokens.append(now)
                    if len(req.tokens) == 1:
                        with self._first_cv:
                            self._first_tokens += 1
                            self._first_cv.notify_all()
                if doc.get("error"):
                    req.error = doc["error"].get("message")
                if ch.get("finish_reason"):
                    req.finish_reason = ch["finish_reason"]
            if req.error is not None:
                req.status = "failed"
            elif req.finish_reason is not None:
                req.status = "ok"
            elif self._stop.is_set():
                req.status = "cancelled"
            else:
                req.status = "failed"
                req.error = "stream ended without a terminal frame"
        except (OSError, http.client.HTTPException, ValueError) as e:
            if self._stop.is_set():
                req.status = "cancelled"
            else:
                req.status = "failed"
                req.error = f"{type(e).__name__}: {e}"
        finally:
            req.t_end = self.clock()
            with self._lock:
                self._live.discard(sock)
            conn.close()

    # -- loops -------------------------------------------------------------
    def _closed_client(self):
        while not self._stop.is_set():
            self._run_one(self.source.next(), self.clock())

    def start_closed(self, clients):
        for i in range(clients):
            t = threading.Thread(target=self._closed_client,
                                 name=f"loadgen-{i}", daemon=True)
            self._threads.append(t)
            t.start()

    def wait_first_tokens(self, n, timeout_s):
        """Block until ``n`` requests have had their first token."""
        deadline = self.clock() + timeout_s
        with self._first_cv:
            while self._first_tokens < n:
                left = deadline - self.clock()
                if left <= 0:
                    raise TimeoutError(
                        f"{self._first_tokens} of {n} first tokens after "
                        f"{timeout_s} s")
                self._first_cv.wait(left)

    def _open_dispatch(self, t0):
        while not self._stop.is_set():
            req = self.source.next()
            t_due = t0 + req.due_s
            delay = t_due - self.clock()
            if delay > 0 and self._stop.wait(delay):
                return
            t = threading.Thread(target=self._run_one, args=(req, t_due),
                                 name=f"loadgen-r{req.idx}", daemon=True)
            with self._lock:
                self._threads.append(t)
            t.start()

    def start_open(self):
        t0 = self.clock()
        t = threading.Thread(target=self._open_dispatch, args=(t0,),
                             name="loadgen-dispatch", daemon=True)
        self._threads.append(t)
        t.start()
        return t0

    def stop(self, join_s=30.0):
        """Close the window's traffic: no new sends, live streams shut."""
        self._stop.set()
        with self._lock:
            live = list(self._live)
        for sock in live:
            if sock is not None:
                try:
                    sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
        deadline = self.clock() + join_s
        with self._lock:
            threads = list(self._threads)
        for t in threads:
            t.join(max(deadline - self.clock(), 0.1))
        alive = [t.name for t in threads if t.is_alive()]
        if alive:
            raise RuntimeError(f"load generator threads still alive: {alive}")


# -- the generator as a process of its own ---------------------------------

_FIELDS = ("idx", "max_tokens", "due_s", "t_due", "t_send", "t_tokens",
           "tokens", "t_end", "status", "finish_reason", "error")


def _child_main():
    """Child: one JSON line of configuration on stdin, events on stdout
    (``started``, ``first_tokens``), ``stop`` on stdin, then the records."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.lib import traffic as traffic_mod

    cfg = json.loads(sys.stdin.readline())
    mix = cfg["traffic"]
    source = traffic_mod.RequestSource(mix, cfg["vocab"], cfg["seed"])
    lg = LoadGen(cfg["host"], cfg["port"], source)

    def say(**doc):
        sys.stdout.write(json.dumps(doc) + "\n")
        sys.stdout.flush()

    if mix["loop"] == "closed":
        lg.start_closed(int(mix["clients"]))
        say(event="started", t0=lg.clock())
        lg.wait_first_tokens(int(mix["clients"]), cfg["first_token_timeout_s"])
        say(event="first_tokens", t=lg.clock())
    else:
        say(event="started", t0=lg.start_open())
    sys.stdin.readline()                       # "stop", or the parent is gone
    lg.stop()
    say(event="records", records=[
        dict({k: getattr(r, k) for k in _FIELDS}, prompt_len=len(r.prompt))
        for r in lg.records])


class LoadGenProcess:
    """The parent's handle on the generator's process."""

    def __init__(self, host, port, mix, vocab, seed, first_token_timeout_s=600):
        self._cfg = {"host": host, "port": port, "traffic": mix,
                     "vocab": vocab, "seed": seed,
                     "first_token_timeout_s": first_token_timeout_s}
        self._timeout = first_token_timeout_s
        self.proc = None
        self.records = []

    def _event(self, name):
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(f"the load generator ended (exit code "
                               f"{self.proc.poll()}) before {name!r}")
        doc = json.loads(line)
        if doc["event"] != name:
            raise RuntimeError(f"load generator said {doc['event']!r}, "
                               f"expected {name!r}")
        return doc

    def start(self):
        """Start the load; returns the instant of the first send."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")   # it imports no JAX anyway
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True, env=env)
        self.proc.stdin.write(json.dumps(self._cfg) + "\n")
        self.proc.stdin.flush()
        return self._event("started")["t0"]

    def wait_first_tokens(self):
        return self._event("first_tokens")["t"]

    def stop(self, timeout_s=60.0):
        """Close the traffic, take the records, wait for the process."""
        from . import traffic as traffic_mod

        try:
            self.proc.stdin.write("stop\n")
            self.proc.stdin.flush()
            doc = self._event("records")
            self.proc.stdin.close()
            self.proc.wait(timeout_s)
        finally:
            if self.proc.poll() is None:
                self.proc.kill()
                self.proc.wait()
        if self.proc.returncode != 0:
            raise RuntimeError(f"load generator exit code {self.proc.returncode}")
        # the prompts are not sent back: the same seed makes them again
        source = traffic_mod.RequestSource(
            self._cfg["traffic"], self._cfg["vocab"], self._cfg["seed"])
        made = {}
        for d in sorted(doc["records"], key=lambda d: d["idx"]):
            while d["idx"] not in made:
                r = source.next()
                made[r.idx] = r
            r = made[d["idx"]]
            if len(r.prompt) != d["prompt_len"] or \
                    r.max_tokens != d["max_tokens"]:
                raise RuntimeError("the seed made other traffic in the parent "
                                   "than in the load generator's process")
            for k in _FIELDS[3:]:
                setattr(r, k, d[k])
            self.records.append(r)
        return self.records

    def kill(self):
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


if __name__ == "__main__":
    _child_main()
