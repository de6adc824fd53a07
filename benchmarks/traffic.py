"""Traffic, made from ``--seed`` and a cell's parameters, and the HTTP client
that offers it.

A cell's *set* of sizes and adapters is fixed by its parameters (the quantiles
of the distributions); the seed orders it and draws the token ids.  The order
is stratified: every ``block`` consecutive requests hold one size from each
``block``-th of the distribution, so any stretch of a run offers the same work
and two seeds differ no more than two runs of one.  Token ids are drawn anew
for every request sent, so no prompt is ever offered twice.

The client is one thread over non-blocking sockets (``selectors``): it sends
``POST /v1/chat/completions`` with ``stream: true`` and stamps each
server-sent event as it arrives.
"""

from __future__ import annotations

import json
import selectors
import socket
import time
from typing import Dict, List

import numpy as np


class IdTokenizer:
    """Token ids as decimal text.  Words that are no number (the chat
    template's role markers) carry no token."""

    def encode(self, text: str) -> List[int]:
        return [int(t) for t in text.split() if t.isdigit()]

    def decode(self, ids) -> str:
        return " ".join(str(int(i)) for i in ids)


def adapter_name(i: int) -> str:
    """The name under which adapter ``i`` (0-based) is registered and asked for."""
    return f"a{i:02d}"


def adapter_index(name: str) -> int:
    return int(name[1:])


# -- distributions ---------------------------------------------------------------

def power_law_weights(n: int, a: float) -> np.ndarray:
    """Adapter i (1-based) is asked for in proportion to i ** -a."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** a
    return w / w.sum()


def uniform_quantiles(lo: int, hi: int, n: int) -> np.ndarray:
    """The n mid-quantiles of the whole numbers lo..hi, each as likely as the
    next: the same set for every seed, in rising order."""
    return lo + np.floor((np.arange(n) + 0.5) / n * (hi - lo + 1)).astype(np.int64)


def apportion(weights: np.ndarray, n: int) -> np.ndarray:
    """n items over the choices in proportion to ``weights`` (largest
    remainder): index of the choice for each item, sorted."""
    exact = weights / weights.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts))[: n - counts.sum()]:
        counts[i] += 1
    return np.repeat(np.arange(len(weights)), counts)


def stratified_order(values: np.ndarray, block: int, rng) -> np.ndarray:
    """``values`` (sorted) in an order drawn from ``rng`` in which every
    ``block`` consecutive items hold one from each ``block``-th of them."""
    n = len(values)
    if n % block:
        raise ValueError(f"{n} requests do not divide into blocks of {block}")
    strata = np.stack([rng.permutation(s) for s in values.reshape(block, n // block)])
    return np.concatenate([rng.permutation(col) for col in strata.T])


class Requests:
    """The requests of a mix by the order in which they are sent.  ``mix``:
    ``requests`` (the size of the set), ``block``, ``prompt`` and ``answer``
    {lo, hi} (uniform over the whole numbers), ``adapters`` {count, power_a},
    ``stagger_first``.  Request i has the sizes and the adapter of item
    ``i % requests`` of the set and token ids of its own.  The first
    ``stagger_first`` requests, the callers a closed loop starts with, ask for
    the (j + 1/2) / stagger_first part of their answer, j in an order drawn
    from the seed: the engine is then found as one that has been serving for
    a while, with requests of every age, and not as one whose first cohort
    ends together."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.seed, self.vocab = int(seed), int(vocab)
        n, block = int(mix["requests"]), int(mix["block"])
        rng = np.random.default_rng([self.seed, 0x5E12])
        p, a, ad = mix["prompt"], mix["answer"], mix["adapters"]
        self.prompts = stratified_order(uniform_quantiles(p["lo"], p["hi"], n), block, rng)
        self.answers = stratified_order(uniform_quantiles(a["lo"], a["hi"], n), block, rng)
        who = apportion(power_law_weights(int(ad["count"]), float(ad["power_a"])), n)
        self.who = stratified_order(who, block, rng)
        k = int(mix.get("stagger_first", 0))
        self.part = (rng.permutation(k) + 0.5) / k if k else np.ones(0)

    def __getitem__(self, i: int) -> dict:
        j = i % len(self.prompts)
        rng = np.random.default_rng([self.seed, 0x70C5, int(i)])
        ids = rng.integers(1, self.vocab, size=int(self.prompts[j]))
        answer = int(self.answers[j])
        if i < len(self.part):
            answer = max(int(np.ceil(answer * self.part[i])), 1)
        return {"idx": int(i), "prompt_ids": [int(t) for t in ids], "max_tokens": answer,
                "adapter": adapter_name(int(self.who[j]))}


# -- the client -------------------------------------------------------------------

class _Conn:
    __slots__ = ("sock", "buf", "rec", "head_done")

    def __init__(self, sock, rec):
        self.sock, self.rec, self.buf, self.head_done = sock, rec, b"", False


class LoadClient:
    """Offers requests to ``127.0.0.1:port`` from the calling thread."""

    def __init__(self, port: int, path: str = "/v1/chat/completions"):
        self.port, self.path = port, path
        self.sel = selectors.DefaultSelector()
        self.open: Dict[int, _Conn] = {}

    def _send(self, req: dict, due: float) -> dict:
        body = {"messages": [{"role": "user", "content": " ".join(
            str(t) for t in req["prompt_ids"])}],
            "max_tokens": req["max_tokens"], "temperature": 0, "stream": True}
        if req["adapter"]:
            body["adapter"] = req["adapter"]
        data = json.dumps(body).encode()
        head = (f"POST {self.path} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                f"Content-Type: application/json\r\nContent-Length: {len(data)}\r\n"
                "Connection: close\r\n\r\n").encode()
        rec = dict(req, due=due, sent=None, first=None, last=None, tokens=[],
                   status=None, done=False, error=None, stamps=[])
        try:
            sock = socket.create_connection(("127.0.0.1", self.port), timeout=10)
            sock.sendall(head + data)
            sock.setblocking(False)
        except OSError as e:
            rec.update(error=repr(e), done=True, sent=time.perf_counter())
            return rec
        rec["sent"] = time.perf_counter()
        conn = _Conn(sock, rec)
        self.open[sock.fileno()] = conn
        self.sel.register(sock, selectors.EVENT_READ, conn)
        return rec

    def _close(self, conn: _Conn) -> None:
        self.sel.unregister(conn.sock)
        self.open.pop(conn.sock.fileno(), None)
        conn.sock.close()
        conn.rec["done"] = True

    def _read(self, conn: _Conn) -> None:
        try:
            data = conn.sock.recv(1 << 16)
        except BlockingIOError:
            return
        except OSError as e:
            conn.rec["error"] = repr(e)
            data = b""
        now = time.perf_counter()
        rec = conn.rec
        if not data:
            if rec["status"] != 200 and rec["error"] is None:
                rec["error"] = f"HTTP {rec['status']}: {conn.buf[-200:]!r}"
            self._close(conn)
            return
        conn.buf += data
        if not conn.head_done:
            if b"\r\n\r\n" not in conn.buf:
                return
            head, conn.buf = conn.buf.split(b"\r\n\r\n", 1)
            rec["status"] = int(head.split(None, 2)[1])
            conn.head_done = True
        if rec["status"] != 200:
            return
        while b"\n\n" in conn.buf:
            event, conn.buf = conn.buf.split(b"\n\n", 1)
            if not event.startswith(b"data: ") or event[6:].strip() == b"[DONE]":
                continue
            piece = json.loads(event[6:])["choices"][0]["delta"].get("content", "")
            ids = [int(t) for t in piece.split()]
            if ids:
                if rec["first"] is None:
                    rec["first"] = now
                rec["last"] = now
                rec["tokens"].extend(ids)
                rec["stamps"].extend([now] * len(ids))

    def _poll(self, timeout: float) -> List[dict]:
        """Serves ready sockets; returns the records that ended."""
        ended = []
        for key, _ in self.sel.select(max(timeout, 0.0)):
            conn = key.data
            self._read(conn)
            if conn.rec["done"]:
                ended.append(conn.rec)
        return ended

    def drain(self, deadline: float) -> None:
        """Waits for every open request, at most until ``deadline``; what is
        open then is closed and marked as never answered."""
        while self.open and time.perf_counter() < deadline:
            self._poll(min(0.25, deadline - time.perf_counter()))
        for conn in list(self.open.values()):
            conn.rec["error"] = conn.rec["error"] or "no end within the wait"
            self._close(conn)

    def run_closed(self, requests: "Requests", callers: int, t0: float,
                   seconds: float, first: int = 0) -> List[dict]:
        """Starts ``callers`` more callers, each (like those already open)
        sending its next request when its last one ends, until ``t0 +
        seconds``; ``first`` is the index of the next request to send."""
        records, i = [], first
        end = t0 + seconds
        for _ in range(callers):
            records.append(self._send(requests[i], time.perf_counter()))
            i += 1
        while time.perf_counter() < end:
            for _ in self._poll(min(0.25, end - time.perf_counter())):
                if time.perf_counter() < end:
                    records.append(self._send(requests[i],
                                              time.perf_counter()))
                    i += 1
        return records

    def close(self) -> None:
        for conn in list(self.open.values()):
            self._close(conn)
        self.sel.close()
