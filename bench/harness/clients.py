"""Closed-loop clients of the service's ``RequestRouter``.

Each client sends its next request as soon as the previous one completes
(zero think time).  The poll loop watches every request's committed tokens
(``ServeRequest.committed``, which the engine aliases to the lane's token
list) and stamps each new token with the host clock, so token gaps are seen
as a client sees them, at the poll interval's resolution (1 ms).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

POLL_S = 0.001


@dataclass
class Record:
    index: int                  # request index in the mix (-1: warm-up)
    rid: str
    prompt_len: int
    bucket: int
    max_new: int
    req: object
    submit_t: float
    times: list = field(default_factory=list)   # host time of each token
    tokens: list = None                          # final tokens, once done
    done_t: float = None


def bucket_of(n: int, buckets) -> int:
    """The prompt bucket the engine pads a prompt of ``n`` tokens to."""
    for b in sorted(buckets):
        if n <= b:
            return b
    return max(buckets)


class ClosedLoop:
    def __init__(self, router, requests, n_clients: int, buckets,
                 clock=time.perf_counter, annotate=None):
        from repro.serve.engine import ServeRequest

        self._Req = ServeRequest
        self.router = router
        self.requests = requests
        self.n_clients = n_clients
        self.buckets = tuple(buckets)
        self.clock = clock
        self.records: list = []
        self.inflight: list = []
        self.next_index = 0
        self.accepting = True
        self.annotate = annotate

    def _send(self, index: int, prompt, max_new: int, rid: str) -> Record:
        req = self._Req(rid=rid, prompt=prompt, max_new_tokens=int(max_new))
        rec = Record(index=index, rid=rid, prompt_len=len(prompt),
                     bucket=bucket_of(len(prompt), self.buckets),
                     max_new=int(max_new), req=req, submit_t=self.clock())
        self.records.append(rec)
        self.inflight.append(rec)
        if self.annotate is None:
            self.router.submit(req)
        else:
            with self.annotate("bench.client.send"):
                self.router.submit(req)
        return rec

    def send_next(self) -> Record:
        i = self.next_index
        self.next_index += 1
        prompt, n = self.requests.request(i)
        return self._send(i, prompt, n, f"r{i:05d}")

    def warmup(self, timeout_s: float, check=None) -> None:
        """Serve one prompt per bucket to completion."""
        for k, (prompt, n) in enumerate(self.requests.warmup(self.buckets)):
            self._send(-1, prompt, n, f"warm{k}")
        self.wait(lambda: not self.inflight, timeout_s, check)

    def start(self) -> None:
        for _ in range(self.n_clients):
            self.send_next()

    def poll(self) -> None:
        now = self.clock()
        done = self.router.completed
        cur, self.inflight = self.inflight, []
        for rec in cur:
            c = rec.req.committed
            n = len(c) if c else 0
            if n > len(rec.times):
                rec.times.extend([now] * (n - len(rec.times)))
            fin = done.get(rec.rid)
            if fin is None:
                self.inflight.append(rec)
                continue
            rec.tokens = list(fin.tokens)
            if len(rec.times) < len(rec.tokens):
                rec.times.extend([now] * (len(rec.tokens) - len(rec.times)))
            rec.done_t = now
            if self.accepting and rec.index >= 0:
                self.send_next()

    def wait(self, cond, timeout_s: float, check=None) -> None:
        """Poll until ``cond()``; ``check()`` raises if the system failed."""
        deadline = self.clock() + timeout_s
        while not cond():
            if check is not None:
                check()
            if self.clock() > deadline:
                raise TimeoutError("clients waited longer than "
                                   f"{timeout_s} s")
            self.poll()
            time.sleep(POLL_S)

    def lanes_busy(self) -> int:
        return sum(1 for r in self.inflight if r.times)
