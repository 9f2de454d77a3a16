#!/usr/bin/env python3
"""Open-loop HTTP client for the serving cells, run as a process of its own
(the proxy lives in the driver process; its threads and the client's must not
share one interpreter lock).  It imports nothing of the program and no JAX.

    python benchmark/loadgen.py <plan.json> <out.json>

The plan holds the schedule (``benchmark.traffic.open_loop_schedule``), the
port, the poll interval and ``start_at`` on ``time.monotonic()`` (one clock
for every process of a Linux host).  Each request is a streaming ``submit``
and then ``poll`` calls pinned to the replica that took it, every ``poll_s``
seconds until done (copied in outline from tools/bench_serve.py ``_Client``).
A few threads, each with one persistent connection, work through two queues of
timed events — one for submits, one for polls, each with threads of its own, so
that a server that stalls its polls does not hold back the arrivals — and the
number of threads does not grow with the load.

Every time is taken against the moment the request was DUE, not the moment
it was sent: a stall of the server, or of this client, delays what follows it
and that delay is the users'.  How late each submit went out is reported
beside the results (``late_s``), and so is how late each poll went out
against ``poll_s`` after the answer to the one before (``poll_late_s``): the
poll threads are a fixed pool, each waiting for its answer, so when the server
answers polls more slowly than the live streams ask, polls wait here for a
free connection and a stream is polled less often than the plan says.
"""

from __future__ import annotations

import heapq
import http.client
import json
import socket
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

REPLICA_HEADER = "x-tpu-air-replica"


class Client:
    def __init__(self, plan: Dict[str, Any]):
        self.plan = plan
        self.start_at = float(plan["start_at"])
        self.poll_s = float(plan["poll_s"])
        # no event is started after this; what is unfinished then has failed
        self.give_up_at = (self.start_at + float(plan["seconds"])
                           + float(plan["drain_s"]))
        self.requests: List[Dict[str, Any]] = [
            {"due": self.start_at + r["due_s"], "prompt": r["prompt"],
             "max_new_tokens": r["max_new_tokens"],
             "priority": r["priority"], "outcome": None, "sent": None,
             "first": None, "done": None, "tokens": [], "rid": None,
             "pin": None, "status": None, "polls": 0, "first_poll": None,
             "last_poll": None}
            for r in plan["requests"]]
        self.heaps = {"submit": [(r["due"], i) for i, r in
                                 enumerate(self.requests)], "poll": []}
        heapq.heapify(self.heaps["submit"])
        self.poll_late: List[float] = []  # seconds, one entry a poll
        self.cond = threading.Condition()
        self.open = len(self.requests)

    # -- the event queue ------------------------------------------------------
    def _next(self, what: str) -> Optional[Tuple[float, int]]:
        """The next event of ``what`` that is due: (when it was due, whose)."""
        heap = self.heaps[what]
        with self.cond:
            while True:
                now = time.monotonic()
                if self.open == 0 or now >= self.give_up_at:
                    self.cond.notify_all()
                    return None
                if heap and heap[0][0] <= now:
                    return heapq.heappop(heap)
                wait = (heap[0][0] - now) if heap else 0.05
                self.cond.wait(min(wait, self.give_up_at - now))

    def _push(self, at: float, i: int, what: str) -> None:
        with self.cond:
            heapq.heappush(self.heaps[what], (at, i))
            self.cond.notify_all()

    def _close(self, i: int, outcome: str) -> None:
        with self.cond:
            self.requests[i]["outcome"] = outcome
            self.open -= 1
            self.cond.notify_all()

    # -- one worker thread ------------------------------------------------------
    def _post(self, conn_box: list, payload: Dict[str, Any],
              headers: Dict[str, str]):
        body = json.dumps(payload).encode()
        hdrs = {"Content-Type": "application/json", **headers}
        for attempt in (0, 1):
            if conn_box[0] is None:
                conn = http.client.HTTPConnection(
                    self.plan["host"], self.plan["port"], timeout=60.0)
                conn.connect()
                conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                conn_box[0] = conn
            try:
                conn_box[0].request("POST", self.plan["path"], body=body,
                                    headers=hdrs)
                resp = conn_box[0].getresponse()
                data = resp.read()
                return resp.status, json.loads(data), resp.getheader(
                    REPLICA_HEADER)
            except (OSError, http.client.HTTPException, ValueError):
                # a stale keep-alive socket: reopen once, then give up
                try:
                    conn_box[0].close()
                finally:
                    conn_box[0] = None
                if attempt:
                    raise
        raise RuntimeError("unreachable")

    def _work(self, what: str) -> None:
        conn_box = [None]
        try:
            while True:
                event = self._next(what)
                if event is None:
                    return
                at, i = event
                r = self.requests[i]
                try:
                    if what == "submit":
                        self._submit(conn_box, i, r)
                    else:
                        self._poll(conn_box, i, r, at)
                except (OSError, http.client.HTTPException, ValueError,
                        KeyError) as e:
                    r["status"] = repr(e)
                    self._close(i, "error")
        finally:
            if conn_box[0] is not None:
                conn_box[0].close()

    def _submit(self, conn_box, i: int, r: Dict[str, Any]) -> None:
        r["sent"] = time.monotonic()
        status, out, pin = self._post(conn_box, {
            "action": "submit", "prompt": r["prompt"],
            "max_new_tokens": r["max_new_tokens"],
            "priority": r["priority"]}, {})
        r["status"] = status
        if status != 200:
            self._close(i, "shed" if status in (429, 503) else "error")
            return
        r["rid"], r["pin"] = out["request_id"], pin or ""
        self._push(time.monotonic() + self.poll_s, i, "poll")

    def _poll(self, conn_box, i: int, r: Dict[str, Any], at: float) -> None:
        sent = time.monotonic()
        self.poll_late.append(sent - at)
        r["first_poll"] = r["first_poll"] or sent
        r["last_poll"] = sent
        status, out, _ = self._post(conn_box, {
            "action": "poll", "request_id": r["rid"],
            "cursor": len(r["tokens"])}, {REPLICA_HEADER: r["pin"]})
        now = time.monotonic()
        r["polls"] += 1
        if status != 200:
            r["status"] = status
            self._close(i, "error")
            return
        got = out.get("tokens") or []
        if got and r["first"] is None:
            r["first"] = now
        r["tokens"].extend(int(t) for t in got)
        if out.get("done"):
            r["done"] = now
            self._close(i, "ok")
        else:
            self._push(now + self.poll_s, i, "poll")

    # -- the run ----------------------------------------------------------------
    def run(self) -> Dict[str, Any]:
        threads = [threading.Thread(target=self._work, args=(what,),
                                    daemon=True)
                   for what in ("submit", "poll")
                   for _ in range(int(self.plan[what + "_threads"]))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        rows = []
        for r in self.requests:
            rows.append({
                "due_s": r["due"] - self.start_at,
                "outcome": r["outcome"] or "unfinished",
                "status": r["status"],
                "late_s": None if r["sent"] is None else r["sent"] - r["due"],
                "ttft_s": None if r["first"] is None else r["first"] - r["due"],
                "done_s": None if r["done"] is None else r["done"] - r["due"],
                "first_to_done_s": (None if r["done"] is None
                                    or r["first"] is None
                                    else r["done"] - r["first"]),
                "budget": r["max_new_tokens"],
                "tokens": r["tokens"], "polls": r["polls"],
                # mean time from one poll of this stream to the next
                "poll_interval_s": (None if r["polls"] < 2 else
                                    (r["last_poll"] - r["first_poll"])
                                    / (r["polls"] - 1))})
        return {"requests": rows, "poll_late_s": self.poll_late,
                "ended_at": time.monotonic()}


def main(argv: List[str]) -> int:
    with open(argv[0]) as f:
        plan = json.load(f)
    result = Client(plan).run()
    with open(argv[1], "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
