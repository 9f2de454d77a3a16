import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from benchmark import loadgen, traffic

PARAMS = {"rate_rps": 20.0, "priority": "interactive",
          "prompt_len": {"median": 128, "sigma": 0.8, "min": 16, "max": 512},
          "output_len": {"median": 64, "sigma": 0.6, "min": 8, "max": 128}}


def test_schedule_is_a_function_of_the_seed_alone():
    a = traffic.open_loop_schedule(PARAMS, 7, 30.0, 32128)
    b = traffic.open_loop_schedule(PARAMS, 7, 30.0, 32128)
    c = traffic.open_loop_schedule(PARAMS, 8, 30.0, 32128)
    assert a == b
    assert a != c
    assert len(a) == len(c) == 600  # rate x seconds, whatever the seed
    due = [r["due_s"] for r in a]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 30.0
    for r in a:
        assert 16 <= len(r["prompt"]) <= 512
        assert 8 <= r["max_new_tokens"] <= 128
        assert all(2 <= t < 32128 for t in r["prompt"])
    # every seed offers the same lengths in another order
    assert sorted(len(r["prompt"]) for r in a) == sorted(
        len(r["prompt"]) for r in c)
    assert sorted(r["max_new_tokens"] for r in a) == sorted(
        r["max_new_tokens"] for r in c)
    med = np.median([len(r["prompt"]) for r in a])
    assert 120 <= med <= 136


def test_token_rows_from_the_seed():
    a = traffic.token_rows(np.random.default_rng([3, 1]), 6, 384, 16, 8)
    b = traffic.token_rows(np.random.default_rng([3, 1]), 6, 384, 16, 8)
    assert all((a[k] == b[k]).all() for k in a)
    assert a["input_ids"].shape == (6, 16) and a["labels"].shape == (6, 8)
    assert a["input_ids"].min() >= 2 and a["input_ids"].max() < 384
    assert len(traffic.as_items(a)) == 6


class _Stub(BaseHTTPRequestHandler):
    """Answers like the engine route: a submit gives an id; a stream has
    its first token ``FIRST_S`` after the submit and is done at 3 tokens."""

    protocol_version = "HTTP/1.1"
    FIRST_S = 0.10
    streams = {}
    lock = threading.Lock()

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        if body["action"] == "submit":
            with self.lock:
                rid = len(self.streams)
                self.streams[rid] = time.monotonic()
            out = {"request_id": rid}
        else:
            age = time.monotonic() - self.streams[body["request_id"]]
            have = 0 if age < self.FIRST_S else min(
                3, 1 + int((age - self.FIRST_S) / 0.03))
            out = {"tokens": list(range(5, 5 + have))[body["cursor"]:],
                   "done": have == 3}
        data = json.dumps(out).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(data)))
        self.send_header("x-tpu-air-replica", "r0")
        self.end_headers()
        self.wfile.write(data)


def test_client_clocks_from_the_due_time():
    server = ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        # the plan starts 0.3 s in the past: every request goes out late,
        # and that lateness must be inside its time to first token
        plan = {"host": "127.0.0.1", "port": server.server_address[1],
                "path": "/", "start_at": time.monotonic() - 0.3,
                "seconds": 0.2, "poll_s": 0.01, "submit_threads": 2, "poll_threads": 2, "drain_s": 5.0,
                "requests": [{"due_s": 0.02 * i, "prompt": [5, 6],
                              "max_new_tokens": 3, "priority": "interactive"}
                             for i in range(8)]}
        result = loadgen.Client(plan).run()
        rows = result["requests"]
    finally:
        server.shutdown()
        server.server_close()
    assert [r["outcome"] for r in rows] == ["ok"] * 8
    for r in rows:
        assert r["tokens"] == [5, 6, 7]
        assert r["late_s"] >= 0.3 - 0.02 * 8
        # first token = lateness + the server's 0.10 s (+ at most a poll or
        # two): clocked from when it was DUE, not from when it was sent
        assert r["ttft_s"] >= r["late_s"] + _Stub.FIRST_S
        assert r["ttft_s"] <= r["late_s"] + _Stub.FIRST_S + 0.5
        # a stream's next poll is due poll_s after the answer to its last
        assert r["polls"] >= 2 and r["poll_interval_s"] >= 0.01
    # every poll says how late it left: the wait for one of the two poll
    # connections, never negative
    assert len(result["poll_late_s"]) == sum(r["polls"] for r in rows)
    assert min(result["poll_late_s"]) >= 0.0
