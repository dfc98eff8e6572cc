"""The request list is a function of (traffic file, seed); what a run
measures is a function of the file and the seconds alone."""

import contextlib
import glob
import hashlib
import importlib
import itertools
import json
import os
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from cellbench import launch, serve, traffic
from cellbench.serve import percentile

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES = sorted(glob.glob(os.path.join(HERE, "traffic", "*.json")))
SEEDS = (0, 7, 2**31 + 12345, 3_999_999_999)
with open(os.path.join(HERE, "tests", "data", "as_before.json")) as fh:
    AS_BEFORE = json.load(fh)


def head(mix, seed, n=480, vocab=32768):
    """All requests of an open loop; the first n of a closed loop's list,
    which has no end."""
    items = traffic.requests(mix, vocab, seed, 51)
    return items if "rate_per_s" in mix else list(itertools.islice(items, n))


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_same_seed_same_list(path):
    mix = traffic.load(path)
    assert head(mix, 11) == head(mix, 11)
    assert head(mix, 11) != head(mix, 12)


@pytest.mark.parametrize("name", sorted(AS_BEFORE["requests"]))
def test_requests_are_what_they_were(name):
    """Recorded on the tree before the list lost its end (PR 28): requests
    0-239 of a list, every request of an open loop, at one seed."""
    was = AS_BEFORE["requests"][name]
    mix = traffic.load(os.path.join(HERE, "traffic", name + ".json"))
    items = head(mix, AS_BEFORE["seed"], 240, was["vocab"])
    assert len(items) == was["n"]
    assert hashlib.sha256(json.dumps(items, sort_keys=True).encode()
                          ).hexdigest() == was["sha256"]


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_measured_set_does_not_move_with_the_seed(path):
    mix = traffic.load(path)
    lists = [head(mix, seed) for seed in SEEDS]
    sizes = traffic.counts(mix, 51) if "rate_per_s" in mix else {"list": 480}
    for group, n in sizes.items():
        histograms = {tuple(traffic.histogram(items, group)) for items in lists}
        assert len(histograms) == 1, group
        assert len(histograms.pop()) == n
        outputs = {tuple(sorted(r["max_new_tokens"] for r in items
                                if r["group"] == group)) for items in lists}
        assert len(outputs) == 1, group


def test_members_are_the_first_two_thirds_in_due_order():
    mix = traffic.load(os.path.join(HERE, "traffic", "chat_steady.json"))
    items = traffic.requests(mix, 32768, 5, 51)
    sizes = traffic.counts(mix, 51)
    n = round(mix["rate_per_s"] * 51)
    assert sizes["member"] == 2 * n // 3 and sizes["member"] + sizes["tail"] == n
    window = [r for r in items if r["group"] in ("member", "tail")]
    assert [r["due_s"] for r in window] == sorted(r["due_s"] for r in window)
    assert all(0 <= r["due_s"] < 51 for r in window)
    assert [r["group"] for r in window] == \
        ["member"] * sizes["member"] + ["tail"] * sizes["tail"]
    assert all(-mix["lead_in_s"] <= r["due_s"] < 0
               for r in items if r["group"] == "lead")


def test_a_batch_list_is_the_same_job_under_every_seed():
    mix = traffic.load(os.path.join(HERE, "traffic", "chat_backlog.json"))
    a, b = (head(mix, seed) for seed in (1, 2**31 + 9))
    assert [(len(r["prompt"]), r["max_new_tokens"]) for r in a] == \
        [(len(r["prompt"]), r["max_new_tokens"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    for start in range(0, len(a), mix["block"]):
        block = a[start:start + mix["block"]]
        assert sorted(len(r["prompt"]) for r in block) == \
            traffic.strata(mix["prompt_tokens"], len(block))
    assert [r["index"] for r in a] == list(range(480))


def test_lengths_keep_to_the_clip():
    for path in FILES:
        mix = traffic.load(path)
        for r in head(mix, 3):
            assert mix["prompt_tokens"]["min"] <= len(r["prompt"]) <= mix["prompt_tokens"]["max"]
            assert mix["output_tokens"]["min"] <= r["max_new_tokens"] <= mix["output_tokens"]["max"]
            assert all(0 <= t < 32768 for t in r["prompt"])


def test_percentile_sorts_misses_last():
    assert percentile([3.0, 1.0, 2.0], 0.5) == 2.0
    assert percentile([1.0, 2.0, 3.0], 0.5, misses=2) == 3.0
    assert percentile([1.0, 2.0], 0.5, misses=2) == float("inf")
    assert percentile([1.0], 0.5, misses=2) == float("inf")
    assert percentile(list(range(1, 21)), 0.95) == 19
    assert percentile(list(range(1, 20)), 0.95, misses=1) == 19
    assert percentile(list(range(1, 19)), 0.95, misses=2) == float("inf")


# -- a closed loop ends at the close, and its list does not ----------------


class _AnswersAtOnce(BaseHTTPRequestHandler):
    """`POST /v1/generate` streamed as the server streams it, with no model
    behind it: a program faster than any."""

    def log_message(self, *args):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        self.send_response(200)
        self.end_headers()
        lines = [json.dumps({"token": 1})] * body["max_new_tokens"]
        lines.append(json.dumps({"done": True, "finish_reason": "length"}))
        self.wfile.write(("\n".join(lines) + "\n").encode())


class _Stub(ThreadingHTTPServer):
    daemon_threads = True
    request_queue_size = 128  # 48 callers connect at once


def test_a_closed_loop_stops_taking_at_the_close_and_cannot_run_out():
    mix = traffic.load(os.path.join(HERE, "traffic", "chat_backlog.json"))
    driver = importlib.import_module("cellbench.drivers." + mix["driver"])
    stub = _Stub(("127.0.0.1", 0), _AnswersAtOnce)
    threading.Thread(target=stub.serve_forever, daemon=True).start()
    seen = {}

    def run_cell(cell, offer):  # what serve.run_cell does with `offer`
        requests = traffic.requests(mix, 32768, AS_BEFORE["seed"], 51)
        calls, stop = [], threading.Event()
        opened = time.perf_counter() + 0.2
        closed = opened + 3.0
        threads = offer(requests, calls, stub.server_address[1],
                        (opened, closed), stop)
        serve.sleep_until(closed + 0.5)
        stop.set()
        for thread in threads:
            thread.join(timeout=30)
        seen.update(calls=calls, closed=closed, threads=threads)
        return {}

    real, serve.run_cell = serve.run_cell, run_cell
    try:
        driver.run({"traffic": mix})
    finally:
        serve.run_cell = real
        stub.shutdown()
        stub.server_close()
    calls, closed = seen["calls"], seen["closed"]
    assert not any(thread.is_alive() for thread in seen["threads"])
    assert len(seen["threads"]) == mix["callers"]
    assert len(calls) > 2 * 240  # the list that ran out had 240
    assert all(c.sent < closed for c in calls)
    assert [c.request["index"] for c in calls] == list(range(len(calls)))
    assert {c.status for c in calls} == {"ok"}
    assert all(len(c.tokens) == c.request["max_new_tokens"] for c in calls)
    was = AS_BEFORE["requests"]["chat_backlog"]
    assert hashlib.sha256(json.dumps(
        [c.request for c in calls[:240]], sort_keys=True).encode()
    ).hexdigest() == was["sha256"]
    numbers = serve.client_numbers(calls, (closed - 3.0, closed), 3.0)
    assert numbers["failed"] == 0 and 0 < numbers["attempted"] <= len(calls)
    assert serve.pick_sample(calls, closed, 5, 5, 300)


# -- a SIGTERM that the task's handler did not see is sent again -----------

DEAF_ONCE = """
import signal, sys, time
got = []
signal.signal(signal.SIGTERM, lambda *_: got.append(1))
print("ready", flush=True)
while len(got) < int(sys.argv[1]):
    time.sleep(0.02)
"""


@contextlib.contextmanager
def _healthz(status):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *args):
            pass

        def do_GET(self):
            body = json.dumps({"status": status}).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    stub = _Stub(("127.0.0.1", 0), Handler)
    threading.Thread(target=stub.serve_forever, daemon=True).start()
    try:
        yield stub.server_address[1]
    finally:
        stub.shutdown()
        stub.server_close()


@pytest.mark.parametrize("status,needs,sent", [
    ("ok", 2, 2), ("draining", 1, 1), ("ok", 99, None)])
def test_stop_sends_again_only_while_the_server_has_not_heard(
        monkeypatch, status, needs, sent):
    """A task that ends at its `needs`-th SIGTERM: the first is lost on it
    where it needs two; one that never ends is named with what it was sent.
    `/healthz` says whether the handler has run."""
    monkeypatch.setattr(launch, "ASK_AGAIN_S", 0.3)
    task = subprocess.Popen([sys.executable, "-c", DEAF_ONCE, str(needs)],
                            stdout=subprocess.PIPE, text=True)
    try:
        assert task.stdout.readline().strip() == "ready"
        stopper = launch.Launch.__new__(launch.Launch)
        stopper.error = None
        stopper.backend = type("Backend", (), {"handle": type("Handle", (), {
            "pids": staticmethod(lambda: {"serving:0": task.pid})})})
        stopper._thread = threading.Thread(target=task.wait)
        stopper._thread.start()
        with _healthz(status) as port:
            if sent is None:
                with pytest.raises(RuntimeError, match=rf"sent [3-9] times to .*{task.pid}"):
                    stopper.stop(port, timeout=1.5)
                return
            stopper.stop(port, timeout=20.0)
        assert task.poll() == 0 and not stopper._thread.is_alive()
        assert stopper.stopping == {"pids": {"serving:0": task.pid}, "sent": sent}
    finally:
        task.kill()
        task.wait()
