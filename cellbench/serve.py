"""One run of a served cell, shared by the open-loop and closed-loop drivers:
launch the `serving` task through `run_on_tpu`, warm the cell's shapes,
offer the lead-in and the window's traffic over `POST /v1/generate`
(streamed), read the server and the device when the window closes, stop the
task, and hand a sample of what the window finished to the reference.

The parent process stays off JAX: the task holds the chip, and after it the
child that runs the reference does.
"""

from __future__ import annotations

import functools
import http.client
import json
import os
import shutil
import statistics
import subprocess
import sys
import threading
import time

import numpy as np

from cellbench import launch as launch_lib
from cellbench import traffic as traffic_lib

TRACE_SECONDS = 6.0    # the end of the window, with --trace 1: the trace is
                       # written out after the window has closed
OPEN_MARGIN_S = 0.5    # between the threads' start and the first due instant


class Call:
    """One request as the client saw it. Times are perf_counter seconds."""

    def __init__(self, request: dict):
        self.request = request
        self.due = None       # when it was due to be sent (open loop)
        self.sent = None      # when it was sent (closed loop: taken from the list)
        self.arrivals = []    # one per token
        self.tokens = []
        self.status = "unsent"  # ok | refused | failed | cut
        self.finish = None

    def send(self, port: int, stop: threading.Event):
        body = json.dumps({
            "prompt": self.request["prompt"],
            "max_new_tokens": self.request["max_new_tokens"],
            "stream": True,
        })
        if self.sent is None:
            self.sent = time.perf_counter()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600.0)
        try:
            conn.request("POST", "/v1/generate", body, {
                "Content-Type": "application/json",
                "X-Request-Id": f"cell-{self.request['index']}",
            })
            response = conn.getresponse()
            if response.status != 200:
                response.read()
                self.status = "refused"
                return
            while True:
                line = response.readline()
                now = time.perf_counter()
                if not line:
                    self.status = "cut"
                    return
                item = json.loads(line)
                if item.get("done"):
                    self.finish = item.get("finish_reason")
                    self.status = {"length": "ok", "eos": "ok",
                                   "shutdown": "cut"}.get(self.finish, "failed")
                    return
                self.arrivals.append(now)
                self.tokens.append(int(item["token"]))
        except (OSError, http.client.HTTPException, ValueError):
            self.status = "cut" if stop.is_set() else "failed"
        finally:
            conn.close()


def percentile(values, q: float, misses: int = 0) -> float:
    """The q-quantile (0..1, nearest rank upward) of `values` with `misses`
    more that sort above every finite value; inf where it falls on a miss,
    nan with nothing to rank. The median of an even count is the mean of the
    two middle ranks."""
    ranked = sorted(values) + [float("inf")] * misses
    if not ranked:
        return float("nan")
    if q == 0.5:
        return statistics.median(ranked)
    return ranked[min(len(ranked) - 1, max(0, int(np.ceil(q * len(ranked))) - 1))]


def client_numbers(calls, window, seconds: float) -> dict:
    """Every number the client's clock gives, over all of the window."""
    opened, closed = window
    in_window = [c for c in calls
                 if c.sent is not None and opened <= (c.due or c.sent) < closed]
    members = [c for c in calls if c.request["group"] == "member"]
    timed = members or in_window
    ttfts, misses = [], 0
    for call in timed:
        first = call.arrivals[0] if call.arrivals else None
        if first is None or first > closed:
            misses += 1
        else:
            ttfts.append((first - (call.due or call.sent)) * 1e3)
    gaps, tokens = [], 0
    for call in calls:
        inside = [t for t in call.arrivals if opened <= t < closed]
        tokens += len(inside)
        gaps.extend((b - a) * 1e3 for a, b in zip(inside, inside[1:]))
    late = [(c.sent - c.due) * 1e3 for c in in_window if c.due is not None]
    return {
        "ttft_p50_ms": percentile(ttfts, 0.5, misses),
        "ttft_p95_ms": percentile(ttfts, 0.95, misses),
        "ttft_members": len(timed), "ttft_misses": misses,
        "itl_p50_ms": percentile(gaps, 0.5), "itl_p90_ms": percentile(gaps, 0.9),
        "itl_p95_ms": percentile(gaps, 0.95), "itl_p99_ms": percentile(gaps, 0.99),
        "itl_gaps": len(gaps),
        "serve_tokens_per_s": tokens / seconds,
        "gen_late_p95_ms": percentile(late, 0.95) if late else None,
        "attempted": len(in_window),
        "failed": len([c for c in in_window
                       if c.status in ("refused", "failed")]),
        "member_prompt_lengths": sorted(
            len(c.request["prompt"]) for c in timed),
    }


def pick_sample(calls, closed: float, seed: int, n: int,
                least_tokens: int = 0) -> list:
    """Requests the window finished at full length: the longest, and more in
    an order drawn from the seed, until there are n of them and they hold
    `least_tokens` served tokens (or the window finished no more)."""
    done = [c for c in calls if c.status == "ok" and c.arrivals
            and c.arrivals[-1] < closed
            and len(c.tokens) == c.request["max_new_tokens"]]
    if not done:
        return []
    done.sort(key=lambda c: c.request["index"])
    longest = max(done, key=lambda c: len(c.request["prompt"]) + len(c.tokens))
    rest = [c for c in done if c is not longest]
    rng = np.random.default_rng([int(seed), 0x73616D70])
    sample = [longest]
    for i in rng.permutation(len(rest)):
        if len(sample) >= n and \
                sum(len(c.tokens) for c in sample) >= least_tokens:
            break
        sample.append(rest[i])
    return sample


def run_check(cell: dict, samples: list, lower=None) -> dict:
    """The reference in a child that takes the chip the task has left. With
    `lower`, the control: the reference in that lower precision stands in
    the program's place, and its first choices are what is compared."""
    config = cell["config"]
    path = os.path.join(cell["run_dir"], "check_input.json")
    with open(path, "w") as fh:
        json.dump({
            "sizes": config, "reference": config["check"]["reference"],
            "seed": cell["seed"], "lower": lower,
            "samples": [{"prompt": c.request["prompt"], "tokens": c.tokens}
                        for c in samples],
        }, fh)
    env = dict(os.environ, **launch_lib.task_env(cell["run_dir"]))
    if not cell["require_chip"]:
        env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "cellbench.check", path], env=env,
        cwd=launch_lib.ROOT, capture_output=True, text=True, timeout=600,
    )
    if proc.returncode != 0:
        raise RuntimeError("the reference failed:\n" + proc.stderr[-3000:])
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    if cell["require_chip"] and result["platform"] != "tpu":
        raise RuntimeError(f"the reference ran on {result['platform']!r}")
    return result


def task_spec(cell: dict, port: int, agent_port: int) -> dict:
    """What the task is handed (agent.serving_experiment): the whole
    configuration file, since an adapter may need its lists and strings."""
    return {
        "config": cell["config"], "seed": cell["seed"], "port": port,
        "agent_port": agent_port, "run_dir": cell["run_dir"],
        "trace_dir": os.path.join(cell["run_dir"], "profile"),
    }


def run_cell(cell: dict, offer) -> dict:
    """`offer(requests, calls, port, (opened, closed), stop)` starts the
    threads that send the requests, puts a `Call` into `calls` for each one it
    takes, and returns the threads; everything else is the same for every
    mix."""
    from tf_yarn_tpu.topologies import NodeLabel, TaskSpec

    config, traffic = cell["config"], cell["traffic"]
    seconds = float(cell["seconds"])
    requests = traffic_lib.requests(
        traffic, config["vocab_size"], cell["seed"], seconds)
    calls = []
    run_dir = cell["run_dir"]
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    port, agent_port = launch_lib.free_port(), launch_lib.free_port()
    spec = task_spec(cell, port, agent_port)
    from cellbench import agent

    task = (TaskSpec(instances=1, chips_per_host=cell["chips"],
                     label=NodeLabel.TPU)
            if cell["require_chip"] else TaskSpec(instances=1))
    door = functools.partial(launch_lib.http_json, agent_port)
    server = functools.partial(launch_lib.http_json, port)
    stop = threading.Event()
    trace_span = None
    with launch_lib.Launch(
        functools.partial(agent.serving_experiment, spec),
        {"serving": task}, "cellbench", run_dir,
    ) as launch:
        launch_lib.wait_healthy(launch, port)
        ready = time.perf_counter()
        device = door("GET", "/device")
        if cell["require_chip"] and (
                device["platform"] != "tpu" or device["count"] < cell["chips"]):
            raise RuntimeError(f"no chip, or too few: {device}")
        rng = np.random.default_rng([int(cell["seed"]), 0x7761726D])
        for length in traffic["warmup_prompt_lengths"]:
            server("POST", "/v1/generate", {
                "prompt": rng.integers(0, config["vocab_size"], length).tolist(),
                "max_new_tokens": 2,
            })
        warm = time.perf_counter()
        opened = time.perf_counter() + float(traffic.get("lead_in_s", 0)) \
            + OPEN_MARGIN_S
        closed = opened + seconds
        threads = offer(requests, calls, port, (opened, closed), stop)
        sleep_until(opened)
        launch.check()
        stats_open = server("GET", "/stats")
        if cell["trace"]:
            sleep_until(closed - min(TRACE_SECONDS, seconds / 2) - 0.5)
            started = door("POST", "/trace/start")["sync_perf_s"]
        sleep_until(closed)
        stats_close = server("GET", "/stats")
        if cell["trace"]:
            stopped = door("POST", "/trace/stop")["stopped_perf_s"]
            trace_span = (started + 0.05, min(stopped, closed) - 0.01)
        launch.check()
        device = door("GET", "/device")
        spans = door("GET", "/spans")["spans"]
        reduced = None
        if cell["trace"]:
            try:
                reduced = door("POST", "/trace/reduce", {"window": trace_span})
            except RuntimeError:
                if cell["require_chip"]:  # only a CPU rehearsal has no device plane
                    raise
        stop.set()
        launch.stop(port)
        task_log = launch.log_tail(400)
    for thread in threads:
        thread.join(timeout=30)
    client = client_numbers(calls, (opened, closed), seconds)
    samples = pick_sample(calls, closed, cell["seed"],
                          config["check"]["sample"],
                          config["check"]["least_tokens"])
    compared, check_began = {}, time.perf_counter()
    if samples:
        checked = run_check(cell, samples)
        for name, limit in config["check"]["limits"].items():
            compared[name] = {"value": checked[name], "limit": limit}
        compared["compared_tokens"] = {
            "value": checked["tokens"],
            "limit": config["check"]["least_tokens"], "at_least": True}
        compared["uncompared"] = {k: v for k, v in checked.items()
                                  if k not in compared}
    return {
        "end_to_end": dict(
            client, setup_s=opened - cell["process_start"]),
        "attempted": client["attempted"], "failed": client["failed"],
        "compared": compared, "device": device, "client": client,
        "stats_open": stats_open, "stats_close": stats_close,
        "spans": spans, "trace": reduced, "window": [opened, closed],
        "trace_window": trace_span,
        "marks": {"process_start": cell["process_start"], "ready": ready,
                  "warm": warm},
        "calls": [{"index": c.request["index"], "group": c.request["group"],
                   "prompt_tokens": len(c.request["prompt"]),
                   "max_new_tokens": c.request["max_new_tokens"],
                   "due": c.due, "sent": c.sent, "status": c.status,
                   "first": c.arrivals[0] if c.arrivals else None,
                   "last": c.arrivals[-1] if c.arrivals else None,
                   "n_tokens": len(c.tokens), "finish": c.finish} for c in calls],
        "config": config, "traffic": traffic, "task_log": task_log,
        "run_dir": run_dir,
        "notes": {"check_s": time.perf_counter() - check_began,
                  "sent": len(calls),
                  "sent_after_close": sum(c.sent >= closed for c in calls
                                          if c.sent is not None),
                  "stopping": launch.stopping,
                  # a run that reads far off says why: model steps over twice
                  # the median, as the program counts them (0 before PR 25)
                  "slow_steps_in_window": stats_close.get("slow_steps", 0)
                  - stats_open.get("slow_steps", 0),
                  "slow_step_seconds_in_window":
                  stats_close.get("slow_step_seconds", 0.0)
                  - stats_open.get("slow_step_seconds", 0.0),
                  "slowest_step": stats_close.get("slowest_step"),
                  "sampled_requests": len(samples),
                  "member_prompt_lengths": client["member_prompt_lengths"]},
    }


def sleep_until(when: float):
    while True:
        left = when - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.25))


def start_thread(target, *args) -> threading.Thread:
    thread = threading.Thread(target=target, args=args, daemon=True)
    thread.start()
    return thread
