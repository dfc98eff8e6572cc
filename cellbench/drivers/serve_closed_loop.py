"""An offline batch: `callers` callers, each sending its next request, the
next of one common list, when its last has completed. It starts `lead_in_s`
before the window opens, so that the window does not open on every slot
admitted in the same tick, and takes nothing more once the window has
closed: what is in flight then goes on until the run stops it. The list has
no end (traffic.py), so a faster program takes more of it and never all."""

from __future__ import annotations

import threading
import time

from cellbench import serve


def run(cell: dict) -> dict:
    def offer(requests, calls, port, window, stop):
        lock, closed = threading.Lock(), window[1]

        def caller():
            while not stop.is_set():
                with lock:
                    now = time.perf_counter()
                    if now >= closed:
                        return
                    call = serve.Call(next(requests))
                    call.sent = now
                    calls.append(call)
                call.send(port, stop)

        return [serve.start_thread(caller)
                for _ in range(cell["traffic"]["callers"])]

    return serve.run_cell(cell, offer)
