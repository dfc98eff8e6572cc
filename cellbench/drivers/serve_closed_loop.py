"""An offline batch: `callers` callers, each sending its next request, the
next of one common list, when its last has completed. It starts `lead_in_s`
before the window opens, so that the window does not open on every slot
admitted in the same tick."""

from __future__ import annotations

import threading

from cellbench import serve


def run(cell: dict) -> dict:
    def offer(calls, port, opened, stop):
        lock, queue = threading.Lock(), iter(calls)

        def caller():
            while not stop.is_set():
                with lock:
                    call = next(queue, None)
                if call is None:
                    return  # run_cell reports a list that ran out
                call.send(port, stop)

        return [serve.start_thread(caller)
                for _ in range(cell["traffic"]["callers"])]

    return serve.run_cell(cell, offer)
