"""Independent users: every request is sent at its due instant whether or
not earlier ones have finished, and is timed from that instant."""

from __future__ import annotations

from cellbench import serve


def run(cell: dict) -> dict:
    def offer(requests, calls, port, window, stop):
        def one(call):
            call.due = window[0] + call.request["due_s"]
            serve.sleep_until(call.due)
            if not stop.is_set():
                call.send(port, stop)

        calls.extend(serve.Call(request) for request in requests)
        return [serve.start_thread(one, call) for call in calls]

    return serve.run_cell(cell, offer)
