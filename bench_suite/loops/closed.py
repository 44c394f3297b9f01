"""Closed loop: ``outstanding`` requests in flight from one generator;
the next is sent when the oldest is answered. Latency from submit."""

import time


def run(session) -> None:
    n_out = int(session.traffic["outstanding"])
    inflight = []
    while True:
        while (len(inflight) < n_out
               and time.perf_counter() < session.t_end):
            req = session.plan.next()
            handle = session.submit(req)
            req.t_due = req.t_sub
            inflight.append((req, handle))
        if not inflight:
            return
        session.finish(*inflight.pop(0))
