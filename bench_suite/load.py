"""The one traffic generator. A traffic mix is a data file of parameters
(``bench_suite/traffic/<mix>.json``) that this module reads:

- ``loop``: the arrival process, a module of its own found by name,
  ``bench_suite/loops/<loop>.py``, whose ``run(session)`` offers the
  plan's requests for the window: ``closed`` (``outstanding`` requests
  in flight; the next one is sent when one completes). A new arrival
  process, such as an open loop that sends on a schedule whatever the
  system does, is a new file;
- ``rows``: ``{"min": a, "max": b}`` query rows per request. Every block
  of ``b - a + 1`` consecutive requests holds each size once, in an
  order drawn from the seed, so every seed sends the same mix;
- the loop's own parameters.

Queries are contiguous (wrapping) runs of the seeded query pool from a
seeded offset. Requests are timed on the host clock: closed loop from
submit, open loop from when the request was due.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Callable, List, Optional

import numpy as np

from bench_suite import spec

#: how long a request may take past the window's close before it counts
#: as never answered
LATE_S = 60.0


@dataclasses.dataclass
class Request:
    rid: int
    start: int
    rows: int
    t_due: float
    t_sub: float = math.nan
    t_done: float = math.nan
    dist: Optional[np.ndarray] = None
    ids: Optional[np.ndarray] = None
    error: Optional[str] = None

    @property
    def latency(self) -> float:
        """Seconds from due to done; +inf for a request that failed."""
        if self.error is not None:
            return math.inf
        return self.t_done - self.t_due


class Blocks:
    """An endless stream of ``values``: every block of ``len(values)``
    holds each once, in an order drawn from ``rng``."""

    def __init__(self, values, rng: np.random.Generator):
        self._values = np.asarray(values)
        self._rng = rng
        self._q: collections.deque = collections.deque()

    def next(self):
        if not self._q:
            self._q.extend(self._rng.permutation(self._values).tolist())
        return self._q.popleft()


class Plan:
    """The seeded stream of requests of one traffic mix. ``stream``
    separates the warm-up's requests from the window's. A loop draws
    any further stream it needs (gaps, bursts) from ``rng``."""

    def __init__(self, traffic: dict, n_pool: int, seed: int,
                 stream: int = 1):
        self.n_pool = int(n_pool)
        self.rng = np.random.default_rng([int(seed), int(stream)])
        lo, hi = int(traffic["rows"]["min"]), int(traffic["rows"]["max"])
        self._sizes = Blocks(np.arange(lo, hi + 1), self.rng)
        self._offset = int(self.rng.integers(self.n_pool))
        self._rid = 0

    def next(self) -> "Request":
        """The next request, not yet due (``t_due`` NaN)."""
        rows = self._sizes.next()
        start = self._offset
        self._offset = (self._offset + rows) % self.n_pool
        self._rid += 1
        return Request(self._rid, start, rows, t_due=math.nan)


class Keeper:
    """Which requests keep their answers for the check: all of them, or
    a seeded reservoir sample of ``sample`` requests."""

    def __init__(self, sample: Optional[int], seed: int):
        self.sample = sample
        self._rng = np.random.default_rng([int(seed), 7])
        self._seen = 0
        self.kept: List[Request] = []

    def offer(self, req: Request) -> None:
        if self.sample is None or len(self.kept) < self.sample:
            self.kept.append(req)
        else:
            j = int(self._rng.integers(self._seen + 1))
            if j < self.sample:
                self.kept[j].dist = self.kept[j].ids = None
                self.kept[j] = req
            else:
                req.dist = req.ids = None
        self._seen += 1


@dataclasses.dataclass
class Window:
    """The measured window: it opens at ``t0``, stops sending at
    ``t_end`` and closes at ``t_close``, the first completion at or after
    ``t_end``, so that a rate over ``[t0, t_close]`` counts whole batches
    of work and all the time they took."""
    t0: float
    t_end: float
    t_close: float
    requests: List[Request]
    lateness_s: List[float]

    def answered(self) -> List[Request]:
        """Requests answered by the window's close."""
        return [r for r in self.requests
                if r.error is None and r.t_done <= self.t_close]


@dataclasses.dataclass
class Session:
    """What a loop module's ``run`` drives: send ``plan``'s requests to
    ``system`` from ``t0`` until ``t_end``, hand each to ``record`` once
    it is answered (or failed), wait for none past ``deadline``, and
    append each open-loop request's send delay to ``lateness``."""
    system: object
    traffic: dict
    plan: Plan
    t0: float
    t_end: float
    deadline: float
    record: Callable[[Request], None]
    lateness: List[float]

    def submit(self, req: Request):
        """Send ``req``; its handle, or None when it was refused."""
        req.t_sub = time.perf_counter()
        try:
            return self.system.submit(req.start, req.rows)
        except Exception as e:  # refused at admission
            req.error = f"{type(e).__name__}: {e}"[:200]
            req.t_done = req.t_sub
            return None

    def finish(self, req: Request, handle) -> None:
        """Wait for ``req``'s answer (unless it was refused) and record
        it."""
        if req.error is None:
            try:
                req.dist, req.ids = self.system.wait(
                    handle, max(0.0, self.deadline - time.perf_counter()))
            except Exception as e:  # never came back, or failed
                req.error = f"{type(e).__name__}: {e}"[:200]
            req.t_done = time.perf_counter()
        self.record(req)


def drive(system, traffic: dict, plan: Plan, seconds: float,
          keeper: Optional[Keeper] = None,
          on_open: Optional[Callable[[], None]] = None,
          root: str = spec.ROOT) -> Window:
    """Offer ``plan``'s requests to ``system`` for ``seconds`` by the
    mix's loop; wait for every request sent in the window, up to
    :data:`LATE_S` past its close. ``on_open`` runs just before the
    window opens."""
    keeper = keeper or Keeper(None, 0)
    loop = spec.loop_module(traffic["loop"], root)
    done: List[Request] = []

    def record(req: Request) -> None:
        done.append(req)
        keeper.offer(req)

    if on_open is not None:
        on_open()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    session = Session(system, traffic, plan, t0, t_end, t_end + LATE_S,
                      record, [])
    loop.run(session)
    t_close = min((r.t_done for r in done
                   if r.error is None and r.t_done >= t_end), default=t_end)
    return Window(t0=t0, t_end=t_end, t_close=t_close, requests=done,
                  lateness_s=session.lateness)
