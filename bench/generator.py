"""The one traffic generator: reads a mix file of ``bench/traffic`` and turns
it into the events (serving mixes) or instances (sweep mixes) of a run.

Mix keys, by ``loop``:

* ``closed`` — a closed loop of held requests, one per user (a full
  buffer: every user always has a request). ``live_per_cell`` requests seed
  every cell, the deployment's apps in equal shares in an order drawn from
  the seed. A request the engine drops (rejected ``max_retries`` times) is
  asked again by its user at the next tick, with the same app, so every cell
  always holds ``live_per_cell`` requests. Besides, each tick
  ``churn_share`` x cells, drawn from the seed, each lose their oldest
  request still held (a ``Departure``) and gain one arrival; the apps of a
  tick's arrivals are the deployment's apps in equal shares, in an order
  drawn from the seed, so every seed offers the same work. The next tick's
  events are drawn after the previous tick commits.
* ``open`` — an open loop. Poisson arrivals at ``rate_per_s`` over the
  whole deployment; each picks its cell uniformly and its app uniformly, and
  departs after an exponential holding time of mean
  ``population / rate_per_s`` seconds, so about ``population`` requests are
  live. ``tmax_slots`` arrivals first go to cell 0 (departing as usual), so
  the slot high-water mark, and with it every shape the window uses, is
  fixed before the window. ``prefill_s`` of traffic is replayed in simulated
  time before the clock starts, so the population is the same whatever the
  compile took, in steps cycling through 1/4 to 8 x ``prefill_step_s`` so
  that every size of delta the window will scatter has been seen; ``warm_s``
  more then runs on the clock.
* ``sweep`` — offline what-ifs: the Fig. 6 grid (``n_tasks`` x ``acc`` x
  ``lat``) x ``instances_per_cell`` seeds per batch, ``batches`` batches
  from distinct seeds.

The same seed gives the same events in the same order.
"""

from __future__ import annotations

import heapq

import numpy as np

from bench import deploy


class Closed:
    def __init__(self, mix: dict, dep: deploy.Metro, seed: int):
        self.rng = np.random.default_rng(seed)
        self.dep = dep
        self.k = int(round(float(mix["churn_share"]) * dep.n_cells))
        self.live_per_cell = int(mix["live_per_cell"])
        self.warm_ticks = int(mix["warm_ticks"])
        self.fifo: list[list[int]] = [[] for _ in range(dep.n_cells)]
        self.sent: dict[int, int] = {}           # rid -> cell
        self.app_of: dict[int, int] = {}         # rid -> app, while held
        self.drops = [0] * dep.n_cells           # the engine's, last seen

    def _arrive(self, cell: int, app: int):
        from repro.core.events import Arrival

        req = deploy.request(self.dep, app)
        self.fifo[cell].append(req.request_id)
        self.sent[req.request_id] = cell
        self.app_of[req.request_id] = app
        return Arrival(req, cell)

    def initial(self) -> list:
        n_apps = len(self.dep.apps)
        share = np.arange(self.live_per_cell) % n_apps
        return [self._arrive(c, a) for c in range(self.dep.n_cells)
                for a in self.rng.permutation(share).tolist()]

    def tick(self, engine) -> list:
        """One tick's events: ``k`` cells each lose their oldest request
        and gain a new arrival; every request the engine dropped since the
        last tick is asked again."""
        from repro.core.events import Departure

        again = []
        for c, cell in enumerate(engine.cells):
            if cell.drops == self.drops[c]:
                continue
            self.drops[c] = cell.drops
            fifo = self.fifo[c]
            again += [(c, r) for r in fifo if engine.locate(r) != c]
            self.fifo[c] = [r for r in fifo if engine.locate(r) == c]
        cells = self.rng.choice(self.dep.n_cells, size=self.k, replace=False)
        apps = self.rng.permutation(np.arange(self.k) % len(self.dep.apps))
        events = []
        for c, a in zip(cells.tolist(), apps.tolist()):
            if self.fifo[c]:
                rid = self.fifo[c].pop(0)
                del self.app_of[rid]
                events.append(Departure(rid, c))
            events.append(self._arrive(c, a))
        return events + [self._arrive(c, self.app_of.pop(r))
                         for c, r in again]


class Open:
    def __init__(self, mix: dict, dep: deploy.Metro, seed: int):
        self.rng = np.random.default_rng(seed)
        self.dep = dep
        self.rate = float(mix["rate_per_s"])
        self.hold = float(mix["population"]) / self.rate
        self.tmax_slots = int(mix["tmax_slots"])
        self.prefill_s = float(mix["prefill_s"])
        self.prefill_step_s = float(mix["prefill_step_s"])
        self.warm_s = float(mix["warm_s"])
        self.next_t = self.rng.exponential(1.0 / self.rate)
        self.departures: list[tuple[float, int, int]] = []   # (due, rid, cell)
        self.sent: dict[int, int] = {}                       # rid -> cell

    def _arrival(self, due: float, cell: int, app: int):
        from repro.core.events import Arrival

        req = deploy.request(self.dep, app)
        hold = self.rng.exponential(self.hold)
        heapq.heappush(self.departures, (due + hold, req.request_id, cell))
        self.sent[req.request_id] = cell
        return Arrival(req, cell)

    def high_water(self) -> list:
        """The slot high-water burst at time 0 (see the module docstring)."""
        n_apps = len(self.dep.apps)
        return [self._arrival(0.0, 0, int(self.rng.integers(n_apps)))
                for _ in range(self.tmax_slots)]

    def due(self, now: float):
        """Every event due by ``now``, in due order. Returns the events, the
        arrivals among them as (rid, due, cell) and the departing ids."""
        from repro.core.events import Departure

        timed = []
        arrivals = []
        n_cells, n_apps = self.dep.n_cells, len(self.dep.apps)
        while self.next_t <= now:
            t = self.next_t
            ev = self._arrival(t, int(self.rng.integers(n_cells)),
                               int(self.rng.integers(n_apps)))
            timed.append((t, ev))
            arrivals.append((ev.request.request_id, t, ev.cell))
            self.next_t = t + self.rng.exponential(1.0 / self.rate)
        gone = []
        while self.departures and self.departures[0][0] <= now:
            t, rid, cell = heapq.heappop(self.departures)
            timed.append((t, Departure(rid, cell)))
            gone.append(rid)
        timed.sort(key=lambda x: x[0])
        return [ev for _, ev in timed], arrivals, gone


def sweep_seeds(mix: dict, seed: int) -> list[list[int]]:
    """Instance seeds for each batch of the run, distinct across batches."""
    rng = np.random.default_rng(seed)
    per, n = int(mix["instances_per_cell"]), int(mix["batches"])
    draw = rng.integers(0, 2 ** 62, size=per * n)
    return [draw[b * per:(b + 1) * per].tolist() for b in range(n)]
