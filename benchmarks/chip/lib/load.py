"""The load generator and the one poller, in one thread.

Every tick: send what is due through ``POST /prompt``, then one
``GET /history`` for all ids in flight.  A request is complete when its id
is on ``/history``; its completion time is when that GET returned.  Times
are seconds on the monotonic clock, relative to the window's start.
"""

from __future__ import annotations

import time

from .server import Http, Server
from .stats import median
from .traffic import Traffic, fill_graph

TICK_S = 0.01          # the poller's period; the run prints what it achieved
DRAIN_S = 30.0         # how long requests still in flight are awaited


class Loader:
    def __init__(self, server: Server, config: dict, traffic: Traffic,
                 run_tag: str):
        self.server = server
        self.config = config
        self.traffic = traffic
        self.run_tag = run_tag
        self.http: Http = server.http()
        self.records: list[dict] = []     # the window's requests
        self._sent = 0
        self.poll_gaps: list[float] = []

    def close(self) -> None:
        self.http.close()

    def _send(self, req: dict, t0: float, due: float, client=None) -> dict:
        prefix = f"{self.run_tag}_{self._sent:05d}"
        self._sent += 1
        graph = fill_graph(self.config, req, prefix)
        sent = time.monotonic() - t0
        status, doc = self.http.post(
            "/prompt", {"prompt": graph, "client_id": "chipbench"})
        rec = {"index": self._sent - 1, "client": client,
               "seed": req["seed"], "prefix": prefix, "due": due,
               "sent": sent, "done": None, "id": None, "entry": None}
        if status == 200 and doc and doc.get("prompt_id"):
            rec["id"] = doc["prompt_id"]
        else:
            # shed (429) or refused: a failure that misses every limit
            rec["entry"] = {"status": f"http_{status}", "error": doc}
        return rec

    def _poll(self, t0: float, inflight: dict) -> list[dict]:
        self.server.require_alive()
        hist = self.http.get("/history")
        now = time.monotonic() - t0
        done = []
        for pid in [p for p in inflight if p in hist]:
            rec = inflight.pop(pid)
            rec["done"] = now
            rec["entry"] = hist[pid]
            done.append(rec)
        return done

    def one(self, timeout: float) -> dict:
        """Warm-up: one request, awaited.  Not part of any window."""
        t0 = time.monotonic()
        rec = self._send(self.traffic.next_request(), t0, 0.0)
        inflight = {rec["id"]: rec} if rec["id"] else {}
        while inflight:
            if time.monotonic() - t0 > timeout:
                break
            self._poll(t0, inflight)
            time.sleep(0.05)
        return rec

    def window(self, seconds: float) -> dict:
        """Offer the mix's load for ``seconds``, then await what is still
        in flight for at most DRAIN_S.  Returns the window's facts; the
        per-request records are in ``self.records``."""
        closed = self.traffic.loop == "closed"
        schedule = [] if closed else self.traffic.schedule(seconds)
        idle_clients = list(range(self.traffic.clients)) if closed else []
        inflight: dict = {}
        next_due = 0
        last_poll = None
        t0 = time.monotonic()

        def launch(req: dict, due: float, client=None) -> None:
            rec = self._send(req, t0, due, client)
            self.records.append(rec)
            if rec["id"]:
                inflight[rec["id"]] = rec

        while True:
            tick = time.monotonic()
            now = tick - t0
            if now < seconds:
                while idle_clients:
                    launch(self.traffic.next_request(), now,
                           idle_clients.pop())
                while next_due < len(schedule) \
                        and schedule[next_due]["due"] <= time.monotonic() - t0:
                    launch(schedule[next_due], schedule[next_due]["due"])
                    next_due += 1
            elif not inflight or now > seconds + DRAIN_S:
                break
            if inflight:
                for rec in self._poll(t0, inflight):
                    if closed and rec["done"] < seconds:
                        idle_clients.append(rec["client"])
                polled = time.monotonic()
                if last_poll is not None:
                    self.poll_gaps.append(polled - last_poll)
                last_poll = polled
            else:
                last_poll = None
            if idle_clients and time.monotonic() - t0 < seconds:
                continue            # a freed client sends at once
            wake = tick + TICK_S
            if next_due < len(schedule):
                wake = min(wake, t0 + schedule[next_due]["due"])
            time.sleep(max(wake - time.monotonic(), 0.0))
        if next_due < len(schedule):
            # the generator fell so far behind that arrivals were never
            # sent: they were due, so they count as attempted and failed
            for req in schedule[next_due:]:
                self.records.append({
                    "index": None, "client": None, "seed": req["seed"],
                    "prefix": None, "due": req["due"], "sent": None,
                    "done": None, "id": None,
                    "entry": {"status": "never_sent"}})
        late = [r["sent"] - r["due"] for r in self.records
                if r["sent"] is not None]
        return {
            "t0_monotonic": t0, "seconds": seconds,
            "ended_s": time.monotonic() - t0,
            "still_in_flight": len(inflight),
            "poll_gap_p50_ms": 1e3 * median(self.poll_gaps)
            if self.poll_gaps else None,
            "poll_gap_max_ms": 1e3 * max(self.poll_gaps)
            if self.poll_gaps else None,
            "generator_late_p50_ms": 1e3 * median(late) if late else None,
            "generator_late_max_ms": 1e3 * max(late) if late else None,
        }
