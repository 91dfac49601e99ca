"""The traffic twin: real policy code on virtual time (ISSUE 19).

:class:`FleetSim` wires the PRODUCTION control-plane classes — the
:class:`~..workflow.scheduler.AdmissionController` (token buckets,
class shed bars, stride fair dequeue via
:func:`~..workflow.scheduler.pop_fair_group`), the
:class:`~..runtime.cluster.ClusterRegistry` lease state machine, the
:class:`~..runtime.cluster.WorkLedger` (exactly-once check-in, hedge
bars, reassignment), the :class:`~..runtime.autoscale.FleetAutoscaler`
reconciliation math and the :class:`~..runtime.shard.HashRing` — into a
discrete-event harness.  None of them are forked or mocked: each is
constructed with the PR 19 ``clock=`` seam pointed at the engine's
:class:`~.engine.VirtualClock`, so the admission decision a scenario
produces is the decision production would have made at that instant.

What IS virtual: workers (a service-time sample instead of a denoise),
the network (a :class:`~.faults.SimChaos` roll instead of a socket) and
time itself.  The fidelity contract is enforced by
``tests/test_sim.py::TestCalibration``: the sim must reproduce the
committed overload and multimaster records
(``benchmarks/scenarios/*.measured.json``) within tolerance before any
sweep result is worth reading.

Mechanics mirrored from the live harness rather than idealized:

- dispatch consults ``registry.state()`` — a freshly-killed worker
  keeps winning dispatches until its lease expires, and those units
  stall until the death sweep sees DEAD and reassigns them (this is
  where the post-kill latency bump comes from);
- a dropped completion message retries with doubling backoff and
  re-rolls chaos each attempt, and the ledger's exactly-once check-in
  dedupes the hedge losers exactly as the blend path does;
- a killed master's queue and in-flight prompts are absorbed by its
  live-ring successor (``HashRing.successor`` semantics) after its
  master-lease expiry, re-enqueued under their original ids, and the
  ring epoch bumps — the multimaster bench's takeover shape.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from comfyui_distributed_tpu.runtime import cluster as cl
from comfyui_distributed_tpu.runtime.autoscale import FleetAutoscaler
from comfyui_distributed_tpu.runtime.shard import HashRing
from comfyui_distributed_tpu.sim import traffic as traffic_mod
from comfyui_distributed_tpu.sim.engine import (Engine, VirtualClock,
                                                percentile)
from comfyui_distributed_tpu.sim.faults import SimChaos
from comfyui_distributed_tpu.sim.scenario import Scenario
from comfyui_distributed_tpu.sim.service import ServiceModel
from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils.clock import Rng
from comfyui_distributed_tpu.workflow.scheduler import (
    AdmissionController, pop_fair_group)


def _per_class(raw: Any, classes, default: float) -> Dict[str, float]:
    """Admission rate/burst knobs accept a scalar (applied to every
    class) or an explicit per-class dict, like the env parser does."""
    if isinstance(raw, dict):
        return dict(raw)
    if raw is None:
        return {c: default for c in classes}
    return {c: float(raw) for c in classes}


class SimWorker:
    """Virtual compute: one prompt (job) at a time off a FIFO of
    ``(job_id, unit)`` tasks.  ``epoch`` invalidates in-flight
    completion events across a kill."""

    __slots__ = ("wid", "seq", "alive", "retired", "epoch", "fifo",
                 "busy")

    def __init__(self, wid: str, seq: int = 0):
        self.wid = wid
        self.seq = seq        # registration order (dispatch scan order)
        self.alive = True
        self.retired = False
        self.epoch = 0
        self.fifo: List[tuple] = []
        self.busy: Optional[tuple] = None   # (jid, unit, end_t, epoch)

    def load(self) -> int:
        return len(self.fifo) + (1 if self.busy is not None else 0)


class SimMaster:
    """One control-plane shard: its own admission, queue, registry and
    ledger (and optionally an autoscaler) — all on the shared virtual
    clock."""

    def __init__(self, mid: str, sc: Scenario, vclock: VirtualClock):
        self.mid = mid
        self.alive = True
        adm = sc.admission
        classes = C.TENANT_CLASSES
        self.max_queue = int(adm.get("max_queue", 0))
        self.admission = AdmissionController(
            weights=dict(adm.get("weights")
                         or C.TENANT_WEIGHTS_DEFAULT),
            shed=dict(adm.get("shed") or C.TENANT_SHED_DEFAULT),
            rate=_per_class(adm.get("rate"), classes, 0.0),
            burst=_per_class(adm.get("burst"), classes,
                             C.TENANT_BURST_DEFAULT),
            default_class=adm.get("default_class"),
            clock=vclock)
        clu = sc.cluster
        self.registry = cl.ClusterRegistry(
            lease_s=float(clu.get("lease_s", C.LEASE_DEFAULT)),
            suspect_probes=int(clu.get("suspect_probes",
                                       C.SUSPECT_PROBES_DEFAULT)),
            clock=vclock)
        self.ledger = cl.WorkLedger(clock=vclock)
        self.queue: List[Dict[str, Any]] = []
        self.scaler: Optional[FleetAutoscaler] = None


class FleetSim:
    """One deterministic run of a :class:`~.scenario.Scenario`."""

    def __init__(self, sc: Scenario):
        self.sc = sc
        self.engine = Engine()
        self.vclock = self.engine.clock
        self.rng = Rng(sc.seed)
        self.chaos = SimChaos(sc.chaos, self.rng.fork("chaos"))
        svc_rng = self.rng.fork("service")
        self.service = ServiceModel(sc.service, svc_rng)
        self.service_per_class = {
            str(k): ServiceModel(v, svc_rng)
            for k, v in (sc.service.get("per_class") or {}).items()}
        self.units_per_job = max(int(sc.service.get("units", 1)), 1)

        mids = list(sc.masters) or ["master"]
        self.masters: Dict[str, SimMaster] = {
            mid: SimMaster(mid, sc, self.vclock) for mid in mids}
        self.multi = len(mids) > 1
        self.ring = HashRing({m: None for m in mids},
                             sc.vnodes if sc.vnodes is not None
                             else C.SHARD_VNODES_DEFAULT)
        self.ring_epoch = 1
        self.takeovers = 0
        self.absorbed: List[str] = []
        self.takeover_successor: Optional[str] = None

        self.workers: Dict[str, SimWorker] = {}
        # idle-candidate pool (wid -> None), maintained incrementally at
        # every busy/fifo/liveness transition so dispatch never has to
        # scan the whole fleet.  A dict, not a set: iteration order must
        # not depend on str hash randomization or determinism dies
        # across processes.  Entries may go stale (a worker handed work
        # elsewhere); readers verify and evict lazily.
        self._idle: Dict[str, None] = {}
        self._wseq = 0
        for i in range(max(int(sc.workers), 0)):
            self._add_worker(f"w{i}")
        self._auto_n = 0

        clu = sc.cluster
        self.heartbeat_s = float(clu.get(
            "heartbeat_s",
            max(float(clu.get("lease_s", C.LEASE_DEFAULT))
                / C.HEARTBEAT_FRACTION, 0.05)))
        self.sweep_s = float(clu.get("sweep_s", 0.25))
        self.retry_backoff_s = float(clu.get("retry_backoff_s", 0.25))
        self.retry_attempts = int(clu.get("retry_attempts", 8))
        self.master_lease_s = float(clu.get("master_lease_s", 2.0))
        h = sc.hedge
        self.hedge_enabled = bool(h.get("enabled", True))
        self.hedge_factor = float(h.get("factor",
                                        C.HEDGE_FACTOR_DEFAULT))
        self.hedge_min_pct = float(h.get("min_progress_pct",
                                         C.HEDGE_PCT_DEFAULT))
        self.hedge_min_wait = float(h.get("min_wait_s",
                                          C.HEDGE_MIN_WAIT_DEFAULT))
        self.hedge_sweep_s = float(h.get("sweep_s", 0.5))

        # fleet-level outcome state (admission counters stay inside the
        # real controllers; completions and latencies are counted here
        # because an absorbed prompt finishes on a DIFFERENT master than
        # the one whose admission admitted it)
        self.jobs: Dict[str, Dict[str, Any]] = {}
        self.completed: Dict[str, int] = {}
        self.latencies: Dict[str, List[float]] = {}
        self.counters: Dict[str, int] = {}
        self.open_jobs = 0
        self._arrivals_open = 0
        self._pid_seq = 0
        self.finished = False
        self.load_wall_s: Optional[float] = None

        # capture-schema export (ISSUE 20): completed sim jobs stream
        # through the REAL TraceExporter as schema-1 segment files, so
        # `cli analyze`/`why --export-dir` and the bench's regression
        # diff run unchanged on synthetic traffic.  Ids are md5 of
        # (scenario, seed, job) — deterministic, no wall clock.
        self.capture = None
        if sc.capture_dir:
            from comfyui_distributed_tpu.utils import trace_export
            self.capture = trace_export.TraceExporter(sc.capture_dir)

    # -- construction helpers -------------------------------------------------

    def _add_worker(self, wid: str) -> SimWorker:
        self._wseq += 1
        w = SimWorker(wid, seq=self._wseq)
        self.workers[wid] = w
        self._idle[wid] = None
        for m in self.masters.values():
            m.registry.register(wid, info={"name": wid}, alive=True)
        return w

    def _bump(self, key: str, n: int = 1) -> None:
        self.counters[key] = self.counters.get(key, 0) + n

    # -- run ------------------------------------------------------------------

    def run(self) -> Dict[str, Any]:
        sc = self.sc
        eng = self.engine
        if sc.arrivals is not None:
            self._arrivals_open = 1
            seq = sorted(
                (float(a.get("t", 0.0)), i, a)
                for i, a in enumerate(sc.arrivals))
            self._schedule_replay(iter(seq))
        else:
            for spec in sc.traffic:
                gen = traffic_mod.arrivals(
                    spec, self.rng.fork(f"traffic:{spec.cls}"),
                    sc.duration_s)
                self._arrivals_open += 1
                self._schedule_next_arrival(spec, gen)
        for j in sc.jobs:
            self._arrivals_open += 1

            def fire(j=j):
                self._arrive(str(j.get("cls", "paid")),
                             str(j.get("client", "jobs")),
                             slo_s=j.get("slo_s"),
                             service_s=j.get("service_s"),
                             units=j.get("units"),
                             preadmitted=True)
                self._arrivals_open -= 1
                self._maybe_finish()
            eng.at(float(j.get("t", 0.0)), fire)
        for m in sorted(self.masters):
            self._schedule_heartbeats(m)
            self._schedule_death_sweep(m)
            if self.hedge_enabled:
                self._schedule_hedge_sweep(m)
            if sc.autoscale:
                self._arm_autoscaler(self.masters[m])
        for f in sc.faults:
            eng.at(float(f.get("t", 0.0)),
                   self._fault_fn(str(f.get("kind")),
                                  str(f.get("id", ""))))
        if self._arrivals_open == 0:
            self._maybe_finish()
        eng.run(until=sc.duration_s + sc.drain_limit_s)
        if self.load_wall_s is None:
            # wedged (drain limit hit): report the truth, never a fake
            self.load_wall_s = self.vclock.now
            self._bump("wedged")
        if self.capture is not None:
            self.capture.close()
        return self.summary()

    # -- arrivals -------------------------------------------------------------

    def _schedule_next_arrival(self, spec, gen) -> None:
        try:
            t, client = next(gen)
        except StopIteration:
            self._arrivals_open -= 1
            self._maybe_finish()
            return
        def fire():
            self._arrive(spec.cls, client, slo_s=spec.slo_s)
            self._schedule_next_arrival(spec, gen)
        self.engine.at(t, fire)

    def _schedule_replay(self, it) -> None:
        try:
            t, _, a = next(it)
        except StopIteration:
            self._arrivals_open -= 1
            self._maybe_finish()
            return
        def fire():
            self._arrive(str(a.get("cls", "")),
                         str(a.get("client", "replay")),
                         service_s=a.get("service_s"),
                         units=a.get("units"))
            self._schedule_replay(it)
        self.engine.at(t, fire)

    def _route(self, pid: str) -> SimMaster:
        if not self.multi:
            return self.masters[next(iter(self.masters))]
        owner = self.ring.owner(pid)
        m = self.masters.get(owner) if owner else None
        if m is not None and m.alive:
            return m
        # owner down and not yet absorbed: the router's re-pull lands
        # the prompt on the live ring's owner (real router behavior)
        live = HashRing({mid: None for mid, mm in self.masters.items()
                         if mm.alive}, self.ring.vnodes)
        return self.masters[live.owner(pid) or next(
            mid for mid in sorted(self.masters)
            if self.masters[mid].alive)]

    def _arrive(self, cls: str, client: str,
                slo_s: Optional[float] = None,
                service_s: Optional[Any] = None,
                units: Optional[int] = None,
                preadmitted: bool = False) -> None:
        self._pid_seq += 1
        pid = f"p{self._pid_seq}"
        m = self._route(pid)
        tenant = m.admission.classify(cls)
        if not preadmitted:
            rej = m.admission.admit(tenant, client, len(m.queue),
                                    self.max_queue_of(m))
            if rej is not None:
                self.engine.log(
                    f"shed {pid} {tenant} {rej['reason']}")
                return
        now = self.vclock.now
        item = {"pid": pid, "tenant": tenant, "client": client,
                "sig": None, "arrival": now}
        if service_s is not None:
            item["service_s"] = float(service_s)
        if slo_s is not None:
            item["slo_s"] = float(slo_s)
        if units is not None:
            item["units"] = max(int(units), 1)
        if preadmitted:
            # scheduled fan-out jobs ride outside the per-class books,
            # like the bench's out-of-band fanout_pids: they consume
            # real capacity but never skew the stream comparisons —
            # and their tile shares go STRAIGHT to the workers' FIFOs
            # at admit time (the live interceptor posts shares to the
            # HTTP workers directly; only plain prompts queue)
            item["fanout"] = True
            self._dispatch_fanout(m, item)
            return
        m.queue.append(item)
        self.engine.log(f"admit {pid} {tenant} q={len(m.queue)}")
        self._dispatch(m)

    def _dispatch_fanout(self, m: SimMaster,
                         item: Dict[str, Any]) -> None:
        jid = item["pid"]
        n_units = max(int(item.get("units", 1)), 1)
        pool = [self.workers[wid] for wid in sorted(self.workers)
                if not self.workers[wid].retired
                and m.registry.state(wid) == cl.HEALTHY]
        if not pool:
            pool = [self.workers[wid] for wid in sorted(self.workers)
                    if not self.workers[wid].retired]
        if not pool:
            return
        pool.sort(key=lambda w: w.load())
        assign = {u: pool[u % len(pool)] for u in range(n_units)}
        m.ledger.create_job(jid,
                            {u: w.wid for u, w in assign.items()},
                            kind="tile")
        if "slo_s" in item:
            m.ledger.set_deadline(jid, item["arrival"] + item["slo_s"])
        self.jobs[jid] = {"tenant": item["tenant"],
                          "arrival": item["arrival"],
                          "master": m.mid, "item": item,
                          "units": n_units, "cancelled": False,
                          "dispatched_at": self.vclock.now}
        self.open_jobs += 1
        for u in sorted(assign):
            assign[u].fifo.append((jid, u))
        self.engine.log(f"fanout {jid} x{n_units}")
        for w in {w.wid: w for w in assign.values()}.values():
            self._kick(w)

    def max_queue_of(self, m: SimMaster) -> int:
        return m.max_queue

    # -- dispatch -------------------------------------------------------------

    def _pool_update(self, w: SimWorker) -> None:
        if w.alive and not w.retired and w.busy is None \
                and not w.fifo:
            self._idle[w.wid] = None
        else:
            self._idle.pop(w.wid, None)

    def _idle_candidates(self) -> List[SimWorker]:
        """Verified idle workers in registration order (the order the
        old full-fleet scan produced), evicting stale pool entries."""
        out = []
        for wid in list(self._idle):
            w = self.workers.get(wid)
            if w is None or not w.alive or w.retired \
                    or w.busy is not None or w.fifo:
                del self._idle[wid]
                continue
            out.append(w)
        out.sort(key=lambda w: w.seq)
        return out

    def _idle_dispatchable(self, m: SimMaster) -> List[SimWorker]:
        return [w for w in self._idle_candidates()
                if m.registry.state(w.wid) == cl.HEALTHY]

    def _take_idle(self, m: SimMaster,
                   exclude: Optional[str] = None) -> \
            Optional[SimWorker]:
        """First dispatchable idle worker, paying ``registry.state()``
        only until the first hit — the common (single-unit) dispatch
        never scans the fleet."""
        for w in self._idle_candidates():
            if exclude is not None and w.wid == exclude:
                continue
            if m.registry.state(w.wid) == cl.HEALTHY:
                return w
        return None

    def _dispatch(self, m: SimMaster) -> None:
        if not m.alive:
            return
        while m.queue:
            first = self._take_idle(m)
            if first is None:
                return
            group = pop_fair_group(m.queue, m.admission,
                                   coalesce_max=1)
            if not group:
                return
            item = group[0]
            jid = item["pid"]
            n_units = max(int(item.get("units", self.units_per_job)),
                          1)
            units = list(range(n_units))
            # multi-unit jobs FAN OUT over the idle workers (the tiled
            # dispatch the live master does); plain jobs take one
            idle = [first] if n_units == 1 \
                else (self._idle_dispatchable(m) or [first])
            assign = {u: idle[u % len(idle)] for u in units}
            m.ledger.create_job(
                jid, {u: w.wid for u, w in assign.items()},
                kind="tile" if n_units > 1 else "sim")
            if "slo_s" in item:
                m.ledger.set_deadline(
                    jid, item["arrival"] + item["slo_s"])
            self.jobs[jid] = {"tenant": item["tenant"],
                              "arrival": item["arrival"],
                              "master": m.mid,
                              "item": item,
                              "units": n_units,
                              "cancelled": False,
                              "dispatched_at": self.vclock.now}
            self.open_jobs += 1
            for u in units:
                assign[u].fifo.append((jid, u))
            self.engine.log(
                f"dispatch {jid} -> "
                f"{','.join(sorted(set(w.wid for w in assign.values())))}")
            for w in {id(w): w for w in assign.values()}.values():
                self._kick(w)

    def _service_sample(self, jid: str) -> float:
        job = self.jobs.get(jid)
        if job is not None:
            fixed = job["item"].get("service_s")
            if fixed is not None:
                return max(float(fixed) / job.get("units", 1), 1e-6)
            model = self.service_per_class.get(job["tenant"])
            if model is not None:
                return model.sample()
        return self.service.sample()

    def _kick(self, w: SimWorker) -> None:
        if not w.alive or w.busy is not None or not w.fifo:
            self._pool_update(w)
            return
        jid, unit = w.fifo.pop(0)
        job = self.jobs.get(jid)
        if job is None or job["cancelled"] \
                or job["master"] not in self.masters \
                or not self.masters[job["master"]].alive:
            self._kick(w)
            return
        end = self.vclock.now + self._service_sample(jid)
        if self.capture is not None:
            # last kick wins — exactly the newest-wins semantics a
            # redispatched/hedged unit has in the live recorder
            job.setdefault("unit_spans", {})[unit] = \
                [w.wid, self.vclock.now, end, None]
        w.busy = (jid, unit, end, w.epoch)
        self._idle.pop(w.wid, None)
        epoch = w.epoch
        self.engine.at(end, lambda: self._complete(w, jid, unit, epoch))

    def _complete(self, w: SimWorker, jid: str, unit: int,
                  epoch: int) -> None:
        if w.epoch != epoch or not w.alive:
            return   # the worker died mid-compute; the unit stays
        w.busy = None
        self._deliver(w, jid, unit, attempt=0)
        self._kick(w)
        for mid in sorted(self.masters):
            self._dispatch(self.masters[mid])

    # -- completion delivery (chaos-mediated message edge) --------------------

    def _deliver(self, w: SimWorker, jid: str, unit: int,
                 attempt: int) -> None:
        job = self.jobs.get(jid)
        if job is None or job["cancelled"]:
            return
        m = self.masters.get(job["master"])
        if m is None or not m.alive:
            return   # delivery to a dead master: the absorb re-runs it
        fate, delay = self.chaos.message_edge(
            "/distributed/job_complete")
        if fate == "drop":
            self._bump("deliveries_dropped")
            if attempt + 1 >= self.retry_attempts:
                self._bump("deliveries_lost")
                self.engine.log(f"lost {jid}/{unit} from {w.wid}")
                return   # hedge/reassign sweeps rescue the unit
            backoff = min(self.retry_backoff_s * (2 ** attempt), 2.0)
            self.engine.after(
                backoff,
                lambda: self._deliver(w, jid, unit, attempt + 1))
            return
        if delay > 0:
            self.engine.after(
                delay, lambda: self._land(w, jid, unit))
            return
        self._land(w, jid, unit)

    def _land(self, w: SimWorker, jid: str, unit: int) -> None:
        job = self.jobs.get(jid)
        if job is None or job["cancelled"]:
            return
        m = self.masters.get(job["master"])
        if m is None or not m.alive:
            return
        m.registry.touch(w.wid)
        if not m.ledger.check_in(jid, unit, w.wid):
            self._bump("duplicate_checkins")
            return
        self.engine.log(f"checkin {jid}/{unit} by {w.wid}")
        if self.capture is not None:
            us = job.get("unit_spans", {}).get(unit)
            if us is not None and us[0] == w.wid:
                us[3] = self.vclock.now   # delivery landed (upload end)
        done, total = m.ledger.progress(jid)
        if done >= total:
            self._finish_job(m, jid)

    def _finish_job(self, m: SimMaster, jid: str) -> None:
        job = self.jobs.get(jid)
        if job is None:
            return
        summary = m.ledger.finish_job(jid) or {}
        tenant = job["tenant"]
        book = "fanout" if job["item"].get("fanout") else tenant
        self.completed[book] = self.completed.get(book, 0) + 1
        self.latencies.setdefault(book, []).append(
            self.vclock.now - job["arrival"])
        self._bump("reassigned_units",
                   int(summary.get("reassigned_units", 0)))
        self._bump("hedged_units", int(summary.get("hedged_units", 0)))
        if book != "fanout":
            m.admission.on_complete(tenant)
        if self.capture is not None:
            self.capture.export(self._capture_record(jid, job))
        del self.jobs[jid]
        self.open_jobs -= 1
        self.engine.log(f"done {jid} {tenant}")
        self._maybe_finish()

    def _capture_record(self, jid: str,
                        job: Dict[str, Any]) -> Dict[str, Any]:
        """One finished sim job as a schema-1 capture record: a root
        ``job`` span over the whole interval, a ``queue_wait`` child
        (arrival -> dispatch), per-unit ``dispatch`` / ``compute`` /
        ``upload`` children on the serving worker's lane.  Virtual-
        clock timestamps, md5-deterministic ids — byte-stable across
        runs of the same (scenario, seed)."""
        import hashlib
        now = self.vclock.now
        arrival = float(job["arrival"])
        trace_id = hashlib.md5(
            f"{self.sc.name}:{self.sc.seed}:{jid}".encode()).hexdigest()
        spans: List[Dict[str, Any]] = []
        sseq = [0]

        def span(name, start, end, parent, attrs=None):
            sseq[0] += 1
            sid = hashlib.md5(
                f"{trace_id}:{sseq[0]}".encode()).hexdigest()[:16]
            spans.append({
                "trace_id": trace_id, "span_id": sid,
                "parent_id": parent, "name": name,
                "start_s": round(start, 6), "end_s": round(end, 6),
                "duration_s": round(max(end - start, 0.0), 6),
                "status": "ok", "attrs": dict(attrs or {})})
            return sid

        root = span("job", arrival, now, None,
                    {"prompt_id": jid, "tenant": job["tenant"]})
        dispatched = min(max(float(job.get("dispatched_at", arrival)),
                             arrival), now)
        if dispatched > arrival:
            span("queue_wait", arrival, dispatched, root)
        for unit in sorted(job.get("unit_spans", {})):
            wid, cstart, cend, landed = job["unit_spans"][unit]
            cstart = max(min(float(cstart), now), arrival)
            cend = max(min(float(cend), now), cstart)
            at = {"worker": wid, "tile_idx": unit}
            if cstart > dispatched:
                span("dispatch", dispatched, cstart, root, at)
            span("compute", cstart, cend, root, at)
            if landed is not None and landed > cend:
                span("upload", cend, min(float(landed), now), root, at)
        return {"prompt_id": jid, "trace_id": trace_id,
                "status": "ok", "root_span_id": root,
                "duration_s": round(now - arrival, 6),
                "finished_at": round(now, 6), "spans": spans}

    def _maybe_finish(self) -> None:
        if self.finished or self._arrivals_open > 0 \
                or self.open_jobs > 0:
            return
        if any(m.queue for m in self.masters.values()):
            return
        self.finished = True
        self.load_wall_s = self.vclock.now
        self.engine.log("drained")

    # -- periodic planes ------------------------------------------------------

    def _schedule_heartbeats(self, mid: str) -> None:
        def beat():
            m = self.masters[mid]
            if self.finished or not m.alive:
                return
            for wid in self.workers:
                w = self.workers[wid]
                if not w.alive or w.retired:
                    continue
                if self.chaos.heartbeat_frozen(wid):
                    continue
                fate, _ = self.chaos.message_edge(
                        "/distributed/heartbeat")
                if fate == "drop":
                    continue
                m.registry.heartbeat(
                    wid, info={"queue_remaining": w.load()})
            self.engine.after(self.heartbeat_s, beat)
        self.engine.after(self.heartbeat_s, beat)

    def _schedule_death_sweep(self, mid: str) -> None:
        def sweep():
            m = self.masters[mid]
            if self.finished or not m.alive:
                return
            for jid in [j for j, job in self.jobs.items()
                        if job["master"] == mid
                        and not job["cancelled"]]:
                owners = m.ledger.owners_of_pending(jid)
                by_owner: Dict[str, List[Any]] = {}
                for u, o in owners.items():
                    by_owner.setdefault(o, []).append(u)
                for owner in sorted(by_owner):
                    if m.registry.state(owner) != cl.DEAD:
                        continue
                    target = self._least_loaded(m, exclude=owner)
                    if target is None:
                        continue
                    moved = m.ledger.reassign(jid, by_owner[owner],
                                              target.wid)
                    if moved:
                        self._bump("sweep_reassigns", len(moved))
                        self.engine.log(
                            f"reassign {jid} {owner}->{target.wid} "
                            f"x{len(moved)}")
                        target.fifo.extend((jid, u) for u in moved)
                        self._kick(target)
            self._dispatch(m)
            self.engine.after(self.sweep_s, sweep)
        self.engine.after(self.sweep_s, sweep)

    def _schedule_hedge_sweep(self, mid: str) -> None:
        def sweep():
            m = self.masters[mid]
            if self.finished or not m.alive:
                return
            for jid in [j for j, job in self.jobs.items()
                        if job["master"] == mid
                        and not job["cancelled"]]:
                overdue = m.ledger.overdue_units(
                    jid, factor=self.hedge_factor,
                    min_progress_pct=self.hedge_min_pct,
                    min_wait_s=self.hedge_min_wait)
                if not overdue:
                    continue
                for u in sorted(overdue, key=str):
                    owner = overdue[u]
                    target = self._hedge_target(m, owner)
                    if target is None:
                        continue
                    hedged = m.ledger.mark_hedged(jid, [u],
                                                  hedge_owner=target.wid)
                    if not hedged:
                        continue
                    self._bump("hedges")
                    self.engine.log(
                        f"hedge {jid}/{u} {owner}->{target.wid}")
                    target.fifo.append((jid, u))
                    self._kick(target)
            self.engine.after(self.hedge_sweep_s, sweep)
        self.engine.after(self.hedge_sweep_s, sweep)

    def _least_loaded(self, m: SimMaster,
                      exclude: str) -> Optional[SimWorker]:
        best = None
        for wid in sorted(self.workers):
            if wid == exclude:
                continue
            w = self.workers[wid]
            if w.retired or m.registry.state(wid) != cl.HEALTHY:
                continue
            if best is None or w.load() < best.load():
                best = w
        return best

    def _hedge_target(self, m: SimMaster,
                      owner: str) -> Optional[SimWorker]:
        return self._take_idle(m, exclude=owner)

    # -- autoscaler -----------------------------------------------------------

    def _arm_autoscaler(self, m: SimMaster) -> None:
        au = dict(self.sc.autoscale or {})

        def spawner() -> Optional[str]:
            self._auto_n += 1
            wid = f"auto_w{self._auto_n}"
            w = self._add_worker(wid)
            for mm in self.masters.values():
                mm.registry.heartbeat(wid)
            self.engine.log(f"spawn {wid}")
            self.engine.after(0.0, lambda: self._dispatch(m))
            return w.wid

        def retirer(wid: str) -> bool:
            w = self.workers.get(wid)
            if w is None:
                return False
            w.retired = True
            w.alive = False
            w.epoch += 1
            self._idle.pop(wid, None)
            self.engine.log(f"retire {wid}")
            return True

        def worker_queue(wid: str) -> Optional[int]:
            w = self.workers.get(wid)
            return None if w is None else w.load()

        cooldown = float(au.get("cooldown_s",
                                C.AUTOSCALE_COOLDOWN_DEFAULT))
        m.scaler = FleetAutoscaler(
            registry=m.registry,
            queue_depth_fn=lambda: len(m.queue),
            util_fn=None,
            spawner=spawner,
            retirer=retirer,
            worker_queue_fn=worker_queue,
            min_workers=int(au.get("min_workers", 1)),
            max_workers=int(au.get("max_workers", 4)),
            up_queue=float(au.get("up_queue",
                                  C.AUTOSCALE_UP_QUEUE_DEFAULT)),
            down_queue=float(au.get("down_queue",
                                    C.AUTOSCALE_DOWN_QUEUE_DEFAULT)),
            up_util=float(au.get("up_util", 2.0)),
            down_util=float(au.get("down_util", 0.0)),
            window=int(au.get("window", C.AUTOSCALE_WINDOW_DEFAULT)),
            cooldown_s=cooldown,
            interval_s=float(au.get("interval_s", 0.25)),
            drain_s=float(au.get("drain_s", C.AUTOSCALE_DRAIN_DEFAULT)),
            flap_window_s=float(au["flap_window_s"])
            if "flap_window_s" in au
            else min(2.0 * cooldown, C.AUTOSCALE_FLAP_S),
            clock=self.vclock)

        def tick():
            if self.finished or not m.alive:
                return
            m.scaler.sample_once()
            self.engine.after(m.scaler.interval_s, tick)
        self.engine.after(m.scaler.interval_s, tick)

    # -- faults ---------------------------------------------------------------

    def _fault_fn(self, kind: str, target: str):
        if kind == "kill_master":
            return lambda: self._kill_master(target)
        return lambda: self._kill_worker(target)

    def _kill_worker(self, wid: str) -> None:
        w = self.workers.get(wid)
        if w is None or not w.alive:
            return
        w.alive = False
        w.epoch += 1
        w.busy = None
        w.fifo.clear()     # pending units stay in the ledgers; the
        self._idle.pop(wid, None)
        self._bump("worker_kills")  # death sweeps reassign after lease
        self.engine.log(f"kill_worker {wid}")

    def _kill_master(self, mid: str) -> None:
        m = self.masters.get(mid)
        if m is None or not m.alive or not self.multi:
            return
        m.alive = False
        self._bump("master_kills")
        self.engine.log(f"kill_master {mid}")
        # drop the dead shard's tasks from worker FIFOs; in-flight
        # compute is wasted (delivery to a dead master goes nowhere)
        for w in self.workers.values():
            w.fifo = [(j, u) for (j, u) in w.fifo
                      if self.jobs.get(j, {}).get("master") != mid]
            self._pool_update(w)
        self.engine.after(self.master_lease_s,
                          lambda: self._absorb(mid))

    def _absorb(self, dead_id: str) -> None:
        """Lease-expiry takeover: the live-ring successor absorbs the
        dead shard — the sim analog of ``ShardManager.watch_once`` +
        ``absorb``, with the SAME successor choice the production ring
        computes."""
        dead = self.masters.get(dead_id)
        if dead is None or dead.alive:
            return
        live = HashRing({mid: None for mid, m in self.masters.items()
                         if m.alive}, self.ring.vnodes)
        succ_id = live.owner(dead_id)
        if succ_id is None:
            return
        succ = self.masters[succ_id]
        moved = 0
        # queued prompts transfer as-is (absorb bypasses re-admission,
        # like enqueue_prompt(_recovered=True))
        for item in dead.queue:
            succ.queue.append(item)
            moved += 1
        dead.queue.clear()
        # in-flight jobs re-run from scratch under their original ids
        for jid in [j for j, job in self.jobs.items()
                    if job["master"] == dead_id]:
            job = self.jobs.pop(jid)
            self.open_jobs -= 1
            dead.ledger.finish_job(jid)
            succ.queue.append(job["item"])
            moved += 1
        self.ring = live
        self.ring_epoch += 1
        self.takeovers += 1
        self.absorbed.append(dead_id)
        self.takeover_successor = succ_id
        self._bump("absorbed_prompts", moved)
        self.engine.log(f"takeover {dead_id}->{succ_id} "
                        f"moved={moved} epoch={self.ring_epoch}")
        self._dispatch(succ)
        self._maybe_finish()

    # -- results --------------------------------------------------------------

    def summary(self) -> Dict[str, Any]:
        per_class: Dict[str, Any] = {}
        admitted_total = 0
        completed_total = 0
        shed_total = 0
        for cls in C.TENANT_CLASSES:
            adm = {"admitted": 0, "shed_rate": 0, "shed_overload": 0}
            for m in self.masters.values():
                c = m.admission.counters.get(cls) or {}
                for k in adm:
                    adm[k] += int(c.get(k, 0))
            lat = sorted(self.latencies.get(cls, ()))
            done = self.completed.get(cls, 0)
            if not any(adm.values()) and not done:
                continue
            admitted_total += adm["admitted"]
            completed_total += done
            shed_total += adm["shed_rate"] + adm["shed_overload"]
            per_class[cls] = {
                **adm,
                "completed": done,
                "p50_s": round(percentile(lat, 0.50), 4),
                "p95_s": round(percentile(lat, 0.95), 4),
                "mean_s": round(sum(lat) / len(lat), 4) if lat else 0.0,
            }
        out: Dict[str, Any] = {
            "name": self.sc.name,
            "seed": self.sc.seed,
            "virtual_duration_s": round(self.vclock.now, 4),
            "load_wall_s": round(self.load_wall_s, 4)
            if self.load_wall_s is not None else None,
            "drained": self.finished,
            "events": self.engine.events_processed,
            "log_lines": self.engine.log_lines,
            "log_digest": self.engine.log_digest(),
            "per_class": per_class,
            "admitted_total": admitted_total,
            "completed_total": completed_total,
            "shed_total": shed_total,
            "completion_rate": round(
                completed_total / admitted_total, 4)
            if admitted_total else 1.0,
            "counters": dict(sorted(self.counters.items())),
            "chaos": self.chaos.snapshot(),
            "workers_final": sum(1 for w in self.workers.values()
                                 if w.alive and not w.retired),
        }
        if self.sc.jobs:
            fan = sorted(self.latencies.get("fanout", ()))
            out["fanout"] = {
                "jobs": len(self.sc.jobs),
                "completed": self.completed.get("fanout", 0),
                "p95_s": round(percentile(fan, 0.95), 4),
            }
        scalers = [m.scaler for m in self.masters.values()
                   if m.scaler is not None]
        if scalers:
            out["autoscale"] = {
                "scale_ups": sum(s.scale_ups for s in scalers),
                "scale_downs": sum(s.scale_downs for s in scalers),
                "flaps": sum(s.flaps for s in scalers),
            }
        if self.capture is not None:
            st = self.capture.stats()
            out["capture"] = {"dir": st["dir"],
                              "exported": st["exported"],
                              "dropped": st["dropped"],
                              "bytes_written": st["bytes_written"]}
        if self.multi:
            out["takeover"] = {
                "takeovers": self.takeovers,
                "successor": self.takeover_successor,
                "owned": sorted(([self.takeover_successor]
                                 if self.takeover_successor else [])
                                + self.absorbed),
                "ring_epoch": self.ring_epoch,
            }
        return out


def run_scenario(sc: Scenario) -> Dict[str, Any]:
    return FleetSim(sc).run()
