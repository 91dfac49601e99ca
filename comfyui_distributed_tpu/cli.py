"""Command-line interface.

The headless equivalent of the reference's browser UI panel (L6): serve the
control plane, run workflows, inspect the mesh, manage workers.

  python -m comfyui_distributed_tpu.cli serve  [--port 8288]
  python -m comfyui_distributed_tpu.cli worker --port 8289
  python -m comfyui_distributed_tpu.cli run workflow.json [--out dir]
  python -m comfyui_distributed_tpu.cli devices
  python -m comfyui_distributed_tpu.cli status [--url http://...]
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _maybe_init_multihost() -> None:
    """Join a jax.distributed cluster when DTPU_COORDINATOR is set (no-op
    otherwise).  Must run before anything probes devices: after init,
    jax.devices() is the GLOBAL pod view and collectives ride ICI/DCN."""
    from comfyui_distributed_tpu.parallel.mesh import initialize_multihost
    initialize_multihost()


def _start_backend() -> None:
    """What ``serve``, ``worker`` and ``run`` do before any JAX work:
    initialise the backend in THIS process — exiting non-zero when it is
    not a TPU and the operator did not ask for the CPU
    (``parallel/mesh.require_backend``) — and switch the persistent
    compile cache on before the first compilation."""
    from comfyui_distributed_tpu.parallel.mesh import require_backend
    from comfyui_distributed_tpu.runtime.manager import \
        enable_persistent_compile_cache
    require_backend()
    enable_persistent_compile_cache()


def cmd_serve(args) -> int:
    _maybe_init_multihost()
    _start_backend()
    if getattr(args, "standby", False):
        # hot-standby master: watch the primary's lease in the shared
        # DTPU_WAL_DIR, take over (replay + resume) on expiry
        from comfyui_distributed_tpu.utils import constants as C
        os.environ[C.STANDBY_ENV] = "1"
    from comfyui_distributed_tpu.server.app import ServerState, serve
    state = ServerState(config_path=args.config, is_worker=False,
                        models_dir=args.models_dir)
    from comfyui_distributed_tpu.runtime.manager import install_exit_hooks
    install_exit_hooks(state.manager)
    serve(host=args.host, port=args.port, state=state)
    return 0


def cmd_worker(args) -> int:
    _maybe_init_multihost()
    _start_backend()
    from comfyui_distributed_tpu.server.app import ServerState, serve
    state = ServerState(config_path=args.config, is_worker=True,
                        models_dir=args.models_dir)
    serve(host=args.host, port=args.port, state=state, auto_launch=False)
    return 0


def cmd_router(args) -> int:
    """Stateless admission router for the multi-master control plane
    (ISSUE 14): spreads /prompt by prompt-id hash over the consistent-
    hash ring (pulled from the masters, refreshed on failure) and
    serves the merged multi-shard read views `cli fleet`/`cli top`/
    `cli cluster` render.  Holds no queue, no WAL, no leases — run as
    many replicas as you like."""
    from aiohttp import web

    from comfyui_distributed_tpu.runtime.shard import build_router_app
    from comfyui_distributed_tpu.utils import constants as C
    masters = [u for u in (args.masters or os.environ.get(
        C.ROUTER_MASTERS_ENV, "")).split(",") if u.strip()]
    if not masters:
        print(f"no masters: pass --masters or set "
              f"{C.ROUTER_MASTERS_ENV}", file=sys.stderr)
        return 2
    app = build_router_app(masters)
    print(f"router listening on {args.host}:{args.port} over "
          f"{len(masters)} seed master(s)", file=sys.stderr)
    web.run_app(app, host=args.host, port=args.port, print=None)
    return 0


def cmd_run(args) -> int:
    if args.via:
        return _run_via_server(args)
    _maybe_init_multihost()
    _start_backend()
    from comfyui_distributed_tpu.ops.base import OpContext
    from comfyui_distributed_tpu.parallel.mesh import get_runtime
    from comfyui_distributed_tpu.workflow import WorkflowExecutor
    ctx = OpContext(runtime=get_runtime(), models_dir=args.models_dir,
                    input_dir=args.input_dir,
                    output_dir=args.out or os.path.join(os.getcwd(), "output"))
    res = WorkflowExecutor(ctx).execute(args.workflow)
    from comfyui_distributed_tpu.utils.image import tensor_to_pil
    os.makedirs(ctx.output_dir, exist_ok=True)
    import numpy as np
    for i, img in enumerate(res.images):
        tensor_to_pil(np.asarray(img)[None]).save(
            os.path.join(ctx.output_dir, f"run_{i:05d}.png"))
    print(json.dumps({
        "images": len(res.images),
        "total_s": round(res.total_s, 3),
        "timings": {k: round(v, 3) for k, v in res.timings.items()},
        "output_dir": ctx.output_dir,
    }))
    return 0


def _run_via_server(args) -> int:
    """Submit a workflow to a running master server and poll until done —
    the headless stand-in for the reference's browser queueing a prompt
    (its interceptor orchestrates server-side)."""
    import time
    import urllib.request

    with open(args.workflow, "r", encoding="utf-8") as f:
        doc = json.load(f)
    from comfyui_distributed_tpu.workflow.graph import parse_workflow
    prompt = parse_workflow(doc).to_api_format()

    def post(url, payload):
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())

    res = post(f"{args.via}/prompt", {"prompt": prompt,
                                      "client_id": "dtpu-cli"})
    pid = res["prompt_id"]
    if res.get("workers"):
        print(f"dispatched to workers: {res['workers']}", file=sys.stderr)
    deadline = time.time() + args.timeout
    while time.time() < deadline:
        with urllib.request.urlopen(f"{args.via}/history", timeout=10) as r:
            hist = json.loads(r.read())
        if pid in hist:
            print(json.dumps({"prompt_id": pid, **hist[pid]}))
            return 0 if hist[pid].get("status") == "success" else 1
        time.sleep(1.0)
    print(json.dumps({"prompt_id": pid, "status": "timeout"}))
    return 1


def cmd_devices(args) -> int:
    _maybe_init_multihost()  # topology must be the global pod view
    from comfyui_distributed_tpu.parallel.mesh import describe_devices
    print(json.dumps(describe_devices(), indent=2))
    return 0


def cmd_worker_ctl(args) -> int:
    """launch/stop/log for one worker — the reference panel's per-card
    buttons (``gpupanel.js:1519-2085``), driven locally or via a running
    master's HTTP endpoints with --url."""
    if args.url:
        import urllib.request
        if args.action == "log":
            with urllib.request.urlopen(
                    f"{args.url}/distributed/worker_log?id={args.id}",
                    timeout=10) as r:
                print(json.loads(r.read())["log"])
            return 0
        req = urllib.request.Request(
            f"{args.url}/distributed/{args.action}_worker",
            data=json.dumps({"id": args.id}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            print(r.read().decode())
        return 0

    from comfyui_distributed_tpu.runtime.manager import WorkerProcessManager
    from comfyui_distributed_tpu.utils import config as cfg_mod
    manager = WorkerProcessManager(config_path=args.config)
    if args.action == "log":
        print(manager.tail_log(args.id))
        return 0
    if args.action == "stop":
        ok = manager.stop_worker(args.id)
        print(json.dumps({"stopped": ok}))
        return 0 if ok else 1
    cfg = cfg_mod.load_config(args.config)
    worker = next((w for w in cfg.get("workers", [])
                   if str(w.get("id")) == str(args.id)), None)
    if worker is None:
        print(json.dumps({"error": f"worker {args.id} not in config"}))
        return 1
    # never tie the worker to this one-shot CLI process: the master-death
    # monitor would kill it the moment the CLI exits (stop_on_master_exit
    # only makes sense when a resident master launches the worker)
    entry = manager.launch_worker(worker, stop_on_master_exit=False)
    print(json.dumps(entry))
    return 0


def cmd_workers(args) -> int:
    """Headless worker panel: config + live health + managed-process state
    (what the reference's sidebar cards show, ``gpupanel.js:327-801``)."""
    from comfyui_distributed_tpu.runtime.health import HealthPoller
    from comfyui_distributed_tpu.runtime.manager import WorkerProcessManager
    from comfyui_distributed_tpu.utils import config as cfg_mod

    cfg = cfg_mod.load_config(args.config)
    manager = WorkerProcessManager(config_path=args.config)
    health = HealthPoller(config_path=args.config).poll_once()
    managed = manager.get_managed_workers()
    out = []
    for w in cfg.get("workers", []):
        wid = str(w.get("id"))
        out.append({
            "id": wid,
            "name": w.get("name", wid),
            "host": w.get("host") or "127.0.0.1",
            "port": w.get("port"),
            "enabled": bool(w.get("enabled")),
            "health": health.get(wid, {}).get("status", "unknown"),
            "queue_remaining": health.get(wid, {}).get("queue_remaining"),
            "managed": managed.get(wid),
        })
    print(json.dumps({"master": cfg.get("master", {}), "workers": out},
                     indent=2))
    return 0


def cmd_status(args) -> int:
    import urllib.request
    with urllib.request.urlopen(f"{args.url}/distributed/status",
                                timeout=5) as r:
        print(r.read().decode())
    return 0


def cmd_cluster(args) -> int:
    """Cluster control-plane reader: lease-based worker states, active
    ledger jobs with recovery counts, and the effective fault/hedge
    policy — the headless answer to "is the cluster healthy, and what
    happened to job X's lost tiles"."""
    import urllib.request
    with urllib.request.urlopen(f"{args.url}/distributed/cluster",
                                timeout=10) as r:
        data = json.loads(r.read())
    if args.json:
        print(json.dumps(data, indent=2))
        return 0
    print(f"policy={data['policy']}  lease={data['lease_s']}s  "
          f"suspect_after={data['suspect_probes']} probes  "
          f"hedge={'armed' if data['hedge']['armed'] else 'off'} "
          f"(>= {data['hedge']['min_progress_pct']:g}% done, "
          f"{data['hedge']['factor']:g}x latency)")
    workers = data.get("workers", {})
    if not workers:
        print("(no registered workers)")
    for wid, w in sorted(workers.items()):
        age = w.get("last_seen_age_s")
        lease = w.get("lease_remaining_s")
        print(f"  {wid:16s} {w['state']:8s} "
              f"last_seen={'never' if age is None else f'{age:.1f}s ago'}"
              f"  lease_remaining="
              f"{'-' if lease is None else f'{lease:.1f}s'}"
              f"  failed_probes={w['failed_probes']}"
              + (f"  {w.get('host')}:{w.get('port')}"
                 if w.get("port") else ""))
    ledger = data.get("ledger", {})
    for jid, job in sorted(ledger.get("active_jobs", {}).items()):
        print(f"  job {jid}: {job['done_units']}/{job['total_units']} "
              f"{job['kind']} units, {job['reassigned_units']} "
              f"reassigned, {job['hedged_units']} hedged")
    for job in ledger.get("completed_jobs", [])[-5:]:
        extra = ""
        if job["reassigned_units"] or job["hedged_units"]:
            extra = (f", {job['reassigned_units']} reassigned, "
                     f"{job['hedged_units']} hedged")
        if job["pending_units"]:
            extra += f", LOST {job['pending_units']}"
        print(f"  done {job['job_id']}: {job['done_units']}/"
              f"{job['total_units']} in {job['duration_s']}s{extra}")
    for t in data.get("transitions", [])[-8:]:
        print(f"  transition {t['worker_id']}: {t['from']} -> {t['to']}")
    return 0


def cmd_top(args) -> int:
    """Live fleet resource table (the headless `top` for the cluster):
    one row per participant from the master's federated
    ``GET /distributed/cluster/metrics`` — device memory in use / peak,
    host RSS, utilization estimate, queue depth, snapshot age."""
    import urllib.request
    with urllib.request.urlopen(
            f"{args.url}/distributed/cluster/metrics", timeout=10) as r:
        data = json.loads(r.read())
    if args.json:
        print(json.dumps(data, indent=2))
        return 0
    parts = data.get("participants", {})
    print(f"{'participant':16s} {'state':8s} {'mem_mb':>9s} "
          f"{'peak_mb':>9s} {'rss_mb':>9s} {'util':>5s} {'queue':>5s} "
          f"{'age_s':>6s}  source")
    def mb(v):
        return f"{v / 1e6:.1f}" if isinstance(v, (int, float)) else "-"
    for wid, p in sorted(parts.items(),
                         key=lambda kv: (kv[1].get("state") != "self",
                                         kv[0])):
        res = p.get("resources") or {}
        util = res.get("utilization")
        qd = res.get("queue_depth")
        age = p.get("age_s")
        print(f"{wid:16s} {p.get('state', '?'):8s} "
              f"{mb(res.get('device_bytes_in_use')):>9s} "
              f"{mb(res.get('device_peak_bytes')):>9s} "
              f"{mb(res.get('host_rss_bytes')):>9s} "
              f"{f'{util:.0%}' if isinstance(util, (int, float)) else '-':>5s} "
              f"{qd if isinstance(qd, int) else '-':>5} "
              f"{f'{age:.1f}' if isinstance(age, (int, float)) else '-':>6s}  "
              f"{res.get('source', '?')}"
              + ("  STALE" if p.get("stale") else ""))
    if not parts:
        print("(no participants reported)")
    return 0


def cmd_fleet(args) -> int:
    """Elastic-fleet reader: autoscaler state + recent decisions, the
    federated signal it scales on, per-tenant-class admission counters
    and the chaos spec — the headless answer to "is the fleet sized
    right, and who is being shed"."""
    import urllib.request
    with urllib.request.urlopen(f"{args.url}/distributed/fleet",
                                timeout=10) as r:
        data = json.loads(r.read())
    if args.json:
        print(json.dumps(data, indent=2))
        return 0
    a = data.get("autoscale", {})
    if a.get("enabled"):
        th = a.get("thresholds", {})
        b = a.get("bounds", {})
        sig = a.get("signal") or {}
        print(f"autoscaler {'RUNNING' if a.get('running') else 'stopped'}"
              f"  workers[{b.get('min_workers')},{b.get('max_workers')}]"
              f"  up>q/p {th.get('up_queue_per_participant')} or util "
              f"{th.get('up_utilization')}  down<q/p "
              f"{th.get('down_queue_per_participant')}"
              f"  window={a.get('window')} cooldown={a.get('cooldown_s')}s")
        util = sig.get("utilization")
        print(f"  signal: queue={sig.get('queue_depth')} "
              f"({sig.get('queue_per_participant')}/participant), "
              f"util={f'{util:.0%}' if isinstance(util, (int, float)) else '-'}, "
              f"{sig.get('live_workers')} live workers")
        print(f"  actions: {a.get('scale_ups', 0)} up, "
              f"{a.get('scale_downs', 0)} down, "
              f"{a.get('flaps', 0)} flaps"
              + (f", retiring {a['retiring']}" if a.get("retiring")
                 else ""))
        for d in a.get("decisions", [])[-8:]:
            print(f"    {d['action']:4s} {d.get('reason', '')}"
                  + (f"  worker={d['worker_id']}" if d.get("worker_id")
                     else ""))
    else:
        print("autoscaler off"
              + (" (DTPU_AUTOSCALE=1 set but not installed — worker "
                 "or embedded server?)" if a.get("armed_env") else
                 " (set DTPU_AUTOSCALE=1 on the master to arm)"))
    adm = data.get("admission", {})
    per = adm.get("per_class", {})
    queued = adm.get("queued_by_class", {})
    print(f"admission: default={adm.get('default_class')}  weights="
          f"{adm.get('weights')}  shed_bars={adm.get('shed_thresholds')}"
          f"  drain={adm.get('drain_rate_per_s')}/s")
    for cls in adm.get("classes", sorted(per)):
        v = per.get(cls, {})
        print(f"  {cls:6s} queued={queued.get(cls, 0):3d}  "
              f"admitted={v.get('admitted', 0):5d}  "
              f"completed={v.get('completed', 0):5d}  "
              f"shed={v.get('shed_overload', 0)} overload"
              f"/{v.get('shed_rate', 0)} rate")
    chaos = data.get("chaos", {})
    if chaos.get("active"):
        print(f"CHAOS ARMED: {chaos}")
    return 0


def cmd_reuse(args) -> int:
    """Cross-request reuse reader (ISSUE 13): per-tier cache
    hits/misses/evictions and byte residency against their budgets,
    the exact-hit replay count, tile skips, and the preview channel's
    client/abandonment gauges — the headless answer to "is the fleet
    actually reusing work"."""
    import urllib.request
    with urllib.request.urlopen(f"{args.url}/distributed/metrics",
                                timeout=10) as r:
        data = json.loads(r.read())
    reuse = data.get("reuse") or {}
    if args.json:
        print(json.dumps(reuse, indent=2))
        return 0
    if not reuse:
        print("(no reuse block reported — older server?)")
        return 1
    print(f"reuse plane: enabled={reuse.get('enabled')} "
          f"total={reuse.get('bytes_total', 0) / 1e6:.1f}MB "
          f"generation={reuse.get('generation', 0)}")
    print(f"{'tier':8s} {'entries':>7s} {'mb':>9s} {'budget_mb':>9s} "
          f"{'hits':>7s} {'misses':>7s} {'evict':>6s}")
    for tier in ("result", "embed", "tile"):
        t = reuse.get(tier) or {}
        print(f"{tier:8s} {t.get('entries', 0):>7d} "
              f"{t.get('bytes', 0) / 1e6:>9.1f} "
              f"{t.get('max_bytes', 0) / 1e6:>9.1f} "
              f"{t.get('hits', 0):>7d} {t.get('misses', 0):>7d} "
              f"{t.get('evictions', 0):>6d}")
    print(f"replays={data.get('prompts_replayed', 0)} "
          f"abandoned={data.get('prompts_abandoned', 0)}")
    pv = reuse.get("previews") or {}
    print(f"previews: enabled={pv.get('enabled')} "
          f"clients={pv.get('clients', 0)} "
          f"watched={pv.get('watched_prompts', 0)} "
          f"abandon_pending={pv.get('abandoned_pending', 0)}")
    return 0


def cmd_trace(args) -> int:
    """Flight-recorder reader: no id lists recent job traces; with an id,
    pretty-prints the job's span tree (indent = parent/child, one line
    per span with duration and status) — the headless way to answer
    "where did THIS job spend its time, across processes".  With
    --export-dir, reads durable capture files instead of a live server
    (post-mortem: the server may be gone); --perfetto emits
    Chrome/Perfetto trace-event JSON for chrome://tracing / ui.perfetto.dev.
    """
    import urllib.error
    import urllib.request
    from comfyui_distributed_tpu.utils import trace_export

    def emit(rec) -> int:
        if args.perfetto:
            doc = trace_export.to_perfetto(rec)
            if args.out:
                with open(args.out, "w", encoding="utf-8") as f:
                    json.dump(doc, f)
                print(f"wrote {len(doc['traceEvents'])} events to "
                      f"{args.out}", file=sys.stderr)
            else:
                print(json.dumps(doc))
            return 0
        n_spans = rec.get("n_spans", len(rec.get("spans", ())))
        print(f"trace {rec['trace_id']}  job {rec['prompt_id']}  "
              f"status={rec['status']}  {rec.get('duration_s')}s  "
              f"{n_spans} spans")

        def walk(node, depth):
            mark = "" if node.get("status") == "ok" else \
                f"  !{node.get('status')}"
            attrs = node.get("attrs") or {}
            extra = "".join(f"  {k}={v}" for k, v in attrs.items()
                            if k in ("worker", "node", "coalesced", "job",
                                     "mem_peak_mb", "mem_peak_delta_mb",
                                     "device_peak_mb", "rss_mb"))
            print(f"{'  ' * depth}{node['name']}  "
                  f"{node['duration_s'] * 1e3:.1f}ms{extra}{mark}")
            for child in node.get("children", []):
                walk(child, depth + 1)

        tree = rec.get("tree")
        if tree is None:
            tree = trace_export.load_forest(rec)
        for root in tree:
            walk(root, 0)
        return 0

    if args.export_dir:
        # offline path: the durable capture files, no server required
        if not args.prompt_id:
            n = 0
            for rec in trace_export.iter_records(args.export_dir):
                dur = rec.get("duration_s")
                print(f"{rec['prompt_id']}  {rec['status']:5s}  "
                      f"{dur if dur is not None else '?':>8}s  "
                      f"{len(rec.get('spans', ())):3d} spans  "
                      f"trace={rec['trace_id']}")
                n += 1
            if not n:
                print("(no captured traces in "
                      f"{args.export_dir})")
            return 0
        rec = trace_export.load_trace(args.export_dir,
                                      prompt_id=args.prompt_id)
        if rec is None:
            print(f"no captured trace for {args.prompt_id!r} in "
                  f"{args.export_dir}", file=sys.stderr)
            return 1
        return emit(rec)
    if not args.prompt_id:
        with urllib.request.urlopen(f"{args.url}/distributed/traces",
                                    timeout=10) as r:
            data = json.loads(r.read())
        for t in data.get("traces", []):
            dur = t.get("duration_s")
            print(f"{t['prompt_id']}  {t['status']:5s}  "
                  f"{dur if dur is not None else '?':>8}s  "
                  f"{t['n_spans']:3d} spans  trace={t['trace_id']}")
        if not data.get("traces"):
            print("(no completed job traces recorded)")
        return 0
    try:
        with urllib.request.urlopen(
                f"{args.url}/distributed/trace/{args.prompt_id}",
                timeout=10) as r:
            rec = json.loads(r.read())
    except urllib.error.HTTPError as e:
        # error bodies may be plain text (older servers, proxies) — never
        # let the JSON parse mask the real status
        try:
            msg = json.loads(e.read()).get("error", str(e))
        except (ValueError, AttributeError):
            msg = str(e)
        print(msg, file=sys.stderr)
        return 1
    return emit(rec)


def cmd_why(args) -> int:
    """Latency autopsy for ONE job (`cli why <pid>`): the critical-path
    blame decomposition — every instant of the end-to-end interval
    attributed to the deepest covering span's category (queue_wait /
    admission / dispatch / compute / d2h / encode / upload / blend /
    park), with the uncovered remainder reported honestly as an
    unattributed gap instead of silently inflating a category.  Reads
    the live flight recorder, or durable capture files with
    --export-dir (post-mortem)."""
    import urllib.error
    import urllib.request
    from comfyui_distributed_tpu.utils import trace_analysis
    from comfyui_distributed_tpu.utils import trace_export
    if args.export_dir:
        rec = trace_export.load_trace(args.export_dir,
                                      prompt_id=args.prompt_id)
        if rec is None:
            print(f"no captured trace for {args.prompt_id!r} in "
                  f"{args.export_dir}", file=sys.stderr)
            return 1
    else:
        try:
            with urllib.request.urlopen(
                    f"{args.url}/distributed/trace/{args.prompt_id}",
                    timeout=10) as r:
                rec = json.loads(r.read())
        except urllib.error.HTTPError as e:
            try:
                msg = json.loads(e.read()).get("error", str(e))
            except (ValueError, AttributeError):
                msg = str(e)
            print(msg, file=sys.stderr)
            return 1
    bd = trace_analysis.critical_path(rec)
    if args.json:
        print(json.dumps(bd, indent=2))
        return 0
    e2e = bd["e2e_s"]
    print(f"job {bd['prompt_id']}  trace {bd['trace_id']}  "
          f"e2e={e2e:.3f}s")
    if e2e <= 0:
        print("(empty or zero-length trace — nothing to blame)")
        return 0
    print(f"{'category':14s} {'seconds':>9s} {'share':>7s}")
    for cat, secs in sorted(bd["categories"].items(),
                            key=lambda kv: -kv[1]):
        print(f"{cat:14s} {secs:>9.3f} {secs / e2e:>6.1%}")
    print(f"{'(unattributed)':14s} {bd['unattributed_s']:>9.3f} "
          f"{bd['unattributed_pct'] / 100:>6.1%}")
    if bd.get("negative_edges"):
        print(f"! {bd['negative_edges']} negative parent->child edges "
              "(cross-process clock skew; is DTPU_SKEW_CORRECTION on?)")
    print("critical path:")
    for seg in bd["path"]:
        who = f"  @{seg['worker']}" if seg.get("worker") else ""
        print(f"  +{seg['start_s']:>8.3f}s {seg['dur_s']:>8.3f}s  "
              f"{seg['name']} [{seg['category']}]{who}")
    return 0


def _print_analysis_report(report) -> None:
    """Shared pretty-printer for `cli analyze` (live route and offline
    capture dirs produce the same report shape)."""
    print(f"traces analysed: {report.get('n_traces', 0)}  "
          f"mean unattributed "
          f"{report.get('unattributed_pct_mean', 0.0):.1f}%  "
          f"negative_edges={report.get('negative_edges', 0)}")
    for group_by, groups in sorted(
            (report.get("profiles") or {}).items()):
        print(f"by {group_by}:")
        for key, prof in sorted(groups.items()):
            cats = "  ".join(
                f"{c}={v['mean_s']:.3f}s({v['share_pct']:.0f}%)"
                for c, v in sorted(
                    prof.get("categories", {}).items(),
                    key=lambda kv: -kv[1]["mean_s"])
                if v["mean_s"] > 0)
            print(f"  {key}: n={prof['n']} "
                  f"p50={prof['e2e_p50_s']:.3f}s "
                  f"p95={prof['e2e_p95_s']:.3f}s  {cats}")
    sc = report.get("stragglers") or {}
    workers = sc.get("workers") or {}
    if workers:
        print(f"straggler scorecard (fleet compute p95 median "
              f"{sc.get('fleet_median_p95_s', 0.0):.3f}s, "
              f"threshold {sc.get('threshold_x')}x):")
        for w, row in sorted(workers.items()):
            flag = "  STRAGGLER" if row["straggler"] else ""
            print(f"  {w}: n={row['n_spans']} "
                  f"p95={row['compute_p95_s']:.3f}s "
                  f"{row['vs_fleet_median_x']:.2f}x{flag}")
    hedging = report.get("hedging_latency_ema_s") or {}
    if hedging:
        ema = "  ".join(f"{j}={v}" for j, v in sorted(hedging.items()))
        print(f"ledger hedging EMA (active jobs): {ema}")
    skews = report.get("skew") or {}
    if skews:
        offs = "  ".join(f"{w}={s['offset_s'] * 1e3:+.1f}ms"
                         for w, s in sorted(skews.items()))
        print(f"clock skew: {offs}")
    live = report.get("live") or {}
    if live.get("armed"):
        print(f"anomaly plane armed (baseline {live.get('baseline')}): "
              f"{live.get('anomalies_total', 0)} anomalies over "
              f"{live.get('traces_analyzed', 0)} traces")


def cmd_analyze(args) -> int:
    """Cross-trace analytics (`cli analyze`): blame profiles grouped by
    tenant / structural signature / worker plus the per-worker
    straggler scorecard, over the live ring (GET /distributed/analysis)
    or durable capture dirs (--export-dir).  --diff A B runs the
    anomaly-gated regression diff between two capture dirs (permutation
    significance test; exit 3 when a regression is flagged);
    --baseline-out writes the profile JSON that arms the live anomaly
    plane via DTPU_ANALYSIS_BASELINE."""
    import urllib.request
    from comfyui_distributed_tpu.utils import trace_analysis
    from comfyui_distributed_tpu.utils import trace_export

    def offline_breakdowns(dir_path):
        stats: dict = {}
        records = list(trace_export.iter_records(dir_path, stats=stats))
        bds = trace_analysis.collect_breakdowns(records)
        skipped = stats.get("torn_lines", 0) \
            + stats.get("unknown_schema", 0)
        if skipped or stats.get("io_errors"):
            print(f"loader: {dir_path}: {stats['records']} records, "
                  f"{stats['torn_lines']} torn lines, "
                  f"{stats['unknown_schema']} unknown-schema, "
                  f"{stats['io_errors']} io errors", file=sys.stderr)
        return bds

    if args.diff:
        dir_a, dir_b = args.diff
        diff = trace_analysis.diff_breakdowns(
            offline_breakdowns(dir_a), offline_breakdowns(dir_b),
            seed=args.seed)
        if args.json:
            print(json.dumps(diff, indent=2))
        else:
            print(f"diff {dir_a} -> {dir_b}  "
                  f"(n={diff['n_a']} vs {diff['n_b']}, "
                  f"{diff['n_resamples']} resamples)")
            print(f"{'category':14s} {'mean_a':>9s} {'mean_b':>9s} "
                  f"{'delta':>8s} {'p':>6s}")
            for cat, row in diff["categories"].items():
                mark = "  REGRESSED" if row["flagged"] else (
                    "  (significant)" if row["significant"] else "")
                # delta_pct is None when the category was absent (mean
                # 0) in arm A -- the relative change is unbounded
                dp = (f"{row['delta_pct']:>+7.1f}%"
                      if row["delta_pct"] is not None else f"{'new':>8s}")
                print(f"{cat:14s} {row['mean_a_s']:>9.3f} "
                      f"{row['mean_b_s']:>9.3f} "
                      f"{dp} "
                      f"{row['p_value']:>6.3f}{mark}")
            print("verdict: " + ("REGRESSED in "
                                 + ", ".join(diff["flagged"])
                                 if diff["regressed"] else "clean"))
        return 3 if diff["regressed"] else 0

    if args.export_dir:
        records = [bd["_rec"]
                   for bd in offline_breakdowns(args.export_dir)]
        report = trace_analysis.analyze_records(records)
    else:
        with urllib.request.urlopen(
                f"{args.url}/distributed/analysis", timeout=10) as r:
            report = json.loads(r.read())
    if args.baseline_out:
        profile = report.get("fleet_profile")
        if not profile or not profile.get("n"):
            print("no traces to build a baseline from", file=sys.stderr)
            return 1
        trace_analysis.save_baseline(profile, args.baseline_out)
        print(f"wrote baseline profile ({profile['n']} traces) to "
              f"{args.baseline_out}", file=sys.stderr)
    if args.json:
        print(json.dumps(report, indent=2))
        return 0
    _print_analysis_report(report)
    return 0


def cmd_slo(args) -> int:
    """SLO burn-rate reader: per-tenant-class objectives, fast/slow
    window burn rates and the remaining slow-window error budget — the
    headless answer to "are we burning the paid error budget right
    now, and how fast"."""
    import urllib.request
    with urllib.request.urlopen(f"{args.url}/distributed/slo",
                                timeout=10) as r:
        data = json.loads(r.read())
    if args.json:
        print(json.dumps(data, indent=2))
        return 0
    if not data.get("enabled"):
        print("slo engine off (set DTPU_SLO_SPEC, e.g. "
              "'paid:p95<2s,completion>0.999')")
        return 0
    print(f"slo windows: fast={data['fast_window_s']:g}s "
          f"slow={data['slow_window_s']:g}s")
    for cls, t in sorted(data.get("tenants", {}).items()):
        objs = ", ".join(o["raw"] for o in t["objectives"]) or "-"
        print(f"  {cls}: {objs}  "
              f"budget_remaining={t['budget_remaining']:.2%}")
        for wname in ("fast", "slow"):
            w = t["windows"][wname]
            flag = "  BURNING" if w["burn_rate"] > 1.0 else ""
            print(f"    {wname:4s} n={w['count']:4d} "
                  f"ok={w['ok_ratio']:.3f} p95={w['p95_s']:.3f}s "
                  f"burn={w['burn_rate']:.2f}{flag}")
    return 0


def cmd_flightdeck(args) -> int:
    """Continuous-batching flight deck: the per-step-boundary occupancy
    timeline (busy/free slots, parked, admits/retires/preemptions per
    boundary) plus the admit-to-first-step latency histogram — the
    headless answer to "what did the batcher do in the last N steps"."""
    import urllib.request
    with urllib.request.urlopen(f"{args.url}/distributed/metrics",
                                timeout=10) as r:
        data = json.loads(r.read())
    b = data.get("batching") or {}
    if args.json:
        print(json.dumps(b, indent=2))
        return 0
    if not b:
        print("(no batching block reported — continuous batching off?)")
        return 1
    print(f"flight deck: running={b.get('running')} "
          f"admits={b.get('admits', 0)} retires={b.get('retires', 0)} "
          f"preemptions={b.get('preemptions', 0)} "
          f"retraces={b.get('retraces', 0)} "
          f"parked={b.get('parked', 0)}")
    h = b.get("admit_to_first_step") or {}
    if h.get("count"):
        print(f"admit->first step: n={h['count']} "
              f"p50={h.get('p50_s', 0):.3f}s p95={h.get('p95_s', 0):.3f}s "
              f"max={h.get('max_s', 0):.3f}s")
    deck = b.get("deck") or []
    rows = deck[-args.last:] if args.last else deck
    if rows:
        print(f"{'seq':>6s} {'bucket':8s} {'occupancy':18s} "
              f"{'park':>4s} {'adm':>4s} {'ret':>4s} {'pre':>4s}")
    for r_ in rows:
        busy, free = r_["busy"], r_["free"]
        bar = "#" * busy + "." * free
        print(f"{r_['seq']:>6d} {r_['bucket']:8s} "
              f"{bar:18s} {r_['parked']:>4d} {r_['admits']:>4d} "
              f"{r_['retires']:>4d} {r_['preemptions']:>4d}")
    if not rows:
        print("(deck timeline empty — no step boundaries yet)")
    return 0


def cmd_wal(args) -> int:
    """Offline write-ahead-log inspector: segment listing with checksum
    validation, snapshot inventory, the lease holder + epoch, per-job
    and per-type record counts, and the replayed summary (what a
    recovering master would resume).  Exit 1 on mid-file corruption —
    a torn TAIL is the expected signature of a crash, not an error."""
    from comfyui_distributed_tpu.runtime import durable as durable_mod
    wal_dir = args.dir or durable_mod.wal_dir()
    if not wal_dir:
        print("no WAL dir: pass --dir or set DTPU_WAL_DIR",
              file=sys.stderr)
        return 2
    if not os.path.isdir(wal_dir):
        print(f"not a directory: {wal_dir}", file=sys.stderr)
        return 2
    report = durable_mod.verify(wal_dir)
    if args.json:
        print(json.dumps(report, indent=2))
        return 0 if report["ok"] else 1
    lease = report["lease"]
    print(f"wal {wal_dir}: "
          f"{'OK' if report['ok'] else 'CORRUPT'}  "
          f"lease={'held by ' + str(lease.get('owner')) if lease.get('held') else 'expired/free'}"
          f"  epoch={lease.get('epoch', 0)}")
    for seg in report["segments"]:
        print(f"  {seg['segment']:26s} {seg['bytes']:>9d} B  "
              f"{seg['records']:>5d} rec  {seg['checksum']}")
    if not report["segments"]:
        print("  (no segments)")
    for snap in report["snapshots"]:
        print(f"  {snap}  (snapshot)")
    bt = report["records_by_type"]
    if bt:
        print("  records: " + ", ".join(
            f"{k}={v}" for k, v in sorted(bt.items())))
    if args.job:
        jobs = {j: n for j, n in report["records_by_job"].items()
                if args.job in j}
    else:
        jobs = report["records_by_job"]
    for jid, n in sorted(jobs.items()):
        live = report["replay"]["active_jobs"].get(jid)
        state = (f"OPEN {live['done']}/{live['total']} {live['kind']}"
                 if live else "finished")
        print(f"  job {jid}: {n} record(s), {state}")
    rp = report["replay"]
    print(f"  replay: {rp['records_replayed']} record(s) past "
          f"{'snapshot' if rp.get('snapshot') else 'genesis'}, "
          f"{len(rp['pending_prompts'])} in-flight prompt(s), "
          f"{len(rp['active_jobs'])} open job(s), idem keys "
          f"{rp['idem_keys']}")
    if rp["torn"]:
        print(f"  torn tail(s): {rp['torn']} (expected after a crash; "
              f"the partial record is ignored)")
    return 0 if report["ok"] else 1


def cmd_lint(args) -> int:
    """Project-invariant static analysis (dtpu-lint): run the AST rule
    suite over the checkout and fail (exit 1) on any violation not in
    the checked-in baseline.  Pure stdlib — never initializes a backend
    (safe on a serving host mid-incident)."""
    from comfyui_distributed_tpu.analysis import engine
    root = args.root or engine.repo_root()
    rules = args.rule or None
    if args.write_baseline and rules:
        # a partial run writes a partial baseline, silently destroying
        # every other rule's audited grandfather entries
        print("--write-baseline requires a full run: drop --rule",
              file=sys.stderr)
        return 2
    if args.graph:
        # interprocedural introspection: the call graph + lock-order
        # edges the v2 rules share, as JSON (no lint verdict)
        from comfyui_distributed_tpu.analysis import callgraph
        project = engine.load_project(root)
        print(json.dumps(callgraph.get_callgraph(project).to_json(),
                         indent=1))
        return 0
    try:
        report = engine.run_lint(root=root, rules=rules)
    except ValueError as e:
        print(str(e), file=sys.stderr)
        return 2
    if args.write_baseline:
        path = engine.write_baseline(root, report.violations)
        print(f"baseline written: {path} "
              f"({len(report.violations)} finding(s)) — audit every "
              f"entry before committing")
        return 0
    if args.json:
        print(json.dumps({
            "new": [vars(v) for v in report.new],
            "total_findings": len(report.violations),
            "baselined": report.baseline_total,
            "rule_counts": report.rule_counts,
            "graph": report.graph_stats,
        }, indent=2))
        return 1 if report.new else 0
    shown = report.violations if args.all else report.new
    for v in shown:
        mark = "" if v in report.new else "  (baselined)"
        print(f"{v.format()}{mark}")
        if args.chain and v.chain:
            print("    witness chain:" + v.format_chain())
    if args.stats:
        by_rule_baselined = {}
        for k, n in engine.load_baseline(root).items():
            by_rule_baselined[k.split("|", 1)[0]] = \
                by_rule_baselined.get(k.split("|", 1)[0], 0) + n
        new_by_rule = {}
        for v in report.new:
            new_by_rule[v.rule] = new_by_rule.get(v.rule, 0) + 1
        print("\nper-rule stats (found / suppressed / baselined / new):")
        for name in sorted(set(report.rule_counts)
                           | set(by_rule_baselined)):
            c = report.rule_counts.get(name,
                                       {"found": 0, "suppressed": 0})
            print(f"  {name:28s} {c['found']:4d} "
                  f"{c['suppressed']:4d} "
                  f"{by_rule_baselined.get(name, 0):4d} "
                  f"{new_by_rule.get(name, 0):4d}")
        g = report.graph_stats or {}
        if g:
            tiers = g.get("resolved_by_tier", {})
            print(f"call graph: {g.get('functions', 0)} function(s), "
                  f"{g.get('call_sites', 0)} call site(s), "
                  f"{sum(tiers.values())} resolved "
                  f"({', '.join(f'{k}={v}' for k, v in tiers.items())}), "
                  f"{g.get('unresolved_calls', 0)} dynamic-dispatch "
                  f"no-summary, {g.get('lock_edges', 0)} lock-order "
                  f"edge(s)")
            print(f"fixpoint passes: "
                  f"block={g.get('block_fixpoint_passes', '-')} "
                  f"lock={g.get('lock_fixpoint_passes', '-')} "
                  f"span={g.get('span_fixpoint_passes', '-')}")
    if report.new:
        print(f"\ndtpu-lint: {len(report.new)} NEW violation(s) "
              f"({len(report.violations)} total, "
              f"{report.baseline_total} baselined).  Fix them, add a "
              f"reasoned `# dtpu-lint: ignore[rule] why`, or — for "
              f"audited-benign findings only — regenerate the baseline "
              f"with `cli lint --write-baseline`.")
        return 1
    print(f"dtpu-lint: clean ({len(report.violations)} baselined "
          f"finding(s), 0 new)")
    return 0


def _sim_brief(summary) -> None:
    """The human-readable tail of a sim run (the full dict is --json)."""
    print(f"scenario {summary['name']} seed={summary['seed']}: "
          f"{summary['events']} events over "
          f"{summary['virtual_duration_s']}s virtual "
          f"({'drained' if summary['drained'] else 'WEDGED'})")
    print(f"  admitted {summary['admitted_total']}  "
          f"completed {summary['completed_total']}  "
          f"shed {summary['shed_total']}  "
          f"completion {summary['completion_rate']}")
    for cls, row in (summary.get("per_class") or {}).items():
        print(f"  {cls:6s} admitted={row['admitted']:>6d} "
              f"shed={row['shed_rate'] + row['shed_overload']:>5d} "
              f"p50={row['p50_s']:>8.3f}s p95={row['p95_s']:>8.3f}s")
    au = summary.get("autoscale")
    if au:
        print(f"  autoscale ups={au['scale_ups']} "
              f"downs={au['scale_downs']} flaps={au['flaps']}")
    tk = summary.get("takeover")
    if tk:
        print(f"  takeover x{tk['takeovers']} -> {tk['successor']} "
              f"epoch={tk['ring_epoch']}")
    print(f"  log digest {summary['log_digest']}")


def cmd_sim(args) -> int:
    """Traffic twin (ISSUE 19): run the real policy code — admission,
    fair dequeue, leases, hedging, autoscaler, hash ring — against a
    virtual clock.  Deterministic: same (seed, scenario) is the same
    event log, byte for byte."""
    from comfyui_distributed_tpu.sim import fleet
    from comfyui_distributed_tpu.sim import replay as replay_mod
    from comfyui_distributed_tpu.sim import scenario as sc_mod
    from comfyui_distributed_tpu.sim import sweep as sweep_mod
    if args.mode == "sweep":
        with open(args.source, "r", encoding="utf-8") as f:
            spec = json.load(f)
        values = sweep_mod.parse_values(args.values)
        if not values:
            print("--values parsed to nothing", file=sys.stderr)
            return 2
        results = sweep_mod.run_sweep(spec, args.param, values)
        if args.json:
            print(json.dumps(results, indent=1))
        else:
            print(sweep_mod.format_table(results))
        return 0
    if args.mode == "replay":
        base = None
        if args.base:
            with open(args.base, "r", encoding="utf-8") as f:
                base = json.load(f)
        spec, stats = replay_mod.build_replay_spec(args.source,
                                                   base=base)
        if not spec["arrivals"]:
            print(f"no replayable records under {args.source} "
                  f"(skipped {stats['skipped_lines']} line(s), "
                  f"{stats['skipped_records']} record(s))",
                  file=sys.stderr)
            return 1
        summary = fleet.run_scenario(sc_mod.from_dict(spec))
        summary["replay"] = stats
        if args.json:
            print(json.dumps(summary, indent=1))
        else:
            print(f"replayed {stats['records']} capture record(s) "
                  f"({stats['skipped_lines']} torn/unknown line(s) "
                  f"skipped) over {stats['window_s']}s")
            _sim_brief(summary)
        return 0
    sc = sc_mod.load_scenario(args.source)
    if getattr(args, "capture_dir", None):
        # capture-schema export (ISSUE 20): the sim emits the same
        # segment files a real master's trace_export plane writes, so
        # the whole analytics stack runs on synthetic traffic
        sc.capture_dir = args.capture_dir
    summary = fleet.run_scenario(sc)
    if args.json:
        print(json.dumps(summary, indent=1))
    else:
        _sim_brief(summary)
        cap = summary.get("capture")
        if cap:
            print(f"  capture: {cap['exported']} trace(s) -> "
                  f"{cap['dir']}")
    return 0 if summary["drained"] else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="comfyui_distributed_tpu")
    sub = ap.add_subparsers(dest="cmd", required=True)

    def common(p):
        p.add_argument("--config", default=None)
        p.add_argument("--models-dir", default=os.environ.get("DTPU_MODELS"))

    p = sub.add_parser("serve", help="run the master control plane")
    common(p)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8288)
    p.add_argument("--standby", action="store_true",
                   help="hot-standby master: watch the primary's lease "
                        "in DTPU_WAL_DIR and take over on expiry "
                        "(replaying the shared WAL)")
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser("worker", help="run a worker server")
    common(p)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, required=True)
    p.set_defaults(fn=cmd_worker)

    p = sub.add_parser("run", help="execute a workflow JSON")
    common(p)
    p.add_argument("workflow")
    p.add_argument("--out", default=None)
    p.add_argument("--input-dir", default=None)
    p.add_argument("--via", default=None, metavar="URL",
                   help="submit to a running master server (it orchestrates "
                        "HTTP workers) instead of executing in-process")
    p.add_argument("--timeout", type=float, default=600.0)
    p.set_defaults(fn=cmd_run)

    p = sub.add_parser("devices", help="show device topology")
    p.set_defaults(fn=cmd_devices)

    p = sub.add_parser("workers", help="worker panel: config+health+managed")
    common(p)
    p.set_defaults(fn=cmd_workers)

    for action in ("launch", "stop", "log"):
        p = sub.add_parser(action, help=f"{action} a managed worker")
        common(p)
        p.add_argument("id")
        p.add_argument("--url", default=None,
                       help="drive a running master instead of acting locally")
        p.set_defaults(fn=cmd_worker_ctl, action=action)

    p = sub.add_parser("status", help="query a running server")
    p.add_argument("--url", default="http://127.0.0.1:8288")
    p.set_defaults(fn=cmd_status)

    def master_alias(p):
        # multi-master (ISSUE 14): `--master <url>` names one master OR
        # a router — a router URL renders the merged multi-shard view
        # from its federated endpoints
        p.add_argument("--master", dest="url", default=argparse.SUPPRESS,
                       metavar="URL",
                       help="master (or router) base URL; a router URL "
                            "renders the merged multi-shard view "
                            "(alias of --url)")

    p = sub.add_parser("cluster", help="worker lease states + work-ledger "
                                       "jobs from a running master")
    p.add_argument("--url", default="http://127.0.0.1:8288")
    master_alias(p)
    p.add_argument("--json", action="store_true",
                   help="raw JSON instead of the pretty table")
    p.set_defaults(fn=cmd_cluster)

    p = sub.add_parser("top", help="fleet resource table: device memory/"
                                   "utilization per participant from the "
                                   "master's federated metrics")
    p.add_argument("--url", default="http://127.0.0.1:8288")
    master_alias(p)
    p.add_argument("--json", action="store_true",
                   help="raw JSON instead of the table")
    p.set_defaults(fn=cmd_top)

    p = sub.add_parser("fleet", help="elastic-fleet status: autoscaler "
                                     "decisions + signal, per-tenant "
                                     "admission counters, chaos spec")
    p.add_argument("--url", default="http://127.0.0.1:8288")
    master_alias(p)
    p.add_argument("--json", action="store_true",
                   help="raw JSON instead of the pretty report")
    p.set_defaults(fn=cmd_fleet)

    p = sub.add_parser("router", help="stateless multi-master admission "
                                      "router: /prompt spread by "
                                      "prompt-id hash over the ring, "
                                      "merged multi-shard read views")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8290)
    p.add_argument("--masters", default=None,
                   help="comma-separated master URLs (default "
                        "$DTPU_ROUTER_MASTERS)")
    p.set_defaults(fn=cmd_router)

    p = sub.add_parser("reuse", help="cross-request reuse status: "
                                     "per-tier cache counters/residency, "
                                     "exact-hit replays, tile skips, "
                                     "preview clients")
    p.add_argument("--url", default="http://127.0.0.1:8288")
    p.add_argument("--json", action="store_true",
                   help="raw JSON instead of the table")
    p.set_defaults(fn=cmd_reuse)

    p = sub.add_parser("wal", help="dump/verify a write-ahead job log: "
                                   "segments, checksums, lease, per-job "
                                   "record counts, replay summary")
    p.add_argument("--dir", default=None,
                   help="WAL directory (default: $DTPU_WAL_DIR)")
    p.add_argument("--job", default=None,
                   help="filter the per-job listing to ids containing "
                        "this substring")
    p.add_argument("--json", action="store_true",
                   help="raw JSON report instead of the pretty listing")
    p.set_defaults(fn=cmd_wal)

    p = sub.add_parser("lint", help="project-invariant static analysis: "
                                    "async-blocking, lockset, device-"
                                    "spine and registry-drift rules; "
                                    "exit 1 on non-baselined findings")
    p.add_argument("--root", default=None,
                   help="checkout root to lint (default: this package's "
                        "own checkout)")
    p.add_argument("--rule", action="append", default=None,
                   metavar="RULE_ID",
                   help="run only this rule (repeatable)")
    p.add_argument("--all", action="store_true",
                   help="print baselined findings too, not just new ones")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report")
    p.add_argument("--write-baseline", action="store_true",
                   help="regenerate the grandfather baseline from the "
                        "current findings (audit first!)")
    p.add_argument("--stats", action="store_true",
                   help="per-rule finding/suppression/baseline counts "
                        "plus call-graph size and fixpoint passes")
    p.add_argument("--graph", action="store_true",
                   help="dump the interprocedural call graph and "
                        "lock-order edges as JSON (no lint verdict)")
    p.add_argument("--chain", action="store_true",
                   help="print each finding's witness chain "
                        "(file:line hops to the blocking leaf / "
                        "cycle edge)")
    p.set_defaults(fn=cmd_lint)

    p = sub.add_parser("trace", help="read a job's distributed trace "
                                     "from a server's flight recorder "
                                     "or durable capture files")
    p.add_argument("prompt_id", nargs="?", default=None,
                   help="prompt id to print (omit to list recent traces)")
    p.add_argument("--url", default="http://127.0.0.1:8288")
    p.add_argument("--export-dir", default=None, metavar="DIR",
                   help="read durable capture files from DIR instead of "
                        "a live server (post-mortem)")
    p.add_argument("--perfetto", action="store_true",
                   help="emit Chrome/Perfetto trace-event JSON instead "
                        "of the pretty tree (load in ui.perfetto.dev)")
    p.add_argument("--out", default=None, metavar="FILE",
                   help="write --perfetto JSON to FILE instead of stdout")
    p.set_defaults(fn=cmd_trace)

    p = sub.add_parser("why", help="latency autopsy for one job: "
                                   "critical-path blame per category + "
                                   "the unattributed gap")
    p.add_argument("prompt_id", help="prompt id to autopsy")
    p.add_argument("--url", default="http://127.0.0.1:8288")
    p.add_argument("--export-dir", default=None, metavar="DIR",
                   help="read durable capture files from DIR instead of "
                        "a live server (post-mortem)")
    p.add_argument("--json", action="store_true",
                   help="raw breakdown dict instead of the blame table")
    p.set_defaults(fn=cmd_why)

    p = sub.add_parser("analyze", help="cross-trace analytics: blame "
                                       "profiles by tenant/signature/"
                                       "worker, straggler scorecard, "
                                       "regression diffs")
    p.add_argument("--url", default="http://127.0.0.1:8288")
    p.add_argument("--export-dir", default=None, metavar="DIR",
                   help="analyse durable capture files from DIR instead "
                        "of the live flight-recorder ring")
    p.add_argument("--diff", nargs=2, default=None,
                   metavar=("DIR_A", "DIR_B"),
                   help="regression diff between two capture dirs "
                        "(baseline A vs candidate B); exit 3 when a "
                        "significant regression is flagged")
    p.add_argument("--baseline-out", default=None, metavar="FILE",
                   help="write the fleet blame profile as the baseline "
                        "JSON that arms DTPU_ANALYSIS_BASELINE")
    p.add_argument("--seed", type=int, default=0,
                   help="resampling seed for the --diff significance "
                        "test (deterministic)")
    p.add_argument("--json", action="store_true",
                   help="raw report dict instead of the tables")
    p.set_defaults(fn=cmd_analyze)

    p = sub.add_parser("slo", help="SLO burn rates: per-tenant objective "
                                   "status over fast/slow windows, "
                                   "remaining error budget")
    p.add_argument("--url", default="http://127.0.0.1:8288")
    p.add_argument("--json", action="store_true",
                   help="raw JSON instead of the pretty report")
    p.set_defaults(fn=cmd_slo)

    p = sub.add_parser("flightdeck", help="continuous-batching flight "
                                          "deck: step-boundary occupancy "
                                          "timeline + admit-to-first-"
                                          "step latency")
    p.add_argument("--url", default="http://127.0.0.1:8288")
    p.add_argument("--last", type=int, default=32, metavar="N",
                   help="show only the last N timeline rows (0 = all)")
    p.add_argument("--json", action="store_true",
                   help="raw JSON batching block instead of the table")
    p.set_defaults(fn=cmd_flightdeck)

    p = sub.add_parser("sim", help="traffic twin: deterministic fleet "
                                   "simulation running the real policy "
                                   "code on a virtual clock")
    simsub = p.add_subparsers(dest="mode", required=True)

    sp = simsub.add_parser("run", help="run one scenario JSON")
    sp.add_argument("source", metavar="SCENARIO",
                    help="scenario spec (see benchmarks/scenarios/)")
    sp.add_argument("--capture-dir", default=None, metavar="DIR",
                    help="emit completed sim jobs as capture-schema "
                         "segment files into DIR (feeds cli analyze / "
                         "why --export-dir)")
    sp.add_argument("--json", action="store_true",
                    help="full summary dict instead of the brief")
    sp.set_defaults(fn=cmd_sim, mode="run")

    sp = simsub.add_parser("sweep", help="vary one dotted knob across "
                                         "values, tabulate outcomes")
    sp.add_argument("source", metavar="SCENARIO")
    sp.add_argument("--param", required=True, metavar="DOTTED",
                    help="knob path, e.g. admission.shed.batch or "
                         "traffic.0.rate")
    sp.add_argument("--values", required=True, metavar="V1,V2,...",
                    help="comma-separated values (JSON tokens ok)")
    sp.add_argument("--json", action="store_true",
                    help="per-value summaries instead of the table")
    sp.set_defaults(fn=cmd_sim, mode="sweep")

    sp = simsub.add_parser("replay", help="replay a capture directory "
                                          "(utils/trace_export "
                                          "segments) as the arrival "
                                          "stream")
    sp.add_argument("source", metavar="CAPTURE_DIR",
                    help="directory of trace-export segment files")
    sp.add_argument("--base", default=None, metavar="SCENARIO",
                    help="scenario JSON supplying the fleet/policy "
                         "side (capture supplies arrivals)")
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_sim, mode="replay")

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
