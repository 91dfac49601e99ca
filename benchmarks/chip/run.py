#!/usr/bin/env python3
"""The chip benchmark's one command.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1> [--rehearse] [--out DIR]

Runs one cell of ``BENCHMARK.json``: starts ``cli serve`` as a child with
the cell's mesh, warms the cell's one shape, offers the cell's traffic
through ``POST /prompt`` for ``--seconds``, checks every image, and prints
as its LAST line one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``).  With
``--trace 0`` the metrics are the cell's end-to-end ones, with
``--trace 1`` its per-layer ones, read from a device trace of a slice in
the middle of the window.  Everything else worth keeping is on earlier
lines and in ``<out>/run.json``.

Driven by data: this file names no cell, configuration, mix or metric.
A cell is an entry of ``BENCHMARK.json``; a configuration is
``configs/<name>.json``; a mix is ``traffic/<name>.json``; a metric is
``end_to_end/<name>.py`` or ``layer_metrics/<name>.py`` with
``read(ctx)``.  Adding one is adding a file and an entry.

This process never imports JAX while the server child lives (a chip
belongs to one process).  There is no CPU fallback: no TPU, or fewer
chips than the cell names, ends the run non-zero with no result.
``--rehearse`` is the one way to run on the CPU (tiny family, small
images, ``"platform": "cpu"``, every device metric absent).
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse                     # noqa: E402
import copy                         # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402
import tempfile                     # noqa: E402
import threading                    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from lib import checks, xplane                      # noqa: E402
from lib.context import Context                     # noqa: E402
from lib.flops import request_shape                 # noqa: E402
from lib.load import Loader                         # noqa: E402
from lib.server import BenchFailure, Server, check  # noqa: E402
from lib.traffic import Traffic, rotation_length          # noqa: E402

FIRST_REQUEST_TIMEOUT_S = 1100.0    # a cold run compiles inside its warm-up
WARM_REQUEST_TIMEOUT_S = 300.0
MAX_WARMUPS = 8
MIN_SLICE_S = 3.0                   # the trace slice: this or two requests,
MAX_SLICE_CHIP_S = 8.0              # but no more than this over all chips:
                                    # the profiler takes ~0.12 ms to write out
                                    # each device event, ~60-110 k of them a
                                    # chip-second, and the run has 360 s
SLICE_STARTS_AT = 0.25              # of the window: the profiler then writes
                                    # its trace out while the window goes on


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def load_json(path: str):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_reader(kind_dir: str, name: str):
    path = os.path.join(HERE, kind_dir, f"{name}.py")
    check(os.path.isfile(path), f"metric {name!r} has no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind_dir}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(manifest: dict, group: str, cell: str) -> list[dict]:
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def server_env(chips: int, rehearse: bool) -> dict:
    """The child's environment: the cell's mesh and no other ``DTPU_*``
    variable, so the cell measures the defaults a user gets."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("DTPU_")}
    env["DTPU_MESH_SHAPE"] = f"data={chips}"
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["DTPU_DEFAULT_FAMILY"] = "tiny"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={chips}").strip()
    return env


def rehearsal_config(config: dict) -> dict:
    config = copy.deepcopy(config)
    for node, field, value in config["rehearsal"]["set"]:
        config["graph"][node]["inputs"][field] = value
    return config


def warm_up(loader: Loader, http, streak: int) -> list[dict]:
    """Requests of the cell's own shape, one at a time, until ``streak``
    in a row (one for each value the configuration rotates through)
    complete with ``retraces.compiles`` unchanged."""
    facts = []
    clean = 0
    compiles = http.get("/distributed/metrics")["retraces"]["compiles"]
    for n in range(MAX_WARMUPS):
        rec = loader.one(FIRST_REQUEST_TIMEOUT_S if n == 0
                         else WARM_REQUEST_TIMEOUT_S)
        entry = rec["entry"] or {}
        check(rec["done"] is not None and entry.get("status") == "success",
              f"warm-up request {n} ended {entry or 'in a timeout'}:\n"
              f"{loader.server.log_tail()}")
        after = http.get("/distributed/metrics")["retraces"]["compiles"]
        facts.append({"seconds": rec["done"] - rec["sent"],
                      "compiles": after - compiles})
        say(f"warm-up {n}: {facts[-1]['seconds']:.2f}s, "
            f"{after - compiles} compile event(s)")
        clean = clean + 1 if after == compiles else 0
        if clean == streak:
            return facts
        compiles = after
    raise BenchFailure(f"{MAX_WARMUPS} warm-up requests of one shape and "
                       f"never {streak} in a row without a compile: {facts}")


class Tracer(threading.Thread):
    """``/distributed/profile/start`` and ``/stop`` around a slice in the
    middle of the window, from a thread of its own so that the load
    generator never waits for the profiler."""

    def __init__(self, server: Server, trace_dir: str, start_at: float,
                 slice_s: float):
        super().__init__(daemon=True)
        self.server, self.trace_dir = server, trace_dir
        self.start_at, self.slice_s = start_at, slice_s
        self.facts: dict = {}
        self.error: str | None = None

    def run(self) -> None:
        http = self.server.http(timeout=600.0)
        try:
            time.sleep(max(self.start_at - time.monotonic(), 0.0))
            t0 = time.monotonic()
            status, doc = http.post("/distributed/profile/start",
                                    {"dir": self.trace_dir})
            if status != 200:
                self.error = f"profile/start answered {status}: {doc}"
                return
            t1 = time.monotonic()
            time.sleep(self.slice_s)
            t2 = time.monotonic()
            status, doc = http.post("/distributed/profile/stop")
            if status != 200:
                self.error = f"profile/stop answered {status}: {doc}"
            self.facts = {"start_call_s": t1 - t0, "slice_s": t2 - t1,
                          "stop_call_s": time.monotonic() - t2}
        except Exception as e:  # noqa: BLE001 - reported by the main thread
            self.error = f"{type(e).__name__}: {e}"
        finally:
            http.close()


def slice_seconds(request_s: float, chips: int, seconds: float) -> float:
    """How much of the window a ``--trace 1`` run traces: two requests
    and at least MIN_SLICE_S, so that a whole execution of every program
    falls inside; at most MAX_SLICE_CHIP_S over the cell's chips, and at
    most 0.6 of the window."""
    return min(max(MIN_SLICE_S, 2.0 * request_s), MAX_SLICE_CHIP_S / chips,
               0.6 * seconds)


def find_xplane(trace_dir: str) -> str:
    found = []
    for base, _, files in os.walk(trace_dir):
        found += [os.path.join(base, f) for f in files
                  if f.endswith(".xplane.pb")]
    check(len(found) == 1, f"{len(found)} .xplane.pb files under "
                           f"{trace_dir}, expected one")
    return found[0]


def resource_after(http, not_before: float) -> dict:
    """The server's resource sample, one taken after ``not_before``
    (its monitor samples every few seconds)."""
    deadline = time.monotonic() + 15.0
    while True:
        res = http.get("/distributed/resource")["resources"]
        if float(res.get("t") or 0.0) >= not_before \
                or time.monotonic() > deadline:
            return res
        time.sleep(0.5)


def run_cell(args, manifest: dict, cell: dict, config: dict, mix: dict,
             out_dir: str, scratch: str) -> dict:
    chips = int(cell["chips"])
    shape = request_shape(config["graph"])
    images_per_request = chips * shape["batch_size"]
    if args.rehearse:
        out_h = config["rehearsal"]["out_height"]
        out_w = config["rehearsal"]["out_width"]
    else:
        out_h, out_w = shape["height"], shape["width"]
    traffic = Traffic(mix, config["name"], args.seed)
    server = Server(ROOT, os.path.join(scratch, "server"),
                    os.path.join(scratch, "server.log"),
                    server_env(chips, args.rehearse))
    tracer = None
    rc = None
    try:
        status = server.wait_ready()
        device = {"platform": status["platform"],
                  "kind": status["devices"][0]["kind"],
                  "count": int(status["num_devices"])}
        say(f"server up: {device}, mesh {status['axes']}")
        want = "cpu" if args.rehearse else "tpu"
        check(device["platform"] == want,
              f"the server runs on {device['platform']!r}, not {want!r}")
        check(device["count"] == chips and
              int(status["axes"].get("data", 0)) == chips,
              f"the cell names {chips} chip(s); the server's mesh has "
              f"{device['count']} device(s), axes {status['axes']}")
        http = server.http()
        loader = Loader(server, config, traffic,
                        f"s{args.seed}t{args.trace}")
        warmups = warm_up(loader, http, rotation_length(config))
        metrics_setup = http.get("/distributed/metrics")
        status_code, doc = http.post("/distributed/metrics/reset", {})
        check(status_code == 200, f"metrics/reset answered {status_code}: "
                                  f"{doc}")
        compiles_before = metrics_setup["retraces"]["compiles"]
        if args.trace:
            tracer = Tracer(server, os.path.join(scratch, "trace"),
                            time.monotonic() + SLICE_STARTS_AT * args.seconds,
                            slice_seconds(warmups[-1]["seconds"], chips,
                                          args.seconds))
            tracer.start()
        setup_s = time.monotonic() - T_PROCESS_START
        wall_start = time.time()
        say(f"window: {args.seconds}s of {cell['traffic']}, set-up took "
            f"{setup_s:.1f}s")
        window = loader.window(float(args.seconds))
        if tracer is not None:
            tracer.join(timeout=600.0)
            check(not tracer.is_alive(), "the profiler never stopped")
            check(tracer.error is None, f"profiler: {tracer.error}")
        metrics_window = http.get("/distributed/metrics")
        resource = resource_after(http, wall_start + window["ended_s"])
        loader.close()
        http.close()
        server.require_alive()
        rc = server.shut_down()
    finally:
        server.kill()
        with open(os.path.join(out_dir, "server.log.tail"), "w",
                  encoding="utf-8") as f:
            f.write(server.log_tail(20000))
    records = loader.records
    compiles = metrics_window["retraces"]["compiles"] - compiles_before
    faults = checks.request_faults(records, images_per_request, out_h, out_w)
    png_faults, probe = checks.image_faults(
        os.path.join(server.cwd, "output"), records, images_per_request,
        out_h, out_w)
    faults += png_faults
    if compiles:
        faults.append(f"{compiles} compile event(s) inside the window")
    if rc != 0:
        faults.append(f"server child exited with code {rc} on SIGTERM")
    if server.log_has_traceback():
        faults.append("traceback in the server's log")
    if probe is not None:
        shutil.copy(probe.pop("path"), os.path.join(out_dir, "probe.png"))
        say(f"probe image sha256 {probe['sha256']}")

    peaks_table = load_json(os.path.join(HERE, "lib", "peaks.json"))
    peaks = peaks_table["by_device_kind"].get(device["kind"])
    check(args.rehearse or peaks is not None,
          f"device kind {device['kind']!r} is not in lib/peaks.json; add "
          f"its published peaks with the source")
    trace = None
    if tracer is not None:
        # the server has exited: reading the trace may import JAX now
        os.environ["JAX_PLATFORMS"] = "cpu"
        events = xplane.read_xplane(find_xplane(tracer.trace_dir))
        trace = xplane.reduce(events, config["programs"],
                              tracer.facts["slice_s"])
        trace["profiler_calls"] = tracer.facts
        with open(os.path.join(out_dir, "trace_reduced.json"), "w",
                  encoding="utf-8") as f:
            json.dump(trace, f, indent=1)
        if args.keep_events:
            xplane.dump_events(events, os.path.join(out_dir,
                                                    "events.json.gz"))
        if not trace["chips"]:
            check(args.rehearse, "the trace holds no /device:TPU plane")
            trace = None
        else:
            check(len(trace["chips"]) == chips,
                  f"the trace holds {len(trace['chips'])} device plane(s), "
                  f"the cell names {chips}")
            check(trace["busy_s"] > 0, "no operation ran on the device "
                                       "inside the trace slice")

    per_device = resource.get("per_device_bytes") or []
    if resource.get("source") == "memory_stats" and per_device:
        device["memory_peak_bytes"] = max(d[1] for d in per_device)
    else:
        check(args.rehearse, f"the resource probe reports "
                             f"{resource.get('source')!r}, not the device "
                             f"allocator's memory_stats")
        device["memory_peak_bytes"] = int(resource["device_peak_bytes"])
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]

    ctx = Context(cell=cell, config=config, mix=mix,
                  seconds=float(args.seconds),
                  images_per_request=images_per_request, setup_s=setup_s,
                  records=records, window=window,
                  metrics_setup=metrics_setup, metrics_window=metrics_window,
                  compiles_in_window=compiles, resource=resource,
                  device=device, peaks=peaks, trace=trace)
    counters = metrics_setup["pipeline"]["counters"]
    lat = ctx.latencies()
    summary = {
        "workload": cell["name"], "seed": args.seed, "trace": args.trace,
        "seconds": args.seconds, "rehearsal": args.rehearse,
        "faults": faults, "probe": probe, "window": window,
        "warmups": warmups, "requests_completed": len(lat),
        "completed_per_s_whole_window": len(
            [r for r in ctx.completed() if r["done"] <= args.seconds])
        / args.seconds,
        "latencies_s": sorted(lat),
        "setup": {
            "seconds": setup_s,
            "node_total_s": {k: v["total_s"] for k, v in
                             metrics_setup["nodes"].items()},
            "compile_cache_hits": counters.get("compile_cache_hits", 0),
            "compile_cache_writes": counters.get("compile_cache_writes", 0),
            "compile_events": compiles_before},
        "window_stages": metrics_window["pipeline"]["stages"],
        "window_nodes": metrics_window["nodes"],
        "window_counters": metrics_window["pipeline"]["counters"],
        "resource": {k: resource.get(k) for k in
                     ("source", "per_device_bytes", "device_bytes_limit",
                      "host_rss_bytes")},
        "records": [{k: r[k] for k in ("index", "client", "seed", "due",
                                       "sent", "done")}
                    | {"status": (r["entry"] or {}).get("status")}
                    for r in records]}
    def write_summary() -> None:
        with open(os.path.join(out_dir, "run.json"), "w",
                  encoding="utf-8") as f:
            json.dump(summary, f, indent=1)

    values = summary["all_metrics"] = {}
    try:
        for group, kind_dir in (("end_to_end", "end_to_end"),
                                ("per_layer", "layer_metrics")):
            for metric in cell_metrics(manifest, group, cell["name"]):
                value = load_reader(kind_dir, metric["name"])(ctx)
                if value is not None:
                    values[metric["name"]] = {"value": float(value),
                                              "unit": metric["unit"],
                                              "group": group}
    except BenchFailure as e:
        summary["error"] = str(e)
        write_summary()
        raise
    wanted = "per_layer" if args.trace else "end_to_end"
    failed = sum(1 for r in records if r["done"] is None
                 or (r["entry"] or {}).get("status") != "success")
    result = {
        "correct": not faults, "attempted": len(records), "failed": failed,
        "metrics": {k: {"value": v["value"], "unit": v["unit"]}
                    for k, v in values.items() if v["group"] == wanted},
        "device": device}
    if trace is not None:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    summary["result"] = result
    write_summary()
    for line in faults:
        say(f"FAULT: {line}")
    say(f"poller: gap p50 {window['poll_gap_p50_ms']} ms, max "
        f"{window['poll_gap_max_ms']} ms; generator late p50 "
        f"{window['generator_late_p50_ms']} ms, max "
        f"{window['generator_late_max_ms']} ms")
    say(f"completed {len(lat)} of {len(records)} attempted, "
        f"{summary['completed_per_s_whole_window']:.4f} requests/s over "
        f"the whole window; set-up nodes "
        f"{ {k: round(v, 1) for k, v in summary['setup']['node_total_s'].items() if v >= 1} }")
    say("all metrics: " + json.dumps(
        {k: v["value"] for k, v in values.items()}))
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny family, small images")
    ap.add_argument("--out", default=None, help="output directory "
                    "(default <checkout>/chiprun_out/chipbench/<run>)")
    ap.add_argument("--keep-events", action="store_true",
                    help="with --trace 1: also write every trace event "
                         "read to <out>/events.json.gz")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "comfyui_distributed_tpu")):
        print("chipbench: comfyui_distributed_tpu/ is not in this checkout; "
              "the benchmark drives the program it lives beside",
              file=sys.stderr)
        return 2
    if not args.rehearse and (os.environ.get("JAX_PLATFORMS") or ""
                              ).strip().lower() == "cpu":
        print("chipbench: JAX_PLATFORMS=cpu holds JAX to the CPU here; a "
              "cell runs on the chip and does not fall back (--rehearse is "
              "the CPU rehearsal)", file=sys.stderr)
        return 2
    scratch = None
    try:
        manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {c["name"]: c for c in manifest["workloads"]}
        check(args.workload in cells, f"no cell {args.workload!r} in "
                                      f"BENCHMARK.json; it has {sorted(cells)}")
        cell = cells[args.workload]
        entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
        config = load_json(os.path.join(ROOT, entry["file"]))
        if args.rehearse:
            config = rehearsal_config(config)
        mix = load_json(os.path.join(HERE, "traffic",
                                     f"{cell['traffic']}.json"))
        out_dir = os.path.abspath(args.out or os.path.join(
            ROOT, "chiprun_out", "chipbench",
            f"{cell['name']}-s{args.seed}-t{args.trace}"))
        os.makedirs(out_dir, exist_ok=True)
        # PNGs (up to ~200 MB a run) and the raw trace: under TMPDIR,
        # outside the checkout, removed when the run ends
        scratch = tempfile.mkdtemp(prefix="chipbench-")
        result = run_cell(args, manifest, cell, config, mix, out_dir,
                          scratch)
    except BenchFailure as e:
        print(f"chipbench: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        if scratch is not None:
            shutil.rmtree(scratch, ignore_errors=True)
    # the result: one JSON object, last, and nothing else on its line
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
