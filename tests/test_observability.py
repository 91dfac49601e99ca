"""End-to-end distributed job tracing (ISSUE 3): span model + traceparent
propagation, flight recorder, latency histograms with percentiles,
Prometheus exposition, JSON log correlation, metrics reset, and the
device-trace state-leak fix.

CPU-only, tier-1-eligible: no /root/reference dependency — the
two-participant acceptance test runs master AND worker as in-process
ServerStates over real loopback HTTP (aiohttp TestServer sockets), the
same topology test_server.py/test_dispatcher.py use.
"""

import asyncio
import itertools
import json
import logging
import os
import re
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.server.app import ServerState, build_app
from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils import logging as log_mod
from comfyui_distributed_tpu.utils import trace as tr


@pytest.fixture(autouse=True)
def tiny_family(monkeypatch):
    monkeypatch.setenv(registry.FAMILY_ENV, "tiny")
    yield


@pytest.fixture(autouse=True)
def tracing_on():
    """Tests assume the always-on default; restore whatever a prior test
    left behind."""
    was = tr.tracing_enabled()
    tr.set_tracing(True)
    yield
    tr.set_tracing(was)


def make_prompt(seed=1, steps=1, size=32, save=False):
    out_node = {"class_type": "SaveImage",
                "inputs": {"images": ["1", 0], "filename_prefix": "obs"}} \
        if save else {"class_type": "PreviewImage",
                      "inputs": {"images": ["1", 0]}}
    return {
        "7": {"class_type": "CheckpointLoaderSimple",
              "inputs": {"ckpt_name": "tiny.safetensors"}},
        "5": {"class_type": "CLIPTextEncode",
              "inputs": {"text": "cat", "clip": ["7", 1]}},
        "6": {"class_type": "CLIPTextEncode",
              "inputs": {"text": "", "clip": ["7", 1]}},
        "9": {"class_type": "EmptyLatentImage",
              "inputs": {"width": size, "height": size, "batch_size": 1}},
        "8": {"class_type": "KSampler",
              "inputs": {"model": ["7", 0], "positive": ["5", 0],
                         "negative": ["6", 0], "latent_image": ["9", 0],
                         "seed": seed, "steps": steps, "cfg": 1.0,
                         "sampler_name": "euler", "scheduler": "normal",
                         "denoise": 1.0}},
        "1": {"class_type": "VAEDecode",
              "inputs": {"samples": ["8", 0], "vae": ["7", 2]}},
        "3": out_node,
    }


def make_distributed_prompt(seed=5, steps=1, size=32):
    """txt2img with DistributedSeed -> KSampler and a DistributedCollector
    between VAEDecode and the preview — the fan-out shape the master's
    interceptor orchestrates."""
    p = make_prompt(seed=seed, steps=steps, size=size)
    p["4"] = {"class_type": "DistributedSeed", "inputs": {"seed": seed}}
    p["8"]["inputs"]["seed"] = ["4", 0]
    p["2"] = {"class_type": "DistributedCollector",
              "inputs": {"images": ["1", 0]}}
    p["3"]["inputs"]["images"] = ["2", 0]
    return p


async def wait_remote_history(client, pid, timeout_s=180.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        hist = await (await client.get("/history")).json()
        if pid in hist:
            return hist[pid]
        await asyncio.sleep(0.05)
    raise AssertionError(f"prompt {pid} never finished")


async def wait_trace(client, pid, timeout_s=5.0):
    """The job's committed trace record.  History marks success slightly
    before the finaliser commits the trace, so poll briefly instead of
    racing one read; on a timeout the last response decides."""
    deadline = time.monotonic() + timeout_s
    while True:
        r = await client.get(f"/distributed/trace/{pid}")
        rec = await r.json() if r.status == 200 else None
        if (rec and rec.get("spans")) or time.monotonic() >= deadline:
            return r.status, rec
        await asyncio.sleep(0.05)


def spans_by_name(rec):
    out = {}
    for s in rec["spans"]:
        out.setdefault(s["name"], []).append(s)
    return out


class TestTraceparent:
    def test_roundtrip(self):
        sp = tr.Span("x")
        header = tr.format_traceparent(sp)
        assert re.fullmatch(r"00-[0-9a-f]{32}-[0-9a-f]{16}-01", header)
        assert tr.parse_traceparent(header) == (sp.trace_id, sp.span_id)

    def test_malformed_headers_rejected(self):
        for bad in (None, "", "garbage", "00-zz-yy-01",
                    "00-" + "0" * 32 + "-" + "1" * 16 + "-01",   # zero trace
                    "00-" + "a" * 32 + "-" + "0" * 16 + "-01",   # zero span
                    "00-" + "a" * 31 + "-" + "b" * 16 + "-01"):  # short
            assert tr.parse_traceparent(bad) is None, bad

    def test_headers_empty_without_active_span(self):
        assert tr.traceparent_headers() == {}

    def test_headers_follow_current_span(self):
        root = tr.start_span("job")
        with tr.use_span(root):
            with tr.span("dispatch") as sp:
                h = tr.traceparent_headers()
                assert h[C.TRACEPARENT_HEADER] == tr.format_traceparent(sp)
        root.end()


class TestSpanContext:
    def test_child_parentage_and_status(self):
        root = tr.start_span("job", attrs={"prompt_id": "p_x"})
        with tr.use_span(root):
            with tr.span("execute") as e:
                assert e.trace_id == root.trace_id
                assert e.parent_id == root.span_id
                assert tr.current_trace_ids()["prompt_id"] == "p_x"
            with pytest.raises(ValueError):
                with tr.span("boom"):
                    raise ValueError("x")
        root.end()
        exported = tr.GLOBAL_TRACES.export(root.trace_id)
        boom = [s for s in exported if s["name"] == "boom"]
        assert boom and boom[0]["status"] == "error"

    def test_capture_reattach_across_thread(self):
        """The HostIOPool handoff contract: a span begun on one thread
        parents work submitted to another thread."""
        root = tr.start_span("job")
        seen = {}

        def work(captured):
            with tr.use_span(captured):
                with tr.span("deferred") as d:
                    seen["parent"] = d.parent_id
                    seen["trace"] = d.trace_id

        with tr.use_span(root):
            t = threading.Thread(target=work,
                                 args=(tr.capture_span_context(),))
            t.start()
            t.join(5)
        root.end()
        assert seen == {"parent": root.span_id, "trace": root.trace_id}

    def test_disabled_tracing_is_noop(self):
        tr.set_tracing(False)
        assert tr.start_span("job") is None
        with tr.span("x") as sp:
            assert sp is None
        assert tr.traceparent_headers() == {}
        tr.set_tracing(True)

    def test_stage_records_histogram_without_span(self):
        """stage() outside any trace still feeds the aggregate timeline
        and never fabricates orphan spans."""
        before = tr.GLOBAL_STAGES.snapshot().get("obs_stage",
                                                 {}).get("count", 0)
        with tr.stage("obs_stage"):
            pass
        snap = tr.GLOBAL_STAGES.snapshot()["obs_stage"]
        assert snap["count"] == before + 1


class TestHistogram:
    def test_bucket_and_percentile_math(self):
        h = tr.LatencyHistogram(bounds=(0.01, 0.1, 1.0))
        for v in (0.005, 0.005, 0.05, 0.5, 5.0):
            h.record(v)
        cum = h.cumulative()
        assert cum == [(0.01, 2), (0.1, 3), (1.0, 4), (float("inf"), 5)]
        snap = h.snapshot()
        assert snap["count"] == 5
        assert snap["max_s"] == 5.0
        assert abs(snap["total_s"] - 5.56) < 1e-9
        # p50: rank 2.5 falls in the (0.01, 0.1] bucket
        assert 0.01 <= snap["p50_s"] <= 0.1
        # p99: rank ~4.95 falls in the overflow bucket, capped toward max
        assert 1.0 <= snap["p99_s"] <= 5.0
        assert h.percentile(0.0) == 0.0 or h.percentile(0.0) <= 0.01

    def test_empty_histogram(self):
        h = tr.LatencyHistogram()
        snap = h.snapshot()
        assert snap["count"] == 0 and snap["p99_s"] == 0.0

    def test_phase_stats_keeps_legacy_keys_and_adds_percentiles(self):
        ps = tr.PhaseStats()
        for v in (0.01, 0.02, 0.03):
            ps.record("x", v)
        snap = ps.snapshot()["x"]
        # legacy readers (the metrics tests) rely on these
        assert snap["count"] == 3
        assert abs(snap["total_s"] - 0.06) < 1e-9
        assert abs(snap["max_s"] - 0.03) < 1e-9
        for k in ("mean_s", "p50_s", "p95_s", "p99_s"):
            assert k in snap
        ps.reset()
        assert ps.snapshot() == {}


class TestFlightRecorder:
    def _commit_one(self, rec, pid):
        sp = tr.Span(f"job_{pid}")
        rec.add(sp.trace_id, sp.to_dict(provisional=True))
        rec.commit(pid, sp.trace_id, status="ok",
                   root_span_id=sp.span_id, duration_s=0.1)
        return sp

    def test_ring_eviction(self):
        rec = tr.FlightRecorder(max_traces=3)
        spans = [self._commit_one(rec, f"p{i}") for i in range(5)]
        assert rec.size() == 3
        assert rec.get("p0") is None and rec.get("p1") is None
        assert rec.get("p4") is not None
        # evicted trace ids are unmapped: late spans for them are dropped
        rec.add(spans[0].trace_id, tr.Span("late").to_dict())
        assert rec.get("p0") is None
        index = rec.index()
        assert [e["prompt_id"] for e in index] == ["p4", "p3", "p2"]

    def test_ingest_dedupes_and_replaces_provisional(self):
        rec = tr.FlightRecorder(max_traces=4)
        sp = tr.Span("execute")
        prov = sp.to_dict(provisional=True)
        rec.ingest([prov])
        final = dict(prov)
        final.pop("provisional", None)
        final["duration_s"] = 9.9
        rec.ingest([final, {"not": "a span"}, None])
        spans = rec.export(sp.trace_id)
        assert len(spans) == 1
        assert spans[0]["duration_s"] == 9.9
        assert "provisional" not in spans[0]

    def test_late_arrival_after_commit_lands_in_ring(self):
        rec = tr.FlightRecorder(max_traces=4)
        sp = self._commit_one(rec, "pj")
        straggler = tr.Span("receive_tile", trace_id=sp.trace_id,
                            parent_id=sp.span_id)
        rec.add(sp.trace_id, straggler.to_dict())
        got = rec.get("pj")
        assert {"job_pj", "receive_tile"} <= \
            {s["name"] for s in got["spans"]}

    def test_span_cap_drops_beyond_limit(self):
        rec = tr.FlightRecorder(max_traces=2, max_spans=3)
        tid = tr.new_trace_id()
        for i in range(6):
            rec.add(tid, tr.Span(f"s{i}", trace_id=tid).to_dict())
        assert len(rec.export(tid)) == 3
        assert rec.dropped_spans == 3

    def test_build_span_tree_orphans_surface_as_roots(self):
        a = tr.Span("root")
        b = tr.Span("child", parent=a)
        orphan = tr.Span("remote", trace_id=a.trace_id,
                         parent_id="feedfacefeedface")
        tree = tr.build_span_tree([s.to_dict(provisional=True)
                                   for s in (a, b, orphan)])
        names = sorted(t["name"] for t in tree)
        assert names == ["remote", "root"]
        root = [t for t in tree if t["name"] == "root"][0]
        assert [c["name"] for c in root["children"]] == ["child"]


def run_with_client(fn, tmp_path, **state_kw):
    async def go():
        state = ServerState(
            config_path=str(tmp_path / "cfg.json"),
            input_dir=str(tmp_path / "input"),
            output_dir=str(tmp_path / "output"),
            **state_kw)
        app = build_app(state)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            return await fn(client, state)
        finally:
            await client.close()
    return asyncio.run(go())


# --- Prometheus text format validation (no prometheus_client in the
# container — assert the grammar and histogram invariants by hand) -----------

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\")*\})?"
    r" [0-9eE+.\-]+(?: [0-9]+)?$")

# OpenMetrics exemplar suffix (ISSUE 18): `# {label="v",...} value [ts]`
_EXEMPLAR_RE = re.compile(
    r"^\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\")*\}"
    r" [0-9eE+.\-]+(?: [0-9eE+.\-]+)?$")


def validate_prometheus(text):
    """Grammar + histogram-invariant check; returns {family: type}."""
    types = {}
    samples = []
    for line in text.rstrip("\n").split("\n"):
        if line.startswith("# TYPE "):
            _, _, family, typ = line.split(" ", 3)
            types[family] = typ
        elif line.startswith("# HELP ") or not line.strip():
            continue
        else:
            if " # " in line:
                # exemplar-carrying sample: validate the suffix, then
                # the base sample; exemplars only ride _bucket series
                line, exemplar = line.split(" # ", 1)
                assert _EXEMPLAR_RE.match(exemplar), \
                    f"bad exemplar: {exemplar!r}"
                name = re.split(r"[{ ]", line, 1)[0]
                assert name.endswith("_bucket"), \
                    f"exemplar on non-bucket series: {line!r}"
            assert _SAMPLE_RE.match(line), f"bad sample line: {line!r}"
            samples.append(line)
    # every sample belongs to a declared family
    for line in samples:
        name = re.split(r"[{ ]", line, 1)[0]
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert name in types or base in types, f"undeclared: {name}"
    # histogram invariants: per labelset, cumulative buckets
    # non-decreasing and +Inf == _count
    hists = {f for f, t in types.items() if t == "histogram"}
    for fam in hists:
        buckets, counts = {}, {}
        for line in samples:
            if line.startswith(fam + "_bucket{"):
                labels = line[len(fam + "_bucket{"):line.index("}")]
                le = re.search(r'le="([^"]*)"', labels).group(1)
                key = re.sub(r'(,?)le="[^"]*"(,?)', ",", labels).strip(",")
                buckets.setdefault(key, []).append(
                    (le, float(line.rsplit(" ", 1)[1])))
            elif line.startswith(fam + "_count"):
                key = line[len(fam + "_count"):].lstrip("{")
                key = key[:key.index("}")] if "}" in key else ""
                counts[key] = float(line.rsplit(" ", 1)[1])
        for key, series in buckets.items():
            vals = [v for _, v in series]
            assert vals == sorted(vals), f"{fam}{{{key}}} not cumulative"
            les = [le for le, _ in series]
            assert les[-1] == "+Inf", f"{fam}{{{key}}} missing +Inf"
            assert key in counts and counts[key] == vals[-1], \
                f"{fam}{{{key}}} +Inf != _count"
    return types


class TestPrometheusExposition:
    def test_prom_endpoint_valid_and_complete(self, tmp_path):
        # make sure stage/phase/counter families have content
        with tr.stage("prom_probe_stage"):
            time.sleep(0.001)
        tr.GLOBAL_COUNTERS.bump("wire_tensor_msgs", 0)
        tr.GLOBAL_COUNTERS.bump("exec_runs", 0)

        async def body(client, state):
            r = await client.get("/distributed/metrics.prom")
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            text = await r.text()
            types = validate_prometheus(text)
            # stage histograms with _bucket/_sum/_count series
            assert types["dtpu_stage_seconds"] == "histogram"
            assert 'dtpu_stage_seconds_bucket{le="+Inf",' \
                   'stage="prom_probe_stage"}' in text
            assert 'dtpu_stage_seconds_sum{stage="prom_probe_stage"}' \
                in text
            assert 'dtpu_stage_seconds_count{stage="prom_probe_stage"}' \
                in text
            assert types["dtpu_node_seconds"] == "histogram"
            # existing wire/scheduler counters ride along
            assert 'dtpu_events_total{event="wire_tensor_msgs"}' in text
            assert 'dtpu_events_total{event="exec_runs"}' in text
            assert types["dtpu_jit_traces_total"] == "counter"
            assert types["dtpu_queue_remaining"] == "gauge"
            assert types["dtpu_prompts_executed_total"] == "counter"
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_label_escaping(self):
        tr.GLOBAL_STAGES.record('we"ird\\name\n', 0.001)
        try:
            text = tr.prometheus_text()
            validate_prometheus(text)
            assert r'stage="we\"ird\\name\n"' in text
        finally:
            tr.GLOBAL_STAGES.reset()


class TestMetricsReset:
    def test_reset_clears_aggregates(self, tmp_path):
        tr.GLOBAL_COUNTERS.bump("reset_probe", 3)
        tr.GLOBAL_STAGES.record("reset_probe_stage", 0.5)

        async def body(client, state):
            r = await client.post("/distributed/metrics/reset", json={})
            assert r.status == 200
            assert tr.GLOBAL_COUNTERS.get("reset_probe") == 0
            assert "reset_probe_stage" not in tr.GLOBAL_STAGES.snapshot()
        run_with_client(body, tmp_path, start_exec_thread=False)

    def test_reset_guard_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv(C.METRICS_RESET_ENV, "0")

        async def body(client, state):
            r = await client.post("/distributed/metrics/reset", json={})
            assert r.status == 403
        run_with_client(body, tmp_path, start_exec_thread=False)


class TestJsonLogs:
    def test_log_lines_carry_trace_correlation(self):
        logger = logging.getLogger("comfyui_distributed_tpu")
        captured = []

        class Capture(logging.Handler):
            def emit(self, record):
                captured.append(self.format(record))

        h = Capture()
        logger.addHandler(h)
        try:
            log_mod.set_json_logs(True)
            root = tr.start_span("job", attrs={"prompt_id": "p_json"})
            with tr.use_span(root):
                with tr.span("execute") as e:
                    log_mod.log("hello from inside a span")
            root.end()
            log_mod.log("outside any span")
        finally:
            logger.removeHandler(h)
            log_mod.set_json_logs(False)
        inside = json.loads(captured[0])
        assert inside["msg"].endswith("hello from inside a span")
        assert inside["trace_id"] == root.trace_id
        assert inside["span_id"] == e.span_id
        assert inside["prompt_id"] == "p_json"
        outside = json.loads(captured[1])
        assert "trace_id" not in outside
        assert outside["level"] == "info"


class TestDeviceTraceLeakFix:
    def test_failed_stop_clears_state(self, monkeypatch, tmp_path):
        import jax
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d: None)
        calls = {"n": 0}

        def boom():
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("profiler exploded")

        monkeypatch.setattr(jax.profiler, "stop_trace", boom)
        tr.start_device_trace(str(tmp_path / "t1"))
        with pytest.raises(RuntimeError, match="exploded"):
            tr.stop_device_trace()
        # the leak fix: state cleared despite the raise, so a new trace
        # can start instead of "trace already running" forever
        assert tr.trace_status()["running"] is False
        tr.start_device_trace(str(tmp_path / "t2"))
        assert tr.stop_device_trace() == str(tmp_path / "t2")


class TestServerTraceLifecycle:
    def test_single_prompt_trace_tree(self, tmp_path,
                                      assert_nothing_compiled):
        """Local job: /prompt -> flight recorder holds job/queue_wait/
        execute/per-node spans under ONE trace id with intact links; and
        telemetry never touches compiled code: with tracing off, then on
        beside a resource monitor sampling at 100x the production rate,
        warm requests lower and compile nothing."""
        from comfyui_distributed_tpu.utils import resource as res_mod

        async def served(client, seed):
            r = await client.post("/prompt", json={
                "prompt": make_prompt(seed=seed), "client_id": "t"})
            pid = (await r.json())["prompt_id"]
            hist = await wait_remote_history(client, pid)
            assert hist["status"] == "success", hist
            return pid

        async def body(client, state):
            r = await client.post("/prompt", json={
                "prompt": make_prompt(seed=3), "client_id": "t"})
            assert r.status == 200
            pid = (await r.json())["prompt_id"]
            hist = await wait_remote_history(client, pid)
            assert hist["status"] == "success", hist
            status, rec = await wait_trace(client, pid)
            assert status == 200
            assert rec["status"] == "ok"
            assert {s["trace_id"] for s in rec["spans"]} == \
                {rec["trace_id"]}
            by = spans_by_name(rec)
            for name in ("job", "queue_wait", "execute", "KSampler",
                         "VAEDecode"):
                assert name in by, (name, sorted(by))
            job = by["job"][0]
            assert by["execute"][0]["parent_id"] == job["span_id"]
            assert by["KSampler"][0]["parent_id"] == \
                by["execute"][0]["span_id"]
            # one root, children nested
            assert [t["name"] for t in rec["tree"]] == ["job"]
            # the index lists it newest-first
            idx = await (await client.get("/distributed/traces")).json()
            assert idx["traces"][0]["prompt_id"] == pid

            mark = tr.GLOBAL_RETRACES.mark()
            monitor = res_mod.ResourceMonitor(
                interval=0.05, ring=64,
                queue_depth_fn=state.queue_remaining)
            try:
                tr.set_tracing(False)
                untraced = await served(client, seed=4)
                tr.set_tracing(True)
                monitor.start()
                traced = await served(client, seed=5)
                assert (await wait_trace(client, traced))[0] == 200
                # committed after it, so the untraced job's is not coming
                r = await client.get(f"/distributed/trace/{untraced}")
                assert r.status == 404
            finally:
                monitor.stop(join=True)
            assert monitor.snapshot()["n_samples"] >= 1
            assert_nothing_compiled(tr.GLOBAL_RETRACES.since(mark))
        run_with_client(body, tmp_path, start_exec_thread=True)

    def test_slow_job_log_line(self, tmp_path, monkeypatch):
        monkeypatch.setenv(C.SLOW_JOB_ENV, "0.0001")
        logger = logging.getLogger("comfyui_distributed_tpu")
        lines = []

        class Capture(logging.Handler):
            def emit(self, record):
                lines.append(record.getMessage())

        h = Capture()
        logger.addHandler(h)
        try:
            async def body(client, state):
                r = await client.post("/prompt", json={
                    "prompt": make_prompt(seed=4), "client_id": "t"})
                pid = (await r.json())["prompt_id"]
                hist = await wait_remote_history(client, pid)
                assert hist["status"] == "success", hist
                slow = [ln for ln in lines if "SLOW job" in ln
                        and pid in ln]
                assert slow, lines[-5:]
                # the breakdown names at least the execute stage
                assert "execute=" in slow[0]
            run_with_client(body, tmp_path, start_exec_thread=True)
        finally:
            logger.removeHandler(h)

    def test_rejected_prompt_leaves_error_trace(self, tmp_path,
                                                monkeypatch):
        monkeypatch.setenv(C.MAX_QUEUE_ENV, "1")

        async def body(client, state):
            state._exec_gate.clear()
            try:
                ok_pid = state.enqueue_prompt(make_prompt(1), "c")
                with pytest.raises(Exception):
                    state.enqueue_prompt(make_prompt(2), "c")
            finally:
                state._exec_gate.set()
            # the rejected prompt committed an error trace (postmortem)
            idx = await (await client.get("/distributed/traces")).json()
            errs = [t for t in idx["traces"] if t["status"] == "error"
                    and t["prompt_id"] != ok_pid]
            assert errs
        run_with_client(body, tmp_path, start_exec_thread=False)


class TestDistributedTraceAcceptance:
    def test_two_participant_fanout_one_trace_tree(self, tmp_path):
        """THE acceptance criterion: master + one worker over CPU
        loopback HTTP; GET /distributed/trace/<prompt_id> on the master
        returns ONE tree where master dispatch, worker execute and
        collect spans share a trace_id with parent/child links intact
        across the HTTP hop (traceparent out, spans shipped back on the
        final job_complete POST)."""
        async def go():
            wdir = tmp_path / "worker"
            mdir = tmp_path / "master"
            for d in (wdir, mdir):
                os.makedirs(d / "in"), os.makedirs(d / "out")
            worker_state = ServerState(
                config_path=str(wdir / "cfg.json"),
                input_dir=str(wdir / "in"), output_dir=str(wdir / "out"),
                is_worker=True, start_exec_thread=True)
            # prompt ids are p_<ms>_<a counter of the state's own>: two
            # states in ONE process can mint the same id in the same
            # millisecond, and the process-wide flight recorder then seals
            # the later job's spans over the earlier one's record (seen
            # under load as a tree of 0 spans).  Separate processes, as
            # deployed, share no recorder; here the counters are kept apart.
            worker_state._id_counter = itertools.count(1000)
            wclient = TestClient(TestServer(build_app(worker_state)))
            await wclient.start_server()
            wport = wclient.server.port
            # master config: one enabled loopback worker
            with open(mdir / "cfg.json", "w") as f:
                json.dump({"workers": [{"id": "w0", "host": "127.0.0.1",
                                        "port": wport, "enabled": True}],
                           "master": {"host": "127.0.0.1"},
                           "settings": {}}, f)
            master_state = ServerState(
                config_path=str(mdir / "cfg.json"),
                input_dir=str(mdir / "in"), output_dir=str(mdir / "out"),
                is_worker=False, start_exec_thread=True)
            mclient = TestClient(TestServer(build_app(master_state)))
            await mclient.start_server()
            master_state.port = mclient.server.port
            try:
                r = await mclient.post("/prompt", json={
                    "prompt": make_distributed_prompt(seed=11),
                    "client_id": "acc"})
                assert r.status == 200, await r.text()
                body = await r.json()
                assert body["workers"] == ["w0"], body
                pid = body["prompt_id"]
                hist = await wait_remote_history(mclient, pid)
                assert hist["status"] == "success", hist
                status, rec = await wait_trace(mclient, pid)
                assert status == 200
                # ONE trace id across every span in the tree
                assert {s["trace_id"] for s in rec["spans"]} == \
                    {rec["trace_id"]}, rec["spans"]
                by = spans_by_name(rec)
                ids = {s["span_id"]: s for s in rec["spans"]}
                # master dispatch span for w0
                dispatch = [s for s in by.get("dispatch", [])
                            if (s.get("attrs") or {}).get("worker")
                            == "w0"]
                assert dispatch, sorted(by)
                # worker job span parents under THAT dispatch span
                wjobs = [s for s in by["job"]
                         if (s.get("attrs") or {}).get("role")
                         == "worker"]
                assert wjobs, by["job"]
                assert wjobs[0]["parent_id"] == dispatch[0]["span_id"]
                # worker execute span parents under the worker job span
                wexec = [s for s in by["execute"]
                         if s["parent_id"] == wjobs[0]["span_id"]]
                assert wexec, by["execute"]
                # master collect span, chained to the master root
                assert "collect" in by, sorted(by)
                node = by["collect"][0]
                while node.get("parent_id") in ids:
                    node = ids[node["parent_id"]]
                assert node["span_id"] == rec["root_span_id"]
                # the worker's upload and the master's receive both made
                # it into the same tree (cross-hop both directions)
                assert "receive_image" in by, sorted(by)
                assert "upload" in by, sorted(by)
            finally:
                await mclient.close()
                await wclient.close()
                worker_state.drain(5)
                master_state.drain(5)
        asyncio.run(go())
