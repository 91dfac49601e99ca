"""Every idle second between programs gets one owner (ISSUE 51): the host
timeline ``trace.py`` keeps beside the profiler's annotations (lanes by
thread role, intervals on ``perf_counter_ns``, clipped to the slice), the
clock marker that lays it on a device trace, the hand-overs between
threads and the collector's pauses by name, and ``trace_summary``'s
``idle_by_executor`` over made-up events.  Clocks are stamped: no test
asserts a wall-clock duration."""

import gc
import glob
import gzip
import json
import os
import threading

import pytest

from comfyui_distributed_tpu.utils import trace
from comfyui_distributed_tpu.utils import trace_summary as ts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "tests", "data")
K = 1000                # the made-up events are written in microseconds
PERF0 = 7_000_000_000   # where perf_counter_ns stood at the first marker
SYNC0 = 10 * K          # and where that marker lies on the trace's clock


class Clock:
    """``perf_counter_ns`` by hand."""

    def __init__(self, ns=PERF0):
        self.ns = ns

    def __call__(self):
        return self.ns

    def at(self, us):
        self.ns = PERF0 + us * K


@pytest.fixture
def clock(monkeypatch):
    clock = Clock()
    monkeypatch.setattr(trace, "_now_ns", clock)
    return clock


@pytest.fixture
def armed(monkeypatch, clock):
    """The ring as `start_device_trace` arms it, without a profiler."""
    trace._ring.clear()
    monkeypatch.setattr(trace, "_ring_armed", True)
    monkeypatch.setattr(trace, "_ring_added", 0)
    yield clock
    trace._ring.clear()


def on_lane(role, fn):
    """Run ``fn`` on a thread of its own that says it is ``role``."""
    def run():
        trace.thread_role(role)
        fn()
    t = threading.Thread(target=run, name=f"test-{role}")
    t.start()
    t.join(30)
    assert not t.is_alive()


def rows_of(timeline):
    """{(role, name): [(start_us, end_us, depth), ...]} of a slice."""
    out = {}
    for lane, name, start, end, depth in timeline["intervals"]:
        key = (timeline["lanes"][lane]["role"], timeline["names"][name])
        out.setdefault(key, []).append((start // K, end // K, depth))
    return out


# --- the timeline ------------------------------------------------------------

def test_a_span_cut_at_either_edge_of_the_slice_is_kept_clipped(armed):
    """``dispatch`` began before the slice and ends inside it, ``exec_idle``
    is still open when it stops: the profiler drops both annotations, the
    timeline has both, clipped; one wholly before the slice is not there."""
    taken = {}

    def executor():
        armed.at(-900)
        with trace.stage("before"):
            armed.at(-800)
        with trace.stage("dispatch", own=True):
            armed.at(40)
            with trace.device_wait():
                armed.at(60)
            armed.at(100)
        with trace.stage("exec_idle"):
            armed.at(250)
            with trace.span("node"):
                taken["slice"] = trace._timeline_slice(PERF0, PERF0 + 300 * K)
    on_lane(trace.EXECUTOR, executor)
    got = rows_of(taken["slice"])
    assert got[("executor", "dispatch")] == [(0, 100, 0)]
    assert got[("executor", "device_wait")] == [(40, 60, 1)]
    assert got[("executor", "exec_idle")] == [(100, 300, 0)]
    assert got[("executor", "node")] == [(250, 300, 1)]
    assert ("executor", "before") not in got
    assert taken["slice"]["start_ns"] == PERF0
    assert taken["slice"]["stop_ns"] - PERF0 == 300 * K
    assert taken["slice"]["dropped"] == 0


def test_measured_intervals_are_kept_off_the_stack(armed, monkeypatch):
    """record_stage / event_span arrive with wall-clock bounds after the
    fact: on the timeline's clock, at depth MEASURED."""
    monkeypatch.setattr(trace.time, "time", lambda: 1000.0)
    armed.at(500)
    trace.record_stage("queue_wait", 1000.0 - 300e-6, 1000.0 - 100e-6)
    trace.event_span("prepare_job", 1000.0 - 50e-6, 1000.0)
    got = rows_of(trace._timeline_slice(PERF0, PERF0 + 600 * K))
    role = trace._lane().role
    assert got[(role, "queue_wait")] == [(200, 400, trace.MEASURED)]
    assert got[(role, "prepare_job")] == [(450, 500, trace.MEASURED)]


def test_two_handlers_of_one_event_loop_interleave(armed):
    """Two ``http_prompt`` stages open on one thread and close in the
    order they opened, as two coroutines of one loop do: each keeps its
    own bounds and nothing is left open."""
    def loop():
        armed.at(10)
        first = trace.stage("http_prompt")
        first.__enter__()
        armed.at(20)
        second = trace.stage("http_prompt")
        second.__enter__()
        armed.at(30)
        first.__exit__(None, None, None)
        armed.at(50)
        second.__exit__(None, None, None)
        assert trace._lane().open == []
    on_lane(trace.HTTP, loop)
    got = rows_of(trace._timeline_slice(PERF0, PERF0 + 100 * K))
    assert sorted(got[("http", "http_prompt")]) == [(10, 30, 0), (20, 50, 1)]


def test_the_ring_is_bounded_and_says_what_it_lost(armed, monkeypatch):
    assert trace._ring.maxlen == trace.TIMELINE_RING
    for i in range(trace.TIMELINE_RING + 10):
        armed.at(i)
        trace._keep(None, ("tick", armed.ns, 0), armed.ns + 1)
    # (a thread an earlier test of this process left running may close an
    # interval at any time: more rows, more lost; so the ring is stopped
    # before it is looked at)
    monkeypatch.setattr(trace, "_ring_armed", False)
    assert len(trace._ring) == trace.TIMELINE_RING
    timeline = trace._timeline_slice(PERF0, armed.ns + K)
    assert timeline["dropped"] == trace._ring_added - trace.TIMELINE_RING
    assert 10 <= timeline["dropped"] < 1000
    ticks = [r for r in trace._ring if r[1][0] == "tick"]
    lost = trace.TIMELINE_RING + 10 - len(ticks)
    assert 0 < lost <= timeline["dropped"]
    assert ticks[0][1][1] == PERF0 + lost * K       # the oldest went out
    assert len(ticks) <= len(timeline["intervals"]) <= trace.TIMELINE_RING


def test_many_threads_close_intervals_at_once_and_none_is_lost(armed):
    """More threads than cores, a switch interval of 10 us: every closed
    interval is a row, counted once, and no lane is left with one open."""
    import sys
    threads, each, left_open = 16, 500, []

    def work():
        for i in range(each):
            with trace.stage("dispatch", own=True):
                with trace.span("node"):
                    armed.ns += 1
        left_open.append(len(trace._lane().open))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pool = [threading.Thread(target=work, name=f"stress-{i}")
                for i in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in pool)
    assert left_open == [0] * threads
    # (rows of threads an earlier test left running are rows too)
    mine = [r for r in trace._ring if r[1][0] in ("dispatch", "node")
            and r[0].thread.startswith("stress-")]
    assert len(mine) == threads * each * 2
    assert trace._ring_added == len(trace._ring) < trace.TIMELINE_RING
    assert trace._timeline_slice(0, 1 << 62)["dropped"] == 0


def test_with_no_trace_running_nothing_is_kept_and_no_file_written(
        tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    trace._ring.clear()
    assert trace.trace_status()["running"] is False
    with trace.stage("dispatch", own=True):
        with trace.span("node"):
            trace.record_stage("queue_wait", 1.0, 2.0)
    trace.woke("queue", trace.now_ns() - 10)
    assert len(trace._ring) == 0
    assert trace._lane().open == []
    with pytest.raises(RuntimeError):
        trace.stop_device_trace()
    assert glob.glob(str(tmp_path / "**" / "*.json"), recursive=True) == []


def test_a_device_trace_writes_the_timeline_beside_the_xplane(tmp_path):
    """The real profiler, on the CPU: both clock markers in the trace, the
    file beside the ``.xplane.pb``, a stage the slice's start cuts and one
    its end cuts both in it, the drift in the summary."""
    taken = {}

    def executor():
        with trace.stage("dispatch", own=True):
            trace.start_device_trace(str(tmp_path / "t"))
            with trace.stage("inside"):
                pass
        with trace.stage("exec_idle"):
            trace.stop_device_trace()
            taken["open"] = list(trace._lane().open)
    on_lane(trace.EXECUTOR, executor)
    assert [e[0] for e in taken["open"]] == ["exec_idle"]
    xplane = glob.glob(str(tmp_path / "t" / "**" / "*.xplane.pb"),
                       recursive=True)
    assert len(xplane) == 1
    beside = os.path.join(os.path.dirname(xplane[0]), trace.TIMELINE_FILE)
    with open(beside, encoding="utf-8") as f:
        timeline = json.load(f)
    span = timeline["stop_ns"] - timeline["start_ns"]
    got = {(timeline["lanes"][ln]["role"], timeline["names"][n]): (s, e)
           for ln, n, s, e, _ in timeline["intervals"]}
    assert got[("executor", "dispatch")][0] == 0
    assert got[("executor", "exec_idle")][1] == span
    assert 0 < got[("executor", "inside")][0] \
        <= got[("executor", "inside")][1] < span
    events = ts.read_events(xplane[0])
    marked = [perf for _, perf in events["clock_sync"]]
    assert marked == sorted(marked) and {
        timeline["start_ns"], timeline["stop_ns"]} <= set(marked)
    assert events["timeline"] == {**timeline,
                                  "bytes": os.path.getsize(beside)}
    # the marker is no span of the program's
    summary = trace.profile_summary()
    assert "clock_sync" not in summary["host_spans"]
    assert ("clock_drift_ns" in summary) != ("timeline_error" in summary)
    assert "idle_by_executor" not in summary        # no device plane here
    assert len(trace._ring) == 0 and trace._ring_armed is False


# --- the waits by name ---------------------------------------------------------

def test_wake_drain_reads_notify_to_return_with_two_threads(armed):
    """A pool thread settles the last image under the queue lock and
    notifies; the leader, waiting in ``lm_drain_wait``, records
    ``wake_drain`` from that stamp to its own return."""
    from comfyui_distributed_tpu.server import lm_handover

    class State:
        pass
    state = State()
    state._queue_lock = threading.Lock()
    state._drained = threading.Condition(state._queue_lock)
    state._owed = {"p1", "p2"}
    state._drained_ns = 0
    trace.GLOBAL_STAGES.reset()
    waiting = threading.Event()

    def settle(pid, at_us, returns_at_us):
        with state._queue_lock:
            state._owed.discard(pid)
            armed.at(at_us)
            state._drained_ns = trace.now_ns()
            # the leader cannot return before this lock is let go: where
            # the clock stands then is what it reads
            armed.at(returns_at_us)
            state._drained.notify_all()

    def pool():
        waiting.wait(30)
        settle("p1", 100, 110)  # not the last: the leader waits on
        settle("p2", 200, 230)

    notifier = threading.Thread(target=pool)
    handover = lm_handover.GenerateHandover.__new__(
        lm_handover.GenerateHandover)
    handover._state = state
    done = {}
    real_wait = state._drained.wait

    def wait():
        waiting.set()           # under the lock: the pool starts behind it
        real_wait()
    state._drained.wait = wait

    def leader():
        armed.at(50)
        notifier.start()
        done["waited"] = handover._drain_wait()
        notifier.join(30)
    on_lane(trace.EXECUTOR, leader)
    assert done["waited"] is True and state._owed == set()
    stages = trace.GLOBAL_STAGES.snapshot()
    assert stages["wake_drain"]["count"] == 1
    assert stages["wake_drain"]["total_s"] == pytest.approx(30e-6)
    got = rows_of(trace._timeline_slice(PERF0, PERF0 + 300 * K))
    assert got[("executor", "wake_drain")] == [(200, 230, 2)]
    assert got[("executor", "lm_drain_wait")] == [(50, 230, 0)]
    # nothing owed: no wait, and no wake
    assert handover._drain_wait() is False
    assert trace.GLOBAL_STAGES.snapshot()["wake_drain"]["count"] == 1


def test_a_stamp_from_before_the_wait_woke_nobody(armed):
    trace.GLOBAL_STAGES.reset()
    armed.at(100)
    trace.woke("queue", PERF0 + 40 * K, since_ns=PERF0 + 50 * K)
    trace.woke("queue", 0)
    assert "wake_queue" not in trace.GLOBAL_STAGES.snapshot()
    trace.woke("queue", PERF0 + 60 * K, since_ns=PERF0 + 50 * K)
    row = trace.GLOBAL_STAGES.snapshot()["wake_queue"]
    assert row["count"] == 1 and row["total_s"] == pytest.approx(40e-6)


def test_a_forced_collection_is_one_gc_pause_of_generation_two(armed):
    trace.install_gc_monitoring()
    trace.install_gc_monitoring()           # idempotent
    assert gc.callbacks.count(trace._on_gc) == 1
    was = gc.isenabled()
    gc.disable()                            # none but the forced one
    try:
        trace.reset_aggregate_metrics()
        armed.at(100)
        trace._on_gc("start", {"generation": 2})    # what gc.collect() does,
        armed.at(180)                               # with the clock moved
        trace._on_gc("stop", {"generation": 2, "collected": 0})
        gc.collect()
        snap = trace.pipeline_snapshot()
        # the aggregates' reset folds what ended before it into the window
        # that ends there
        trace._on_gc("start", {"generation": 0})
        trace._on_gc("stop", {"generation": 0})
        trace.reset_aggregate_metrics()
        after = trace.pipeline_snapshot()
    finally:
        if was:
            gc.enable()
    assert snap["stages"]["gc_pause"]["count"] == 2
    assert snap["stages"]["gc_pause"]["max_s"] == pytest.approx(80e-6)
    assert snap["counters"]["gc.collections"] == 2
    assert snap["counters"]["gc.collections_gen2"] == 2
    assert "gc_pause" not in after["stages"]
    assert "gc.collections" not in after["counters"]
    got = rows_of(trace._timeline_slice(PERF0, PERF0 + 300 * K))
    pauses = [v for (_, name), v in got.items() if name == trace.GC_PAUSE]
    assert (100, 180, 0) in [row for rows in pauses for row in rows]


# --- one owner an instant, by the executor -----------------------------------------

def device_events():
    """One chip: programs at 100-200 (core), 400-450 (core), 500-520
    (pad) us, each one operation long, so every idle second lies BETWEEN
    programs: 200-400, 450-500, and the slice's edges."""
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "names": ["fusion.1", "pad.0"],
         "paths": ["jit(core)/UNet/mid_res_0/in_norm/GroupNorm_0/x:",
                   "jit(pad)/pad:"],
         "name_idx": [0, 0, 1],
         "start_ns": [100 * K, 400 * K, 500 * K],
         "dur_ns": [100 * K, 50 * K, 20 * K]},
        {"name": "XLA Modules",
         "names": ["jit_core(12)", "jit_core(7)", "jit_pad(1)"],
         "name_idx": [0, 1, 2],
         "start_ns": [100 * K, 400 * K, 500 * K],
         "dur_ns": [100 * K, 50 * K, 20 * K]}]}]}


def timeline(intervals, lanes=("executor", "host_pool", "http"),
             start_us=0, stop_us=600):
    """A ``host_timeline.json``: ``intervals`` as (role, name, start_us,
    end_us, depth) on the slice's own microseconds."""
    names = sorted({row[1] for row in intervals})
    return {"start_ns": PERF0 + start_us * K, "stop_ns": PERF0 + stop_us * K,
            "dropped": 0,
            "lanes": [{"role": r, "thread": r} for r in lanes],
            "names": names,
            "intervals": [[lanes.index(r), names.index(n), (s - start_us) * K,
                           (e - start_us) * K, d]
                          for r, n, s, e, d in intervals]}


def with_timeline(intervals, drift_ns=0, **kw):
    ev = device_events()
    ev["timeline"] = timeline(intervals, **kw)
    ev["clock_sync"] = [
        [SYNC0, ev["timeline"]["start_ns"]],
        [SYNC0 + ev["timeline"]["stop_ns"] - ev["timeline"]["start_ns"]
         + drift_ns, ev["timeline"]["stop_ns"]]]
    return ev


def us(x):
    return pytest.approx(x * 1e-6, abs=1e-12)


def test_the_sync_marker_maps_a_timeline_onto_a_trace():
    """perf_counter_ns PERF0 is trace nanosecond SYNC0: an interval at
    90-190 us of the slice lies at 100-200 us of the trace."""
    ev = with_timeline([("executor", "dispatch", 90, 190, 0),
                        ("host_pool", "encode", 0, 50, 0),
                        ("http", "gc_pause", 20, 30, 0),
                        ("executor", "queue_wait", 0, 90, trace.MEASURED)],
                       drift_ns=250)
    on = ts.timeline_on_trace(ev)
    assert (on["t0"], on["t1"]) == (SYNC0, SYNC0 + 600 * K)
    assert on["clock_drift_ns"] == 250
    # the executor's stack alone, and every thread's pauses
    assert on["names"] == ["dispatch"]
    assert on["start"].tolist() == [100 * K] and on["end"].tolist() == [200 * K]
    assert on["gc_start"].tolist() == [30 * K]
    assert ts.timeline_on_trace(device_events()) is None
    ev["clock_sync"] = ev["clock_sync"][1:]       # the first marker is lost
    assert ts.timeline_on_trace(ev) is None


def test_clocks_that_drift_apart_are_an_error_not_a_number():
    ev = with_timeline([("executor", "dispatch", 0, 600, 0)],
                       drift_ns=ts.MAX_DRIFT_NS + 1)
    s = ts.summarize(ev)
    assert "clock_drift_ns" not in s and "idle_by_executor" not in s
    assert str(ts.MAX_DRIFT_NS + 1) in s["timeline_error"]
    assert s["idle"] == {"none": us(250)}         # what was there still is
    ev = with_timeline([("executor", "dispatch", 0, 600, 0)],
                       drift_ns=-ts.MAX_DRIFT_NS)
    assert ts.summarize(ev)["clock_drift_ns"] == -ts.MAX_DRIFT_NS


def test_the_rows_add_up_to_the_idle_between_programs_to_the_nanosecond():
    """Slice 10-610 us of the trace.  Idle between programs: 10-100 (the
    edge), 200-400, 450-500, 520-610 (the edge): 430 us.  The executor:
    dispatch 0-240 us of the slice (cut at its start) with a device_wait
    150-220 in it, exec_idle 300-380 with wake_queue 370-380 at its end,
    nothing 380-455, detokenize 455-600 (cut at its end)."""
    ev = with_timeline([
        ("executor", "dispatch", 0, 240, 0),
        ("executor", "device_wait", 150, 220, 1),
        ("executor", "exec_idle", 300, 380, 0),
        ("executor", "wake_queue", 370, 380, 1),
        ("executor", "detokenize", 455, 600, 0)])
    s = ts.summarize(ev)
    rows = s["idle_by_executor"]
    # trace = slice + 10: dispatch 10-250 owns 10-100 and 200-250 less the
    # wait's 200-230; exec_idle 310-390 less the wake's 380-390; detokenize
    # 465-610 owns 465-500 and 520-610; unowned 250-310, 390-400, 450-465
    assert rows == {"dispatch": us(90 + 20), "device_wait": us(30),
                    "exec_idle": us(70), "wake_queue": us(10),
                    "detokenize": us(35 + 90), "unowned": us(60 + 10 + 15)}
    assert round(sum(rows.values()) * 1e9) == 430 * K
    assert s["idle_between_s"] == us(430)
    assert s["idle_by_executor_class"] == {
        "host": us(110 + 125), "wake": us(10), "wait_request": us(70),
        "wait_device": us(30), "gc": 0.0, "unowned": us(85)}
    assert round(sum(s["idle_by_executor_class"].values()) * 1e9) == 430 * K
    # what was there is what it was: the chip's own window, no edges
    assert s["idle"] == {"none": us(250)}
    assert s["gaps_in_programs_s"] == 0.0
    assert s["clock_drift_ns"] == 0
    assert s["host_timeline"] == {"intervals": 5, "dropped": 0, "bytes": 0}


def test_the_executor_owns_a_gap_a_pool_thread_has_a_shorter_span_over():
    """200-400 us idle with the executor in ``dispatch`` and a host_pool
    thread in a shorter ``encode`` over the same gap: ``idle`` would give
    it to the shorter span; the owner is the thread that feeds the
    device."""
    ev = with_timeline([("executor", "dispatch", 100, 500, 0),
                        ("host_pool", "encode", 200, 380, 0),
                        ("http", "http_prompt", 210, 230, 0)])
    ev["planes"].append({"name": "/host:CPU", "lines": [
        {"name": "exec", "names": ["dtpu/dispatch"], "name_idx": [0],
         "start_ns": [110 * K], "dur_ns": [400 * K]},
        {"name": "pool", "names": ["dtpu/encode"], "name_idx": [0],
         "start_ns": [210 * K], "dur_ns": [180 * K]}]})
    s = ts.summarize(ev)
    assert s["idle"]["encode"] == us(180)           # innermost of ANY thread
    assert s["idle_by_executor"]["dispatch"] == us(200 + 50)
    assert "encode" not in s["idle_by_executor"]
    assert "http_prompt" not in s["idle_by_executor"]


def test_a_collector_pause_on_any_thread_wins_over_the_executors_interval():
    ev = with_timeline([("executor", "dispatch", 100, 500, 0),
                        ("executor", "KSampler", 190, 390, 1),
                        ("http", "gc_pause", 250, 330, 1)])
    s = ts.summarize(ev)
    rows = s["idle_by_executor"]
    # trace = slice + 10: the gap 200-400 is KSampler's but for 260-340
    assert rows["gc_pause"] == us(80)
    assert rows["KSampler"] == us(200 - 80)
    assert s["idle_by_executor_class"]["gc"] == us(80)
    # at any depth: dispatch is never the innermost where a node's span
    # lies in it, and is over every second of it all the same (the pause
    # too: it stopped the executor inside its span)
    assert s["idle_under_executor"] == {"dispatch": us(200 + 50),
                                        "KSampler": us(200)}
    assert rows["dispatch"] == us(50)       # innermost over 450-500 only
    top = s["top_idle_between"][0]
    assert top == {"s": us(120), "n": 1, "owner": "KSampler",
                   "before": "jit_core", "after": "jit_core"}


def test_the_longest_stretches_name_their_owner_and_both_programs():
    ev = with_timeline([("executor", "dispatch", 0, 240, 0),
                        ("executor", "detokenize", 455, 600, 0)])
    top = ts.summarize(ev)["top_idle_between"]
    assert len(top) <= ts.TOP_IDLE
    assert [r["s"] for r in top] == sorted((r["s"] for r in top),
                                           reverse=True)
    keyed = {(r["before"], r["after"], r["owner"]): r for r in top}
    assert keyed[(ts.SLICE_STARTS, "jit_core", "dispatch")]["s"] == us(90)
    assert keyed[("jit_core", "jit_core", "unowned")]["s"] == us(150)
    assert keyed[("jit_core", "jit_pad", "detokenize")]["s"] == us(35)
    assert keyed[("jit_pad", ts.SLICE_ENDS, "detokenize")] == {
        "s": us(90), "n": 1, "owner": "detokenize", "before": "jit_pad",
        "after": ts.SLICE_ENDS}
    assert sum(r["s"] for r in top) == us(430)


def test_the_owner_rows_are_a_mean_over_the_chips():
    """Four chips of one host: the same timeline over each chip's own
    gaps, the rows a mean as ``idle`` is."""
    ev = with_timeline([("executor", "dispatch", 0, 600, 0)])
    second = json.loads(json.dumps(ev["planes"][0]))
    second["name"] = "/device:TPU:1"
    second["lines"][0]["dur_ns"][0] = 200 * K       # busy 100-300 here
    second["lines"][1]["dur_ns"][0] = 200 * K
    ev["planes"].append(second)
    s = ts.summarize(ev)
    assert s["idle_by_executor"] == {"dispatch": us((430 + 330) / 2)}
    assert s["idle_between_s"] == us(380)
    assert [c["idle_between_s"] for c in s["chips"]] == [us(430), us(330)]


@pytest.mark.parametrize("owner, want", [
    ("dispatch", "host"), ("KSampler", "host"), ("detokenize", "host"),
    ("lm_generate", "host"), ("execute", "host"),
    ("wake_drain", "wake"), ("wake_queue", "wake"), ("wake_pool", "wake"),
    ("exec_idle", "wait_request"), ("device_wait", "wait_device"),
    ("lm_drain_wait", "wait_device"), ("gc_pause", "gc"),
    ("unowned", "unowned")])
def test_every_owner_falls_in_one_of_the_six_rows(owner, want):
    assert ts.owner_class(owner) == want and want in ts.EXEC_CLASSES


@pytest.mark.parametrize("recorded", [
    "tpu_v5e_unet_block_scan.xplane.pb",
    "sd15_512_one_request.events.json.gz"])
def test_a_summary_without_a_timeline_has_no_owner_table(recorded):
    """Both recorded slices are from before the timeline: no new key, and
    (tests/test_trace_names.py pins them to the digit) ``idle``,
    ``idle_under`` and ``classes`` what they were."""
    if recorded.endswith(".pb"):
        events = ts.read_events(os.path.join(DATA, recorded))
    else:
        with gzip.open(os.path.join(REPO, "benchmarks", "chip", "testdata",
                                    recorded), "rt") as f:
            events = json.load(f)
    assert "timeline" not in events and "clock_sync" not in events
    s = ts.summarize(events)
    assert s["programs"] and s["idle"]
    for key in ("idle_by_executor", "idle_by_executor_class",
                "idle_under_executor", "idle_between_s", "top_idle_between",
                "clock_drift_ns",
                "timeline_error", "host_timeline"):
        assert key not in s, key
    assert all("idle_by_executor" not in c for c in s["chips"])
