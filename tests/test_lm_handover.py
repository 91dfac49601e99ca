"""One execution of ``lm_generate`` for the request that reaches its
generate node and the requests queued behind it (server/lm_handover.py),
tiny families on the CPU: who joins, what is kept and for whom, what is
counted, and that nothing of it touches a graph without the node."""

import asyncio
import concurrent.futures
import functools
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.ops import base as ops_base
from comfyui_distributed_tpu.ops.base import get_op
from comfyui_distributed_tpu.runtime import reuse
from comfyui_distributed_tpu.server.app import ServerState, build_app
from comfyui_distributed_tpu.utils import trace
from comfyui_distributed_tpu.workflow.executor import ExecutionResult
from comfyui_distributed_tpu.workflow.graph import parse_workflow
from tests.test_lm_prompt_ids import Counting

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKFLOW = os.path.join(REPO, "workflows", "prompt-expand-txt2img.json")
LOADER, GENERATE, SEED = "20", "21", "13"
NEW, PROMPT = 4, 32
TEXTS = ["a red fox in the snow", "a harbour at night, long exposure",
         "a walled garden in june, seen from above", "a cat"]


@pytest.fixture(autouse=True)
def tiny_family(monkeypatch):
    monkeypatch.setenv(registry.FAMILY_ENV, "tiny")
    trace.reset_aggregate_metrics()
    yield


def graph(text, seed=5, **generate):
    with open(WORKFLOW, encoding="utf-8") as f:
        g = json.load(f)
    g.pop("__doc__")
    g["5"]["inputs"].update(width=64, height=64)
    g["3"]["inputs"]["steps"] = 2
    g[SEED]["inputs"]["seed"] = seed
    g[GENERATE]["inputs"].update(text=text, max_new_tokens=NEW,
                                 prompt_tokens=PROMPT, **generate)
    return g


@pytest.fixture
def state(tmp_path):
    """A server whose executor the test drives by hand: pop, execute."""
    return ServerState(config_path=str(tmp_path / "cfg.json"),
                       input_dir=str(tmp_path / "input"),
                       output_dir=str(tmp_path / "output"),
                       start_exec_thread=False, overlap=False)


def run_next(state):
    """What one turn of ``_exec_loop`` does."""
    state._purge_abandoned()
    group = state._pop_group()
    state._execute_group(group)
    return [item["id"] for item in group]


def counters():
    snap = trace.GLOBAL_COUNTERS.snapshot()
    return {k[3:]: v for k, v in snap.items() if k.startswith("lm.")}


def stage_count(name):
    return trace.GLOBAL_STAGES.snapshot().get(name, {}).get("count", 0)


@pytest.fixture
def encoded(monkeypatch):
    """Every text that reaches ``CLIPTextEncode``."""
    texts = []
    cls = ops_base.NODE_CLASS_MAPPINGS["CLIPTextEncode"]
    real = cls.execute

    def execute(self, ctx, clip, text, **kw):
        texts.append(text)
        return real(self, ctx, clip=clip, text=text, **kw)

    monkeypatch.setattr(cls, "execute", execute)
    return texts


@pytest.fixture
def generated(monkeypatch):
    """The STRING every ``LanguageModelGenerate`` node gives, in order."""
    outs = []
    cls = ops_base.NODE_CLASS_MAPPINGS["LanguageModelGenerate"]
    real = cls.execute

    def execute(self, ctx, **kw):
        out = real(self, ctx, **kw)
        outs.append(out[0])
        return out

    monkeypatch.setattr(cls, "execute", execute)
    return outs


def alone(text, seed=5, temperature=0.0):
    model = registry.load_language_model("ouro-2.6b.safetensors")
    words, _ = model.generate(text, seed=seed, max_new_tokens=NEW,
                              prompt_tokens=PROMPT, temperature=temperature)
    return f"{text}, {words}"


# --- who joins, and what each request gets -----------------------------------

def test_four_prompts_behind_a_blocked_executor_share_one_execution(
        tmp_path, encoded):
    """Through ``POST /prompt`` and the executor's own thread: the first
    request leads, the three queued behind it are rows of its execution,
    each is counted as a request served and gets the words of its own
    single-row run."""
    async def body():
        state = ServerState(config_path=str(tmp_path / "cfg.json"),
                            input_dir=str(tmp_path / "input"),
                            output_dir=str(tmp_path / "output"))
        client = TestClient(TestServer(build_app(state)))
        await client.start_server()
        try:
            state._exec_gate.clear()
            pids = []
            for i, text in enumerate(TEXTS):
                r = await client.post("/prompt", json={
                    "prompt": graph(text, seed=10 + i), "client_id": "t"})
                assert r.status == 200, await r.text()
                pids.append((await r.json())["prompt_id"])
            assert [it["id"] for it in state._queue] == pids
            state._exec_gate.set()
            for _ in range(2400):
                hist = await (await client.get("/history")).json()
                if all(p in hist for p in pids):
                    break
                await asyncio.sleep(0.05)
            assert [hist[p]["status"] for p in pids] == ["success"] * 4
            m = await (await client.get("/distributed/metrics")).json()
            spans = [await (await client.get(
                f"/distributed/trace/{p}")).json() for p in pids]
            return state, m, spans
        finally:
            await client.close()

    state, m, traces = asyncio.run(body())
    got = {k[3:]: v for k, v in m["pipeline"]["counters"].items()
           if k.startswith("lm.")}
    assert got["executions"] == 1 and got["rows"] == 4
    assert got["padded_rows"] == 0 and got["followers_served"] == 3
    assert "followers_dropped" not in got and state.lm_handover.kept() == 0
    # written, and 0: no program is built with the few-row kernel here
    assert got["executions_fewrow"] == 0
    # counted per request: tokens over stages is one execution's steps
    stages = m["pipeline"]["stages"]
    assert stages["lm_generate"]["count"] == 4
    assert stages["detokenize"]["count"] == 4
    assert got["tokens_decoded"] / stages["lm_generate"]["count"] == NEW
    assert got["layer_applications"] == 4 * NEW * 4 * 3
    assert m["pipeline"]["gauges"]["lm.kv_cache_bytes"] == \
        4 * 2 * 12 * (PROMPT + NEW) * 4 * 16 * 4
    # the stage and the instant are on every request's own trace, the
    # followers' over the leader's interval
    intervals = []
    for tr in traces:
        spans = tr["spans"] if isinstance(tr, dict) and "spans" in tr else tr
        (gen,) = [s for s in spans if s["name"] == "lm_generate"]
        root = [s for s in spans if not s.get("parent_id")][0]
        assert "lm_ids_ready" in root["attrs"]["instants"]
        intervals.append((gen["start_s"], gen["end_s"]))
    assert all(abs(a - b) < 0.05 for iv in intervals[1:]
               for a, b in zip(iv, intervals[0]))
    # four distinct texts into the text encoder, each its own request's
    positive = [t for t in encoded if t != "blurry, lowres, watermark"]
    assert len(set(positive)) == 4
    assert positive == [alone(t, 10 + i) for i, t in enumerate(TEXTS)]


def test_rows_keep_their_own_seed_and_temperature(state, generated):
    """Two sampled followers of one text differ by their seeds, as their
    own runs do; the greedy leader ignores its seed."""
    for seed, t in ((1, 0.0), (2, 1.0), (3, 1.0)):
        state.enqueue_prompt(graph("a tower", seed=seed, temperature=t), "t")
    for _ in range(3):
        run_next(state)
    assert counters()["executions"] == 1 and counters()["padded_rows"] == 1
    assert generated == [alone("a tower", 1), alone("a tower", 2, 1.0),
                         alone("a tower", 3, 1.0)]
    assert generated[1] != generated[2] != generated[0]


def test_a_follower_stays_where_it_was_in_the_queue(state):
    pids = [state.enqueue_prompt(graph(t), "t") for t in TEXTS]
    assert run_next(state) == pids[:1]
    assert [it["id"] for it in state._queue] == pids[1:]
    assert state.lm_handover.kept() == 3
    assert [run_next(state) for _ in range(3)] == [[p] for p in pids[1:]]
    assert state.lm_handover.kept() == 0
    assert counters()["followers_served"] == 3
    assert all(state._history[p]["status"] == "success" for p in pids)


def test_no_more_rows_than_the_largest_count_and_no_request_twice(state):
    """Six wait: the first leads three, the fifth leads the sixth; a
    request that already has its result is not a row again."""
    pids = [state.enqueue_prompt(graph(f"prompt number {i}"), "t")
            for i in range(6)]
    run_next(state)
    assert counters()["rows"] == registry.LM_ROW_COUNTS[-1] == 4
    assert state.lm_handover.kept() == 3
    for _ in range(3):
        run_next(state)
    assert counters()["executions"] == 1
    run_next(state)                     # the fifth: alone with the sixth
    pad = next(b for b in registry.LM_ROW_COUNTS if b >= 2) - 2
    assert counters().get("padded_rows", 0) == pad
    assert counters() | {"executions": 2, "rows": 6} == counters()
    run_next(state)
    assert counters()["followers_served"] == 4
    assert all(state._history[p]["status"] == "success" for p in pids)


# --- what is kept is dropped when its request goes another way ----------------

def test_a_follower_cancelled_before_its_turn_is_dropped(state):
    pids = [state.enqueue_prompt(graph(t), "t") for t in TEXTS[:3]]
    run_next(state)
    assert state.lm_handover.kept() == 2
    reuse.PREVIEWS.abandon(pids[1])
    assert run_next(state) == [pids[2]]       # the purge, then the pop
    assert counters()["followers_dropped"] == 1
    assert counters()["followers_served"] == 1
    assert state.lm_handover.kept() == 0 and not state._queue
    assert state._history[pids[1]]["status"] != "success"
    # it was a request served when the execution ran: the ratio holds
    assert counters()["tokens_decoded"] / stage_count("lm_generate") == NEW


def test_a_drain_that_times_out_drops_what_was_kept(state):
    for t in TEXTS[:3]:
        state.enqueue_prompt(graph(t), "t")
    run_next(state)
    state._exec_started = True          # a queue that somebody would pop
    assert state.drain(timeout=0.0) is False
    assert state.lm_handover.kept() == 0
    assert counters()["followers_dropped"] == 2


def test_a_follower_that_left_while_the_execution_ran_is_never_kept(
        state, monkeypatch):
    """A drain times out while the leader's execution is on the device:
    its followers are finalized before anything could be kept for them,
    so nothing is, and no later execution loses a row to them."""
    model = registry.load_language_model("ouro-2.6b.safetensors")
    real = model.generate_rows

    def generate_rows(rows, *a, **kw):
        out = real(rows, *a, **kw)
        state._exec_started = True
        assert state.drain(timeout=0.0) is False
        return out

    monkeypatch.setattr(model, "generate_rows", generate_rows)
    pids = [state.enqueue_prompt(graph(t), "t") for t in TEXTS[:3]]
    run_next(state)
    assert counters()["rows"] == 3 and not state._queue
    assert state.lm_handover.kept() == 0
    assert counters()["followers_dropped"] == 2
    assert [state._history[p]["status"] for p in pids[1:]] == ["error"] * 2
    monkeypatch.setattr(model, "generate_rows", real)
    state._draining = False
    state.interrupt_event.clear()
    for t in TEXTS:
        state.enqueue_prompt(graph(t + ", again"), "t")
    run_next(state)
    assert counters()["rows"] == 3 + registry.LM_ROW_COUNTS[-1]


def test_an_edited_request_never_receives_anothers_words(state, encoded):
    """The result is kept under what it is a function of.  A request
    whose text changed after the leader read it finds nothing under its
    key, runs alone, and what was kept for it is dropped."""
    state.enqueue_prompt(graph(TEXTS[0]), "t")
    state.enqueue_prompt(graph(TEXTS[1]), "t")
    run_next(state)
    state._queue[0]["prompt"][GENERATE]["inputs"]["text"] = "a late edit"
    run_next(state)
    got = counters()
    assert got["executions"] == 2 and got["followers_dropped"] == 1
    assert "followers_served" not in got and state.lm_handover.kept() == 0
    positive = [t for t in encoded if t != "blurry, lowres, watermark"]
    assert positive == [alone(TEXTS[0]), alone("a late edit")]


def test_an_error_in_a_shared_execution_fails_the_leader_only(
        state, monkeypatch):
    model = registry.load_language_model("ouro-2.6b.safetensors")
    real = model.generate_rows

    def generate_rows(rows, *a, **kw):
        if len(rows) > 1:
            raise RuntimeError("the shared execution broke")
        return real(rows, *a, **kw)

    monkeypatch.setattr(model, "generate_rows", generate_rows)
    pids = [state.enqueue_prompt(graph(t), "t") for t in TEXTS[:3]]
    run_next(state)
    assert state._history[pids[0]]["status"] == "error"
    assert state.lm_handover.kept() == 0
    run_next(state)                     # leads the third, and fails
    run_next(state)                     # nobody waits: it runs alone
    assert state._history[pids[1]]["status"] == "error"
    assert state._history[pids[2]]["status"] == "success"
    assert counters()["executions"] == 1 and counters()["rows"] == 1


# --- the row set is closed when the device has drained --------------------------

def owe(state, pid="a group dispatched earlier"):
    """The device still owes the image of a group the executor has
    dispatched: what `_execute_group` counts under overlap."""
    with state._queue_lock:
        state._owed.add(pid)
    return {"id": pid}


def lead(state):
    """One turn of the executor on a thread of its own, back when the
    leader is in the wait for the device (it holds the queue's lock until
    it is: whoever posts next posts after the host reached the node)."""
    there = threading.Event()

    def wait(timeout=None):
        there.set()
        return threading.Condition.wait(state._drained, timeout)

    state._drained.wait = wait
    turn = threading.Thread(target=run_next, args=(state,), daemon=True)
    turn.start()
    assert there.wait(120), "the leader never waited for the device"
    return turn


def ended(turn):
    turn.join(120)
    assert not turn.is_alive(), "the leader is still waiting"


def test_prompts_posted_while_the_device_drains_ride_along(state, encoded):
    """The host reaches the node while an earlier image is in flight.  Two
    callers post before it is out: ONE execution, three real rows in four,
    each request its own words."""
    earlier = owe(state)
    pids = [state.enqueue_prompt(graph(TEXTS[0], seed=10), "a")]
    turn = lead(state)
    pids += [state.enqueue_prompt(graph(t, seed=11 + i), "bc"[i])
             for i, t in enumerate(TEXTS[1:3])]
    state._image_settled(earlier)
    ended(turn)
    got = counters()
    assert (got["executions"], got["rows"], got["padded_rows"]) == (1, 3, 1)
    assert got["drain_waits"] == 1 and got["rows_joined_in_drain"] == 2
    assert state.lm_handover.kept() == 2
    assert [run_next(state) for _ in range(2)] == [[p] for p in pids[1:]]
    assert counters()["followers_served"] == 2
    assert counters()["executions"] == 1
    assert all(state._history[p]["status"] == "success" for p in pids)
    positive = [t for t in encoded if t != "blurry, lowres, watermark"]
    assert positive == [alone(t, 10 + i) for i, t in enumerate(TEXTS[:3])]


def test_a_request_queued_when_the_host_arrived_is_not_one_that_joined(state):
    earlier = owe(state)
    for t in TEXTS[:2]:
        state.enqueue_prompt(graph(t), "t")
    turn = lead(state)
    state.enqueue_prompt(graph(TEXTS[2]), "t")
    state._image_settled(earlier)
    ended(turn)
    got = counters()
    assert got["rows"] == 3 and got["drain_waits"] == 1
    assert got["rows_joined_in_drain"] == 1


def test_where_the_device_owes_nothing_nobody_waits(state):
    """An empty server, a request below the knee, no overlap: the set is
    closed when the host reaches the node, as before."""
    for t in TEXTS[:2]:
        state.enqueue_prompt(graph(t), "t")
    run_next(state)
    got = counters()
    assert got["executions"] == 1 and got["rows"] == 2
    assert "drain_waits" not in got and "rows_joined_in_drain" not in got
    assert stage_count("lm_drain_wait") == 0 and not state._owed


def test_a_set_that_is_full_when_the_host_arrives_does_not_wait(state):
    owe(state)
    for t in TEXTS:
        state.enqueue_prompt(graph(t), "t")
    turn = threading.Thread(target=run_next, args=(state,), daemon=True)
    turn.start()
    ended(turn)
    got = counters()
    assert got["rows"] == registry.LM_ROW_COUNTS[-1] == 4
    assert "drain_waits" not in got and stage_count("lm_drain_wait") == 0


def test_two_callers_in_a_closed_loop_wait_and_find_nobody(state):
    """The closed loop of two, with the other caller's image in flight
    when the host reaches the node: every leader waits for it, and its
    caller posts again only after it is out and the set is closed."""
    state.enqueue_prompt(graph("caller a, round 0"), "a")
    for turn_no in range(1, 5):
        others = owe(state, f"the other caller's denoise, turn {turn_no}")
        turn = lead(state)
        state._image_settled(others)
        ended(turn)
        state.enqueue_prompt(
            graph(f"caller {'ab'[turn_no % 2]}, round {turn_no // 2}"),
            "ab"[turn_no % 2])
    got = counters()
    assert got["rows"] / got["executions"] == 1.0 and got["rows"] == 4
    assert got["drain_waits"] == got["executions"]
    assert got["rows_joined_in_drain"] == 0 and got["padded_rows"] == 0
    assert "followers_served" not in got


def test_every_drain_wait_is_one_wake_drain_on_the_leaders_thread(state):
    """The hand-over by name (PR 51): from the pool thread's notify under
    the queue's lock to the leader past its wait, once a drain wait, so
    ``wake_drain.count`` is ``lm.drain_waits``; a leader that found
    nothing owed waited for nobody."""
    state.enqueue_prompt(graph("nothing is owed"), "a")
    run_next(state)
    assert stage_count("wake_drain") == 0 and "drain_waits" not in counters()
    for turn_no in range(3):
        others = owe(state, f"an image in flight, turn {turn_no}")
        state.enqueue_prompt(graph(f"caller b, round {turn_no}"), "b")
        turn = lead(state)
        before = state._drained_ns
        state._image_settled(others)
        assert state._drained_ns > before       # stamped under the lock
        ended(turn)
    assert stage_count("wake_drain") == counters()["drain_waits"] == 3
    assert stage_count("lm_drain_wait") == 3


@pytest.mark.parametrize("name, rows, where, want", [
    ("ouro", 1, ("tpu", None), 0),      # the one-row program, on a TPU too
    ("ouro", 3, ("tpu", None), 1),      # padded to four: few rows
    ("ouro", 3, ("cpu", None), 0),
    ("ouro", 3, ("tpu", {"data": 2, "tensor": 1}), 0),      # under a mesh
    ("openpangu", 1, ("tpu", None), 0),
    ("openpangu", 2, ("tpu", None), 1),
])
def test_an_execution_built_with_the_few_row_path_is_counted(
        name, rows, where, want, monkeypatch):
    """``lm.executions_fewrow`` beside ``lm.executions``: how often the
    program an execution ran was built with the few-row path (its decode
    step's rows and where it was traced decide).  The platform is read as
    the case says; the tiny widths keep every product with ``jnp.dot``,
    so the CPU runs either program."""
    from comfyui_distributed_tpu.models import looplm
    monkeypatch.setattr(looplm, "_where", lambda: where)
    model = registry.load_language_model(
        f"{name}-fewrow-{rows}-{want}-{len(where[1] or ())}.safetensors")
    out = model.generate_rows(
        [registry.LMRow(t, i) for i, t in enumerate(TEXTS[:rows])],
        max_new_tokens=NEW, prompt_tokens=PROMPT)
    assert len(out) == rows
    got = counters()
    assert got["executions"] == 1 and got["rows"] == rows
    assert got["executions_fewrow"] == want


def _result(*futures):
    return ExecutionResult(outputs={}, images=[], timings={},
                           image_futures=list(futures))


def _raising():
    f = concurrent.futures.Future()
    f.set_exception(OSError("the disk is full"))
    return f


@pytest.mark.parametrize("way, ends", [
    ("device_ready", None), ("a host edge that raises", "error"),
    ("a graph without an image", "success"), ("a purge", "abandoned"),
    ("a slot the step executor aborts", "error"),
    ("a drain that times out", None)])
def test_every_way_a_dispatched_group_ends_lets_the_leader_go(state, way,
                                                              ends):
    """A leader that could hang is worse than a padded row.  ``ends``:
    the earlier group's history entry, where that way writes one."""
    state.enqueue_prompt(graph("dispatched earlier"), "t")
    group = state._pop_group()
    owe(state, group[0]["id"])
    pid = state.enqueue_prompt(graph("the leader"), "t")
    turn = lead(state)
    t0 = time.perf_counter()
    if way == "device_ready":
        ops_base.fetch_image_array(
            np.zeros((1, 8, 8, 3), np.float32),
            functools.partial(state._image_settled, group[0]))
    elif way == "a host edge that raises":
        state._finalize_hand(group, _result(_raising()), None, t0)
    elif way == "a graph without an image":
        state._finalize_hand(group, _result(), None, t0)
    elif way == "a purge":
        state._finalize_hand(group, None, reuse.AbandonedError("gone"), t0)
    elif way == "a slot the step executor aborts":
        state._finalize_hand(group, None, RuntimeError("aborted"), t0)
    else:
        state._exec_started = True
        assert state.drain(timeout=0.0) is False
    ended(turn)
    assert not state._owed and counters()["drain_waits"] == 1
    # let go by a drain, it runs into the drain's interrupt at its next node
    assert state._history[pid]["status"] == \
        ("error" if way == "a drain that times out" else "success")
    assert state._history.get(group[0]["id"], {}).get("status") == ends


def test_the_server_owes_what_it_dispatched_until_the_image_is_out(
        tmp_path, monkeypatch):
    """Under overlap the host edge is deferred: the group is owed from the
    return of its last enqueue to its ``device_ready`` on the pool's
    thread, before the PNG and the history entry; a graph that dispatched
    nothing to wait for is never owed."""
    from comfyui_distributed_tpu.ops import basic
    state = ServerState(config_path=str(tmp_path / "cfg.json"),
                        input_dir=str(tmp_path / "input"),
                        output_dir=str(tmp_path / "output"),
                        start_exec_thread=False, overlap=True)
    let_go, ready = threading.Event(), threading.Event()
    real = basic.fetch_image_array

    def fetch(x, hook=None):
        def hooked():
            hook()
            ready.set()
        assert let_go.wait(120)
        return real(x, hooked)

    monkeypatch.setattr(basic, "fetch_image_array", fetch)
    pid = state.enqueue_prompt(graph(TEXTS[0]), "t")
    run_next(state)
    assert state._owed == {pid} and pid not in state._history
    let_go.set()
    assert ready.wait(120)
    assert not state._owed and pid not in state._history
    state._finalize_group(*state._finalize_q.get(timeout=120))
    assert state._history[pid]["status"] == "success" and not state._owed
    # a text-only graph: nothing deferred, nothing owed
    state.enqueue_prompt({k: v for k, v in graph(TEXTS[1]).items()
                          if k in (LOADER, GENERATE, SEED)}, "t")
    run_next(state)
    assert not state._owed
    state._finalize_group(*state._finalize_q.get(timeout=120))
    state.host_pool.shutdown()


def test_the_wait_is_the_leaders_span_and_not_the_hosts_own_seconds(state):
    """``lm_drain_wait`` is a stage of the leader's own trace, with the
    profiler off; the thread spends it in ``device_wait``, which
    ``dispatch`` (own) leaves out."""
    earlier = owe(state)
    pid = state.enqueue_prompt(graph(TEXTS[0]), "t")
    turn = lead(state)
    state._image_settled(earlier)
    ended(turn)
    spans = trace.GLOBAL_TRACES.get(pid)["spans"]
    by_id = {s["span_id"]: s for s in spans}
    (drain,) = [s for s in spans if s["name"] == "lm_drain_wait"]
    node = drain
    while node.get("parent_id") in by_id:
        node = by_id[node["parent_id"]]
    assert node["name"] == "job" and node["attrs"]["prompt_id"] == pid
    assert by_id[drain["parent_id"]]["name"] == "LanguageModelGenerate"
    (inside,) = [s for s in spans if s["name"] == "device_wait"
                 and s["parent_id"] == drain["span_id"]]
    (dispatch,) = [s for s in spans if s["name"] == "dispatch"]
    assert dispatch["attrs"]["device_wait_s"] >= inside["duration_s"] - 1e-5
    (generate,) = [s for s in spans if s["name"] == "lm_generate"]
    assert drain["end_s"] <= generate["start_s"] + 1e-5
    stages = trace.GLOBAL_STAGES.snapshot()
    assert stages["lm_drain_wait"]["count"] == 1
    assert stages["dispatch_wait"]["total_s"] \
        >= inside["duration_s"] - 1e-5



# --- who does not join ---------------------------------------------------------

def test_two_callers_in_a_closed_loop_never_meet_at_the_node(state):
    """A closed loop of two: while one request is in its generate node
    the other caller's is in its own denoise, and posts its next only
    when that has ended.  Whoever reaches the node finds nobody."""
    waiting = state.enqueue_prompt(graph("caller a, round 0"), "a")
    for turn in range(1, 7):
        assert run_next(state) == [waiting]
        waiting = state.enqueue_prompt(
            graph(f"caller {'ab'[turn % 2]}, round {turn // 2}"), "ab"[turn % 2])
    got = counters()
    assert got["rows"] / got["executions"] == 1.0 and got["rows"] == 6
    assert got["padded_rows"] == 0 and "followers_served" not in got
    assert got["tokens_decoded"] / stage_count("lm_generate") == NEW


@pytest.mark.parametrize("change, joins", [
    ({}, True),
    ({"max_new_tokens": NEW + 1}, False),
    ({"prompt_tokens": PROMPT - 8}, False),
    ({"model_name": "another-tiny-lm.safetensors"}, False),
    ({"text": ["30", 0]}, False),           # a text another node makes
    ({"temperature": 0.5}, True),
])
def test_only_a_call_of_the_same_model_and_lengths_joins(state, change,
                                                         joins):
    state.enqueue_prompt(graph("the leader"), "t")
    g = graph("the one behind")
    name = change.pop("model_name", None)
    if name:
        g[LOADER]["inputs"]["model_name"] = name
    g[GENERATE]["inputs"].update(change)
    if isinstance(change.get("text"), list):
        g["30"] = {"class_type": "LanguageModelGenerate", "inputs": {
            **g[GENERATE]["inputs"], "text": "inner"}}
    state.enqueue_prompt(g, "t")
    run_next(state)
    # an inner literal call of a graph may join where its outer cannot
    rows = 2 if joins or isinstance(change.get("text"), list) else 1
    assert counters()["rows"] == rows
    run_next(state)
    assert all(h["status"] == "success" for h in state._history.values())
    assert state.lm_handover.kept() == 0


GRANITE = "granite-4.0-h-micro.safetensors"
GUIDES = {"A": "draw in the style of a woodcut",
          "B": "draw in the style of a fresco", " ": ""}


@pytest.mark.parametrize("model_name, queued, executions", [
    # the leader's rows start from the snapshot of A: the two behind it
    # that carry A ride along, past B and the one without, which lead
    # their own at their turn (B from a snapshot of its own, alone)
    (GRANITE, "AAB A", [("A", 3), ("B", 1), (" ", 1)]),
    (GRANITE, "ABB", [("A", 1), ("B", 2)]),
    # a leader that scans its whole prompt takes whoever waits, as before
    (GRANITE, " AB", [(" ", 3)]),
    # and so does a family that offers no snapshot
    ("ouro-2.6b.safetensors", "AB A", [("A", 4)]),
])
def test_behind_a_leader_on_a_snapshot_only_its_instructions_ride_along(
        state, generated, model_name, queued, executions):
    """Who joins follows from what the leader's execution is: a short
    prefill behind one snapshot.  Every request gets the words of its own
    single-row run, whoever led it."""
    for i, which in enumerate(queued):
        g = graph(f"a tower number {i}", seed=i, instructions=GUIDES[which])
        g[LOADER]["inputs"]["model_name"] = model_name
        state.enqueue_prompt(g, "t")
    seen = []
    for _ in queued:
        before = counters()
        run_next(state)
        now = counters()
        if now["executions"] != before.get("executions", 0):
            seen.append((now["rows"] - before.get("rows", 0),
                         now.get("prefix_hits", 0)
                         - before.get("prefix_hits", 0)))
    on_snapshot = model_name == GRANITE
    assert seen == [(rows, rows if GUIDES[which] and on_snapshot else 0)
                    for which, rows in executions]
    assert counters()["followers_served"] == len(queued) - len(executions)
    assert state.lm_handover.kept() == 0
    assert all(h["status"] == "success" for h in state._history.values())
    model = registry.load_language_model(model_name)
    for i, which in enumerate(queued):
        (words, _), = model.generate_rows(
            [registry.LMRow(f"a tower number {i}", i,
                            instructions=GUIDES[which])], NEW, PROMPT)
        assert generated[i] == f"a tower number {i}, {words}"


@pytest.mark.parametrize("instructions", ["", GUIDES["A"]])
@pytest.mark.parametrize("model_name", [GRANITE, "ouro-2.6b.safetensors"])
def test_a_requests_ids_are_made_once_and_never_while_the_device_waits(
        state, monkeypatch, model_name, instructions):
    """Four prompts behind an executor that waits for the device, two of
    them posted during the wait, twice over: ONE execution each time, every
    row encoded once, the instructions once for both executions, and after
    the drain no pass for a row that was queued before it and one, over
    its own words alone, for a row that joined.  Each request gets the
    words of its own single-row run and the tokenizer's own ids."""
    model = registry.load_language_model(model_name)
    drained = threading.Event()
    tok = Counting(model.tokenizer, drained.is_set)
    monkeypatch.setattr(model, "tokenizer", tok)
    model._row_ids.clear()
    model._instruction_ids.clear()
    real = state.lm_handover._drain_wait

    def drain_wait():
        out = real()
        drained.set()
        return out

    monkeypatch.setattr(state.lm_handover, "_drain_wait", drain_wait)
    outs = []
    cls = ops_base.NODE_CLASS_MAPPINGS["LanguageModelGenerate"]
    execute = cls.execute
    monkeypatch.setattr(cls, "execute", lambda self, ctx, **kw: (
        outs.append(execute(self, ctx, **kw)) or outs[-1]))

    def post(text, seed):
        g = graph(text, seed=seed, instructions=instructions)
        g[LOADER]["inputs"]["model_name"] = model_name
        # the language model's nodes alone: no image is made
        return state.enqueue_prompt(
            {k: g[k] for k in (LOADER, GENERATE, SEED)}, "t")

    asked = [(f"{t}, round {rnd}", 10 * rnd + i)
             for rnd in range(2) for i, t in enumerate(TEXTS)]
    for rnd in range(2):
        drained.clear()
        earlier = owe(state, f"dispatched before round {rnd}")
        for text, seed in asked[4 * rnd:4 * rnd + 2]:
            post(text, seed)
        turn = lead(state)
        for text, seed in asked[4 * rnd + 2:4 * rnd + 4]:
            post(text, seed)
        state._image_settled(earlier)
        ended(turn)
        for _ in range(3):
            run_next(state)
    got = counters()
    assert (got["executions"], got["rows"], got["padded_rows"]) == (2, 8, 0)
    assert got["drain_waits"] == 2 and got["rows_joined_in_drain"] == 4
    assert got["followers_served"] == 6 and state.lm_handover.kept() == 0
    assert got.get("prefix_hits", 0) == \
        (8 if instructions and model_name == GRANITE else 0)
    assert all(h["status"] == "success" for h in state._history.values())
    # every row once and the instructions once, whatever the executions
    assert sum(text == instructions for text, _, _ in tok.passes) == \
        bool(instructions)
    assert len(tok.passes) == 8 + bool(instructions)
    assert (got["prompt_encodes"], got["prompt_encode_ids"]) == \
        (len(tok.passes), sum(n for _, n, _ in tok.passes))
    # after the drain: the two that joined, each over its own words (with
    # instructions too: the hash tokenizer says what a text adds to them)
    assert [text for text, _, after in tok.passes if after] == [
        registry.EXPAND_TEMPLATE.format(text=text) for rnd in range(2)
        for text, _ in asked[4 * rnd + 2:4 * rnd + 4]]
    # the words of each request's own run, the ids the tokenizer gives
    for (text, seed), (said, out) in zip(asked, outs):
        (words, alone_out), = model.generate_rows(
            [registry.LMRow(text, seed, instructions=instructions)],
            NEW, PROMPT)
        assert said == f"{text}, {words}"
        whole = registry.EXPAND_TEMPLATE.format(text=text)
        whole = tok.inner.encode(
            f"{instructions} {whole}" if instructions else whole)[:PROMPT]
        assert np.array_equal(out.prompt_ids, whole)
        assert np.array_equal(alone_out.prompt_ids, whole)
        assert np.array_equal(np.asarray(out.tokens)[out.row],
                              np.asarray(alone_out.tokens)[0])
    # and those runs found every row's ids kept
    assert len(tok.passes) == 8 + bool(instructions)


@pytest.mark.parametrize("edit, want", [
    (lambda g: None, ("ouro-2.6b.safetensors", "x", 5, 0.0, NEW, PROMPT)),
    (lambda g: g[GENERATE]["inputs"].update(seed=9, temperature=1),
     ("ouro-2.6b.safetensors", "x", 9, 1.0, NEW, PROMPT)),
    (lambda g: [g[GENERATE]["inputs"].pop(k) for k in
                ("max_new_tokens", "prompt_tokens", "temperature")],
     ("ouro-2.6b.safetensors", "x", 5, 0.0, 64, 64)),
    (lambda g: g[GENERATE]["inputs"].update(seed=["5", 0]), None),
    (lambda g: g[GENERATE]["inputs"].update(text=["6", 0]), None),
    (lambda g: g[GENERATE]["inputs"].update(model=["4", 1]), None),
    (lambda g: g[GENERATE]["inputs"].update(temperature="hot"), None),
    (lambda g: g[GENERATE].update(hidden={"x": 1}), None),
    (lambda g: g[SEED].update(hidden={"is_worker": True}), None),
])
def test_literal_call_reads_the_graph_alone(edit, want):
    g = graph("x")
    edit(g)
    parsed = parse_workflow(g)
    call = get_op("LanguageModelGenerate").literal_call(
        parsed, parsed.nodes[GENERATE])
    if want is None:
        assert call is None
    else:
        name, row, n, p = call
        assert (name, row.text, row.seed, row.temperature, n, p) == want


def test_on_a_worker_a_distributed_seed_is_not_read_from_the_graph():
    parsed = parse_workflow(graph("x"))
    op = get_op("LanguageModelGenerate")
    assert op.literal_call(parsed, parsed.nodes[GENERATE]) is not None
    assert op.literal_call(parsed, parsed.nodes[GENERATE],
                           is_worker=True) is None


# --- nothing compiles in a served window ---------------------------------------

def test_the_first_shared_execution_compiles_nothing(state):
    """Every row count is compiled when the first request of a length
    meets the model: between the end of the first single request and the
    end of the first shared one ``retraces.compiles`` does not move."""
    state.enqueue_prompt(graph("the first request, alone"), "t")
    run_next(state)
    mark = trace.GLOBAL_RETRACES.mark()
    for t in TEXTS:
        state.enqueue_prompt(graph(t), "t")
    for _ in TEXTS:
        run_next(state)
    assert counters()["executions"] == 2 and counters()["rows"] == 5
    assert trace.GLOBAL_RETRACES.since(mark)["compiles"] == 0
    model = registry.load_language_model("ouro-2.6b.safetensors")
    assert sorted(model._programs[(NEW, PROMPT, 0)]) == \
        list(registry.LM_ROW_COUNTS)


def test_the_first_shared_execution_after_a_wait_compiles_nothing(state):
    """The same guard where the rows came in during the wait: three real
    rows run the program the first request compiled for four."""
    state.enqueue_prompt(graph("the first request, alone"), "t")
    run_next(state)
    mark = trace.GLOBAL_RETRACES.mark()
    earlier = owe(state)
    state.enqueue_prompt(graph(TEXTS[0]), "t")
    turn = lead(state)
    for t in TEXTS[1:3]:
        state.enqueue_prompt(graph(t), "t")
    state._image_settled(earlier)
    ended(turn)
    got = counters()
    assert got["executions"] == 2 and got["rows"] == 4
    assert got["rows_joined_in_drain"] == 2
    since = trace.GLOBAL_RETRACES.since(mark)
    assert since["compiles"] == 0 and since["cache_loads"] == 0
    assert since["lower_s"] == 0


# --- a graph without the node ----------------------------------------------------

_PROBE = """
import json, sys, tempfile
from comfyui_distributed_tpu.server.app import ServerState
g = json.load(open(sys.argv[1])); g.pop("__doc__", None)
g["5"]["inputs"].update(width=64, height=64); g["3"]["inputs"]["steps"] = 2
d = tempfile.mkdtemp()
state = ServerState(config_path=d + "/cfg.json", input_dir=d, output_dir=d,
                    start_exec_thread=False, overlap=False)
pids = [state.enqueue_prompt(json.loads(json.dumps(g)), "t") for _ in "ab"]
for _ in pids:
    state._purge_abandoned()
    state._execute_group(state._pop_group())
from comfyui_distributed_tpu.utils import trace
print(json.dumps({
    "status": [state._history[p]["status"] for p in pids],
    "looplm_imported": "comfyui_distributed_tpu.models.looplm" in sys.modules,
    "handover": type(state.lm_handover).__name__,
    "lm_calls": [it.get("lm_calls") for it in state._queue],
    "counters": [c for c in trace.GLOBAL_COUNTERS.snapshot()
                 if c.startswith("lm.")]}))
"""


def test_a_server_whose_graphs_hold_no_generate_node_never_imports_looplm(
        tmp_path):
    r = subprocess.run(
        [sys.executable, "-c", _PROBE,
         os.path.join(REPO, "workflows", "distributed-txt2img.json")],
        capture_output=True, text=True, timeout=600, cwd=str(tmp_path),
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
             "DTPU_DEFAULT_FAMILY": "tiny",
             "DISTRIBUTED_TPU_CONFIG": str(tmp_path / "cfg.json")})
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["status"] == ["success", "success"]
    assert got["looplm_imported"] is False and got["counters"] == []
    assert got["handover"] == "GenerateHandover"


# --- a second family through the same path (PR 32) ----------------------------

PANGU = "openpangu-ultra-moe-718b.safetensors"


def pangu_graph(text, seed=5):
    g = graph(text, seed=seed)
    g[LOADER]["inputs"]["model_name"] = PANGU
    return g


def test_four_prompts_of_the_expert_model_share_one_execution(tmp_path):
    """As Ouro's test above, for the model of the other family: a held
    executor with four requests behind it gives ONE execution, 4 rows, 3
    followers served, through the same nodes, hand-over and counting; and
    the family's routing counters and the latent cache's gauge are on
    ``GET /distributed/metrics`` beside them."""
    async def body():
        state = ServerState(config_path=str(tmp_path / "cfg.json"),
                            input_dir=str(tmp_path / "input"),
                            output_dir=str(tmp_path / "output"))
        client = TestClient(TestServer(build_app(state)))
        await client.start_server()
        try:
            state._exec_gate.clear()
            pids = []
            for i, text in enumerate(TEXTS):
                r = await client.post("/prompt", json={
                    "prompt": pangu_graph(text, seed=10 + i),
                    "client_id": "t"})
                assert r.status == 200, await r.text()
                pids.append((await r.json())["prompt_id"])
            state._exec_gate.set()
            for _ in range(2400):
                hist = await (await client.get("/history")).json()
                if all(p in hist for p in pids):
                    break
                await asyncio.sleep(0.05)
            assert [hist[p]["status"] for p in pids] == ["success"] * 4
            return state, await (
                await client.get("/distributed/metrics")).json()
        finally:
            await client.close()

    state, m = asyncio.run(body())
    got = {k[3:]: v for k, v in m["pipeline"]["counters"].items()
           if k.startswith("lm.")}
    assert got["executions"] == 1 and got["rows"] == 4
    assert got["padded_rows"] == 0 and got["followers_served"] == 3
    assert "followers_dropped" not in got and state.lm_handover.kept() == 0
    assert got["executions_fewrow"] == 0
    stages = m["pipeline"]["stages"]
    assert stages["lm_generate"]["count"] == 4
    assert got["tokens_decoded"] / stages["lm_generate"]["count"] == NEW
    # tokens x blocks held (3 in the tiny model of this family)
    assert got["layer_applications"] == 4 * NEW * 3
    # rows x steps x expert blocks x top-k pairs, some of them local
    assert got["expert_pairs"] == 4 * NEW * 2 * 4
    assert 0 < got["expert_pairs_local"] < got["expert_pairs"]
    assert 0 < got["expert_hits"] <= NEW * 2 * 4
    assert got["expert_pairs_dropped"] == 0
    # the LATENT cache: 3 blocks x 4 rows x positions x (16 + 8) float32
    assert m["pipeline"]["gauges"]["lm.kv_cache_bytes"] == \
        3 * 4 * (PROMPT + NEW) * 24 * 4


def test_two_graphs_naming_the_two_models_keep_both_resident(state):
    """Nothing evicts a language model: both stay, each serves its own
    graph, and a request of one is no row of the other's execution."""
    first = state.enqueue_prompt(graph("a red fox"), "t")
    second = state.enqueue_prompt(pangu_graph("a red fox"), "t")
    third = state.enqueue_prompt(graph("a harbour"), "t")
    assert run_next(state) == [first]
    # the leader took the other Ouro request along, not the other model's
    assert counters()["rows"] == 2 and state.lm_handover.kept() == 1
    assert run_next(state) == [second]
    assert counters()["executions"] == 2 and counters()["rows"] == 3
    assert run_next(state) == [third]
    assert counters()["executions"] == 2
    assert all(state._history[p]["status"] == "success"
               for p in (first, second, third))
    resident = {k.split(":")[1]: v for k, v in
                registry._pipeline_cache.items() if k.startswith("lm:")}
    assert {"ouro-2.6b.safetensors", PANGU} <= set(resident)
    assert {m.family for m in resident.values()} >= {"ouro", "pangu"}


def test_a_model_that_cannot_fit_beside_the_resident_one_is_refused_by_name(
        monkeypatch):
    """Where the device says what it has free (a TPU does; the CPU does
    not) the second model is refused with its name, its need and what is
    resident, before the allocator fails with an error that names
    nothing."""
    registry.load_language_model("ouro-2.6b.safetensors")
    monkeypatch.setattr(registry, "_device_free_bytes", lambda: 1000)
    with pytest.raises(ValueError) as e:
        registry.load_language_model("openpangu-second.safetensors")
    said = str(e.value)
    assert "openpangu-second.safetensors" in said and "0.00 GB free" in said
    assert "ouro-2.6b.safetensors" in said
    # what is resident is served as before
    assert registry.load_language_model("ouro-2.6b.safetensors").family \
        == "ouro"
