"""The prompt expander's graph on the system's normal path, tiny families
on the CPU: ``workflows/prompt-expand-txt2img.json`` through the executor
and through ``POST /prompt`` -> ``/history`` -> PNG; what the caches key
on; the stages and counters on ``/distributed/metrics``; a ``data=4``
mesh; and that a graph without the new nodes touches nothing of the
language model."""

import asyncio
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.models.tokenizer import (HashLMTokenizer,
                                                      make_lm_tokenizer)
from comfyui_distributed_tpu.ops.base import OpContext, get_op
from comfyui_distributed_tpu.parallel.mesh import get_runtime
from comfyui_distributed_tpu.runtime import reuse
from comfyui_distributed_tpu.server.app import ServerState, build_app
from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils import trace
from comfyui_distributed_tpu.workflow import scheduler
from comfyui_distributed_tpu.workflow.executor import WorkflowExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKFLOW = os.path.join(REPO, "workflows", "prompt-expand-txt2img.json")
GENERATE, POSITIVE = "21", "6"


@pytest.fixture(autouse=True)
def tiny_family(monkeypatch):
    monkeypatch.setenv(registry.FAMILY_ENV, "tiny")
    yield


def graph(text="a lighthouse on a cliff at dawn", seed=5, save=None,
          **generate):
    """The shipped workflow at the tiny family's sizes."""
    with open(WORKFLOW, encoding="utf-8") as f:
        g = json.load(f)
    g.pop("__doc__")
    g["5"]["inputs"].update(width=64, height=64)
    g["3"]["inputs"]["steps"] = 2
    g["13"]["inputs"]["seed"] = seed
    g[GENERATE]["inputs"].update(text=text, max_new_tokens=4,
                                 prompt_tokens=32, **generate)
    if save:
        g["9"] = {"class_type": "SaveImage",
                  "inputs": {"images": ["14", 0], "filename_prefix": save}}
    return g


def execute(g, tmp_path):
    ctx = OpContext(runtime=get_runtime(), output_dir=str(tmp_path))
    return WorkflowExecutor(ctx).execute(g)


# --- through the executor ----------------------------------------------------

def test_the_shipped_workflow_is_txt2img_with_two_nodes_in_front():
    with open(WORKFLOW, encoding="utf-8") as f:
        g = json.load(f)
    with open(os.path.join(REPO, "workflows",
                           "distributed-txt2img.json")) as f:
        base = json.load(f)
    added = {k: v["class_type"] for k, v in g.items()
             if k not in base and k != "__doc__"}
    assert added == {"20": "LanguageModelLoader",
                     GENERATE: "LanguageModelGenerate"}
    assert g[POSITIVE]["inputs"]["text"] == [GENERATE, 0]
    assert g[GENERATE]["inputs"]["seed"] == ["13", 0]
    for nid in ("3", "7", "8", "13", "14", "9"):
        assert g[nid] == base[nid]


def test_the_graph_runs_and_a_second_request_compiles_nothing(tmp_path):
    first = execute(graph(), tmp_path)
    text = first.outputs[GENERATE][0]
    assert text.startswith("a lighthouse on a cliff at dawn, ")
    assert len(text.split(", ", 1)[1].split()) == 4     # one word a token
    assert len(first.images) >= 1 and first.images[0].shape == (16, 16, 3)
    out = first.outputs[GENERATE][1]
    assert out.tokens.shape == (1, 4) and out.logits.shape == (1, 4, 512)
    assert out.logits.dtype == np.float32
    again = execute(graph(), tmp_path)
    assert again.retraces["compiles"] == 0
    assert again.outputs[GENERATE][0] == text
    np.testing.assert_array_equal(again.images[0], first.images[0])


def test_two_texts_give_two_expansions_and_two_images(tmp_path):
    a = execute(graph("a red fox in the snow"), tmp_path)
    b = execute(graph("a harbour at night, long exposure"), tmp_path)
    assert b.retraces["compiles"] == 0          # one program shape
    cont = [r.outputs[GENERATE][0].split(", ", 1)[1] for r in (a, b)]
    assert cont[0] != cont[1]
    assert not np.array_equal(a.images[0], b.images[0])


def test_the_seed_moves_a_sampled_expansion_and_not_a_greedy_one(tmp_path):
    def words(seed, temperature):
        return execute(graph(seed=seed, temperature=temperature),
                       tmp_path).outputs[GENERATE][0]
    assert words(1, 0.0) == words(2, 0.0)
    assert words(1, 1.0) != words(2, 1.0)
    assert words(1, 1.0) == words(1, 1.0)


def test_the_embed_cache_keys_a_string_link_on_the_resolved_text(tmp_path):
    """The text encode behind the generate node is keyed as a widget
    holding the expanded text would be: a second run hits, and so does a
    plain graph that carries that text itself."""
    reuse.get_reuse().subgraph.clear()
    cache = reuse.get_reuse().subgraph
    first = execute(graph("a walled garden in june"), tmp_path)
    text = first.outputs[GENERATE][0]
    hits = cache.hits
    execute(graph("a walled garden in june"), tmp_path)
    assert cache.hits == hits + 2               # positive and negative
    plain = graph()
    del plain["20"], plain[GENERATE]
    plain[POSITIVE]["inputs"]["text"] = text
    hits = cache.hits
    out = execute(plain, tmp_path)
    assert cache.hits == hits + 2
    np.testing.assert_array_equal(out.images[0], first.images[0])
    # another text behind the same link misses
    misses = cache.misses
    execute(graph("a walled garden in november"), tmp_path)
    assert cache.misses == misses + 1


def test_node_key_reads_a_resolved_string_as_the_widget_it_stands_for():
    from comfyui_distributed_tpu.workflow.graph import parse_workflow
    linked = parse_workflow(graph())
    keys = reuse.subgraph_keys(linked, {})
    assert "4" in keys and POSITIVE not in keys     # not known before a run
    resolved = reuse.node_key(linked, POSITIVE, {}, keys,
                              resolved={(GENERATE, 0): "expanded text"})
    plain = graph()
    plain[POSITIVE]["inputs"]["text"] = "expanded text"
    assert resolved == reuse.subgraph_keys(parse_workflow(plain),
                                           {})[POSITIVE]
    assert resolved != reuse.node_key(
        linked, POSITIVE, {}, keys, resolved={(GENERATE, 0): "other text"})


def test_the_scheduler_treats_the_new_nodes_as_it_treats_a_text_encode():
    """Coalescing signature and result-cache key: the generate node's
    text and seed are part of the program's identity, as a text encode's
    text is; the KSampler's seed alone is masked."""
    for types in (C.COALESCE_SAFE_NODE_TYPES, C.RESULT_CACHE_SAFE_NODE_TYPES):
        assert {"LanguageModelLoader", "LanguageModelGenerate"} <= types
    plain = {k: v for k, v in graph().items() if k not in ("13", "14")}
    plain["3"]["inputs"]["seed"] = 11
    plain[GENERATE]["inputs"]["seed"] = 0
    plain["9"]["inputs"]["images"] = ["8", 0]
    sig = scheduler.coalesce_signature(plain)
    assert sig is not None
    other_seed = json.loads(json.dumps(plain))
    other_seed["3"]["inputs"]["seed"] = 12
    assert scheduler.coalesce_signature(other_seed) == sig
    other_text = json.loads(json.dumps(plain))
    other_text[GENERATE]["inputs"]["text"] = "something else"
    assert scheduler.coalesce_signature(other_text) != sig
    assert reuse.result_key(plain) is not None
    assert reuse.result_key(plain) != reuse.result_key(other_text)


def test_the_save_node_writes_what_the_generate_node_left_on_the_device(
        tmp_path):
    g = graph()
    g["22"] = {"class_type": "SaveLanguageModelOutput",
               "inputs": {"lm_output": [GENERATE, 1],
                          "filename_prefix": "probe"}}
    res = execute(g, tmp_path)
    saved = np.load(tmp_path / "probe.npz")
    out = res.outputs[GENERATE][1]
    assert saved["tokens"].shape == (4,)
    assert saved["logits"].shape == (4, 512)
    assert saved["exit_probs"].shape == (4, 4)
    np.testing.assert_array_equal(saved["tokens"], np.asarray(out.tokens[0]))
    assert saved["prompt_ids"][0] == HashLMTokenizer.bos_id
    assert 3 < len(saved["prompt_ids"]) <= 32
    # greedy: each token is the largest of the logits it was drawn from
    np.testing.assert_array_equal(saved["logits"].argmax(-1),
                                  saved["tokens"])
    with pytest.raises(ValueError, match="escapes the output directory"):
        get_op("SaveLanguageModelOutput").execute(
            OpContext(output_dir=str(tmp_path)), out,
            filename_prefix="../outside")


# --- the tokenizer pair ------------------------------------------------------

def test_the_hash_tokenizer_goes_both_ways_with_no_asset(tmp_path):
    tok = make_lm_tokenizer(str(tmp_path), 49152)
    assert isinstance(tok, HashLMTokenizer)
    ids = tok.encode("A lighthouse, at dawn")
    assert ids[0] == tok.bos_id and len(ids) == 6       # the comma is one
    assert ids == tok.encode("a  LIGHTHOUSE , at dawn")
    assert all(3 <= i < 49152 for i in ids[1:])
    words = tok.decode(ids + [tok.eos_id, tok.pad_id])
    assert len(words.split()) == 5                      # specials are silent
    assert len({tok.word(i) for i in range(49152)}) == 49152
    assert tok.word(49151).isalpha() and tok.decode([]) == ""


def test_a_tokenizer_json_beside_the_checkpoint_is_used(tmp_path):
    from tokenizers import Tokenizer, models, pre_tokenizers
    vocab = {"<|endoftext|>": 0, "a": 1, "cat": 2, "sat": 3}
    t = Tokenizer(models.WordLevel(vocab, unk_token="<|endoftext|>"))
    t.pre_tokenizer = pre_tokenizers.Whitespace()
    t.save(str(tmp_path / "tokenizer.json"))
    tok = make_lm_tokenizer(str(tmp_path), 49152)
    assert tok.encode("a cat sat") == [1, 2, 3] and tok.vocab_size == 4
    assert tok.decode([2, 3]) == "cat sat" and tok.pad_id == 0


# --- through POST /prompt ------------------------------------------------------

def run_with_client(fn, tmp_path, **state_kw):
    async def go():
        state = ServerState(
            config_path=str(tmp_path / "cfg.json"),
            input_dir=str(tmp_path / "input"),
            output_dir=str(tmp_path / "output"), **state_kw)
        client = TestClient(TestServer(build_app(state)))
        await client.start_server()
        try:
            return await fn(client, state)
        finally:
            await client.close()
    return asyncio.run(go())


async def post_and_wait(client, g):
    r = await client.post("/prompt", json={"prompt": g, "client_id": "t"})
    assert r.status == 200, await r.text()
    pid = (await r.json())["prompt_id"]
    for _ in range(2400):
        hist = await (await client.get("/history")).json()
        if pid in hist:
            return pid, hist[pid]
        await asyncio.sleep(0.05)
    raise AssertionError("the prompt never reached /history")


def test_post_prompt_to_history_to_png_with_its_stages_and_counters(
        tmp_path):
    async def body(client, state):
        await client.post("/distributed/metrics/reset", json={})
        pid, entry = await post_and_wait(client, graph(save="expand"))
        assert entry["status"] == "success", entry
        n = entry["images"]
        assert n >= 1 and entry["image_shapes"] == [[16, 16, 3]] * n
        pngs = sorted(os.listdir(tmp_path / "output"))
        assert len(pngs) == n and pngs[0].startswith("expand_")
        m = await (await client.get("/distributed/metrics")).json()
        stages, counters = m["pipeline"]["stages"], m["pipeline"]["counters"]
        assert stages["lm_generate"]["count"] == 1
        assert stages["detokenize"]["count"] == 1
        assert stages["device_wait"]["count"] >= 1
        assert counters["lm.tokens_decoded"] == 4
        assert counters["lm.layer_applications"] == 4 * 4 * 3   # x R x L
        assert 3 < counters["lm.prompt_tokens"] <= 32
        assert m["pipeline"]["gauges"]["lm.kv_cache_bytes"] == \
            2 * 12 * 36 * 4 * 16 * 4        # k+v, slots, positions, H, D, f32
        assert m["attention_paths"]["xla_causal"] >= 4      # one a loop
        assert m["attention_paths"]["xla_decode"] >= 4
        assert "LanguageModelGenerate" in m["nodes"]
        # the wait for the ids lies inside the generate stage's span, and
        # the instant is on the request's root span
        tr = await (await client.get(f"/distributed/trace/{pid}")).json()
        spans = tr["spans"] if isinstance(tr, dict) and "spans" in tr else tr
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        gen = by_name["lm_generate"][0]
        assert any(w["parent_id"] == gen["span_id"]
                   for w in by_name["device_wait"])
        root = [s for s in spans if not s.get("parent_id")][0]
        assert "lm_ids_ready" in root["attrs"]["instants"]
        # a second, identical request: nothing compiles
        before = m["retraces"]["compiles"]
        _, entry = await post_and_wait(client, graph(save="expand"))
        assert entry["status"] == "success"
        m = await (await client.get("/distributed/metrics")).json()
        assert m["retraces"]["compiles"] == before
    run_with_client(body, tmp_path)


def test_kernel_classes_and_path_names_are_the_issues():
    classes = {row[0] for row in trace.KERNEL_CLASSES}
    assert {"lm_attn", "lm_proj", "lm_mlp", "lm_norm", "lm_cache",
            "lm_head"} <= classes


# --- a mesh, and a graph without the new nodes --------------------------------

_PROBE = """
import json, os, sys, tempfile
from comfyui_distributed_tpu.ops.base import OpContext
from comfyui_distributed_tpu.parallel.mesh import get_runtime
from comfyui_distributed_tpu.workflow.executor import WorkflowExecutor
from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.utils import trace
g = json.load(open(sys.argv[1])); g.pop("__doc__")
g["5"]["inputs"].update(width=64, height=64); g["3"]["inputs"]["steps"] = 2
if "21" in g:
    g["21"]["inputs"].update(max_new_tokens=4, prompt_tokens=32)
    g["21"]["inputs"].update(json.loads(os.environ.get("PROBE_GENERATE",
                                                       "{}")))
    g["20"]["inputs"].update(json.loads(os.environ.get("PROBE_LOADER",
                                                       "{}")))
rt = get_runtime()
res = WorkflowExecutor(OpContext(runtime=rt,
                                 output_dir=tempfile.mkdtemp())).execute(g)
print(json.dumps({
    "axes": {k: int(v) for k, v in rt.mesh.shape.items()},
    "images": len(res.images),
    "text": res.outputs["21"][0] if "21" in res.outputs else None,
    "looplm_imported": "comfyui_distributed_tpu.models.looplm" in sys.modules,
    "lm_resident": [k for k in registry._pipeline_cache
                    if k.startswith("lm:")],
    "paths": trace.ATTENTION_PATHS.snapshot(),
    "stages": sorted(trace.GLOBAL_STAGES.snapshot()),
    "counters": trace.GLOBAL_COUNTERS.snapshot()}))
"""


def probe(workflow, tmp_path, devices, **env):
    r = subprocess.run(
        [sys.executable, "-c", _PROBE,
         os.path.join(REPO, "workflows", workflow)],
        capture_output=True, text=True, timeout=600, cwd=str(tmp_path),
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
             "DTPU_DEFAULT_FAMILY": "tiny",
             "DISTRIBUTED_TPU_CONFIG": str(tmp_path / "cfg.json"),
             "XLA_FLAGS": "--xla_force_host_platform_device_count="
                          f"{devices}", **env})
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def one_device_text(tmp_path_factory):
    return probe("prompt-expand-txt2img.json",
                 tmp_path_factory.mktemp("one"), 1,
                 DTPU_MESH_SHAPE="data=1")["text"]


@pytest.mark.parametrize("mesh, images", [("data=4", 4),
                                          ("data=2,tensor=2", 2)])
def test_on_a_mesh_of_four_the_graph_still_runs(mesh, images, tmp_path,
                                                one_device_text):
    """The weights replicated over ``data`` and column-split over
    ``tensor`` by the shape rule: the same expansion as on one device,
    an image a ``data`` replica."""
    got = probe("prompt-expand-txt2img.json", tmp_path, 4,
                DTPU_MESH_SHAPE=mesh, DTPU_TP_MIN_SHARD_ELEMENTS="64")
    assert {k: v for k, v in got["axes"].items() if v > 1} == dict(
        (kv.split("=")[0], int(kv.split("=")[1])) for kv in mesh.split(","))
    assert got["images"] == images and got["text"] == one_device_text
    assert got["lm_resident"] and got["looplm_imported"]


def test_a_graph_without_the_new_nodes_touches_nothing_of_the_model(
        tmp_path):
    got = probe("distributed-txt2img.json", tmp_path, 1)
    assert got["images"] == 1
    assert got["looplm_imported"] is False and got["lm_resident"] == []
    assert not {"xla_causal", "xla_decode"} & set(got["paths"])
    assert not {"lm_generate", "detokenize"} & set(got["stages"])
    assert not [c for c in got["counters"] if c.startswith("lm.")]


def test_ids_outside_the_vocabulary_are_refused_on_the_host(tmp_path):
    """The device would clamp them in silence."""
    model = registry.load_language_model("tiny-refusals")

    class Wide(HashLMTokenizer):
        def encode(self, text):
            return [self.bos_id, model.cfg.vocab_size]

    real, model.tokenizer = model.tokenizer, Wide(model.cfg.vocab_size)
    try:
        with pytest.raises(ValueError, match="vocabulary of 512"):
            model.generate("a cat", max_new_tokens=2, prompt_tokens=8)
    finally:
        model.tokenizer = real
    with pytest.raises(ValueError, match="0 new tokens"):
        model.generate("a cat", max_new_tokens=0, prompt_tokens=8)
    with pytest.raises(ValueError, match="a prompt of 0 ids"):
        model.generate("a cat", max_new_tokens=2, prompt_tokens=0)


@pytest.mark.parametrize("mesh, devices, images", [
    ("data=1", 1, 1), ("data=2,tensor=2", 4, 2)])
def test_the_long_shot_graph_runs_with_the_fourth_family(mesh, devices,
                                                         images, tmp_path):
    """``workflows/prompt-expand-longshot-txt2img.json`` (PR 40): a model
    of the family with a recurrent state beside a key-value cache behind
    the same nodes, on one device and under a mesh (weights replicated
    over ``data``, column-split over ``tensor``): the same expansion, no
    family-specific line in the nodes or the executor."""
    got = probe("prompt-expand-longshot-txt2img.json", tmp_path, devices,
                DTPU_MESH_SHAPE=mesh, DTPU_TP_MIN_SHARD_ELEMENTS="64")
    assert got["images"] == images
    assert got["lm_resident"] == ["lm:granite-4.0-h-micro.safetensors:"]
    assert got["text"].startswith("a lighthouse on a cliff at dawn, ")
    assert len(got["text"].split()) == 7 + 4
    # a call site a run of attention layers, in the programs of both row
    # counts: two runs, prefill and decode
    assert {k: got["paths"][k] for k in ("xla_causal", "xla_decode")} == {
        "xla_causal": 4, "xla_decode": 4}
    # 32 prompt positions in 4 chunks of 8 and 4 steps, 4 Mamba blocks;
    # 32 prompt ids, all real (the instructions fill the buffer), and
    # what was decoded, over 2 attention blocks
    counters = got["counters"]
    assert counters["lm.prefill_positions"] == 32
    assert counters["lm.scan_chunks"] == 4 * 4
    assert counters["lm.state_steps"] == 4 * 4
    assert counters["lm.keys_attended_full"] == 2 * (33 + 34 + 35 + 36)


@pytest.mark.parametrize("mesh, devices, images", [
    ("data=1", 1, 1), ("data=2,tensor=2", 4, 2)])
def test_the_system_prompt_graph_runs_with_the_fifth_family(mesh, devices,
                                                            images, tmp_path):
    """``workflows/prompt-expand-sysprompt-txt2img.json`` (PR 42): a model
    of the family whose queries select their keys (an index-key cache
    beside the key-value cache, a top-k and a gather in every decode step)
    behind the same nodes, on one device and under a mesh: the same
    expansion, no family-specific line in the nodes or the executor."""
    got = probe("prompt-expand-sysprompt-txt2img.json", tmp_path, devices,
                DTPU_MESH_SHAPE=mesh, DTPU_TP_MIN_SHARD_ELEMENTS="64")
    assert got["images"] == images
    assert got["lm_resident"] == ["lm:keye-vl-2.0-30b-a3b.safetensors:"]
    assert got["text"].startswith("a lighthouse on a cliff at dawn, ")
    assert len(got["text"].split()) == 7 + 4
    # in the programs of both row counts: a prefill of 32 positions in
    # chunks of 4, the first two within topk = 8 and six selecting; a
    # decode step gathers
    assert {k: got["paths"][k] for k in (
        "xla_causal", "xla_selected", "xla_gathered")} == {
        "xla_causal": 4, "xla_selected": 12, "xla_gathered": 2}
    # 32 prompt ids, all real (the instructions fill the buffer); 4 steps
    # over 3 blocks: every cached index key scored, 8 keys attended to
    counters = got["counters"]
    assert counters["lm.prefill_positions"] == 32
    assert counters["lm.keys_scored_decode"] == 3 * (33 + 34 + 35 + 36)
    assert counters["lm.keys_attended"] == counters["lm.keys_selected"] \
        == 4 * 3 * 8
    assert counters["lm.expert_pairs_local"] == counters["lm.expert_pairs"] \
        == 4 * 3 * 2
    assert counters["lm.expert_pairs_dropped"] == 0


@pytest.mark.parametrize("mesh, devices, images", [
    ("data=1", 1, 1), ("data=2,tensor=2", 4, 2)])
def test_the_system_prompt_graph_runs_with_the_sixth_family(mesh, devices,
                                                            images, tmp_path):
    """The same workflow with the loader's ``model_name`` changed (PR 46):
    a model of the decoder-hybrid-decoder family (states and rings in
    front, ONE cache and ONE memory shared by the layers behind; its
    prefill runs the back half for the last position only) behind the
    same nodes, on one device and under a mesh: the same expansion, no
    family-specific line in the nodes or the executor."""
    name = "phi-4-mini-flash-reasoning.safetensors"
    got = probe("prompt-expand-sysprompt-txt2img.json", tmp_path, devices,
                DTPU_MESH_SHAPE=mesh, DTPU_TP_MIN_SHARD_ELEMENTS="64",
                PROBE_LOADER=json.dumps({"model_name": name}))
    assert got["images"] == images
    assert got["lm_resident"] == [f"lm:{name}:"]
    assert got["text"].startswith("a lighthouse on a cliff at dawn, ")
    assert len(got["text"].split()) == 7 + 4
    # in the programs of both row counts (a call site a scan body): the
    # window layers' chunk of the prefill (banded) and their ring in a
    # decode step; the full layer's and the cross layers' one query over
    # the cache, in the prefill and in a decode step; no causal square
    assert {k: got["paths"].get(k, 0) for k in (
        "xla_banded", "xla_ring", "xla_decode", "xla_causal")} == {
        "xla_banded": 2, "xla_ring": 2, "xla_decode": 8, "xla_causal": 0}
    # 32 prompt ids, all real, walked as 36 positions in 6 chunks of 6;
    # the back half on ONE position; 4 steps, 3 Mamba layers, a window of
    # 8 in two layers, one cache read by two
    counters = got["counters"]
    assert counters["lm.prefill_positions"] == 36
    assert counters["lm.cross_positions"] == 1
    assert counters["lm.scan_chunks"] == 3 * 6
    assert counters["lm.state_steps"] == 3 * 4
    assert counters["lm.keys_attended_ring"] == 2 * 4 * 8
    assert counters["lm.keys_attended_full"] == 2 * (33 + 34 + 35 + 36)


@pytest.fixture(scope="module")
def whole_prompt_text(tmp_path_factory):
    """The long-shot graph with no instructions: the whole prompt buffer
    scanned, on one device."""
    return probe("prompt-expand-longshot-txt2img.json",
                 tmp_path_factory.mktemp("whole"), 1, DTPU_MESH_SHAPE="data=1",
                 PROBE_GENERATE=json.dumps(
                     {"prompt_tokens": 48, "instructions": ""}))


@pytest.mark.parametrize("mesh, devices, images", [
    ("data=1", 1, 1), ("data=2,tensor=2", 4, 2)])
def test_behind_instructions_that_fit_the_fourth_family_starts_from_a_snapshot(
        mesh, devices, images, tmp_path, whole_prompt_text):
    """The same graph with instructions that leave room for the user's
    words (14 ids of 48): the request makes the snapshot and is served
    through it, on one device and under a mesh (the snapshot laid out as
    the maker's program leaves it, the served program compiled for
    that): 34 positions computed, 14 served."""
    guide = "example prompt a red fox detailed prompt a red fox on fresh snow"
    got = probe("prompt-expand-longshot-txt2img.json", tmp_path, devices,
                DTPU_MESH_SHAPE=mesh, DTPU_TP_MIN_SHARD_ELEMENTS="64",
                PROBE_GENERATE=json.dumps(
                    {"prompt_tokens": 48, "instructions": guide}))
    assert got["images"] == images
    assert len(got["text"].split()) == 7 + 4
    counters = got["counters"]
    assert counters["lm.prefill_positions"] == 48 - 14
    assert counters["lm.scan_chunks"] == 4 * 5
    assert (counters["lm.prefix_misses"], counters["lm.prefix_hits"],
            counters["lm.prefix_positions_served"]) == (1, 1, 14)
    assert "lm_prefix_state" in got["stages"]
    # without instructions the whole prompt is scanned, and counted
    assert whole_prompt_text["counters"]["lm.prefill_positions"] == 48
    assert "lm.prefix_hits" not in whole_prompt_text["counters"]
    assert "lm_prefix_state" not in whole_prompt_text["stages"]


@pytest.mark.parametrize("mesh, devices, images", [
    ("data=1", 1, 1), ("data=2,tensor=2", 4, 2)])
def test_the_few_shot_graph_runs_with_the_third_family(mesh, devices, images,
                                                       tmp_path):
    """``workflows/prompt-expand-fewshot-txt2img.json`` (PR 34): the
    operator's instructions on the generate node and a model of the
    family with a ring beside a full cache, on one device and under a
    mesh (weights replicated over ``data``, column-split over
    ``tensor``): the same expansion, the ring full in every step."""
    got = probe("prompt-expand-fewshot-txt2img.json", tmp_path, devices,
                DTPU_MESH_SHAPE=mesh, DTPU_TP_MIN_SHARD_ELEMENTS="64")
    assert got["images"] == images
    assert got["lm_resident"] == ["lm:k-exaone-236b-a23b.safetensors:"]
    assert got["text"] == "a lighthouse on a cliff at dawn, bavi dase " \
                          "data data"
    # a call site a run of layers of one kind, in the programs of both
    # row counts: three sliding runs and a full one, prefill and decode
    assert {k: got["paths"][k] for k in ("xla_banded", "xla_ring",
                                         "xla_causal", "xla_decode")} == {
        "xla_banded": 6, "xla_ring": 6, "xla_causal": 2, "xla_decode": 2}
    # 4 steps x 4 sliding layers x a window of 8; 32 prompt ids, all
    # real (the instructions fill the buffer), and what was decoded
    assert got["counters"]["lm.keys_attended_window"] == 4 * 4 * 8
    assert got["counters"]["lm.keys_attended_full"] == 33 + 34 + 35 + 36
