"""Kernel names the programs already carry, and one clock (ISSUE 24): the
classes of ``trace.KERNEL_CLASSES`` over the op_name paths of the lowered
programs (this is what breaks when someone renames a module), host spans
on the profiler's clock, the counters that tell a compile from a load,
the program's own trace summary, and the executor's self times."""

import collections
import json
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.server.app import ServerState
from comfyui_distributed_tpu.utils import trace
from comfyui_distributed_tpu.utils import trace_summary as ts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "tests", "data")
OP_NAME = re.compile(r'op_name="([^"]+)"')

DENOISE_CLASSES = {"attn_self", "attn_cross", "attn_proj", "ff", "norm",
                   "resblock", "resample", "embed", "sampler"}
VAE_CLASSES = {"vae_res", "vae_conv", "norm"}      # the tiny VAE has no
CLIP_CLASSES = {"clip_attn", "clip_mlp", "norm", "embed"}   # mid_attn
# the looped language model's (PR 26; tests/test_looplm.py holds its rows)
LM_CLASSES = {"lm_attn", "lm_proj", "lm_mlp", "lm_norm", "lm_cache",
              "lm_head", "embed"}
# the expert model's (PR 32; tests/test_mla_moe.py holds its rows): the
# same classes and one for everything routing adds
MOE_CLASSES = LM_CLASSES | {"lm_experts"}
# the state-space hybrid's (PR 40; tests/test_ssm_hybrid.py holds its
# rows): the same classes and two for the Mamba mixer's own work and its
# recurrent state
SSM_CLASSES = LM_CLASSES | {"lm_ssm", "lm_state"}
# the decoder with a learned key selection (PR 42): what the selection
# adds to an attention
DSA_CLASSES = MOE_CLASSES | {"lm_index"}
# the decoder-hybrid-decoder's (PR 46; tests/test_sambay.py holds its
# rows): the state-space hybrid's and two for the layers that own no
# state: a gate with another layer's memory, an attention over another
# layer's cache
SAMBAY_CLASSES = SSM_CLASSES | {"lm_gmu", "lm_cross"}
# the decoder with a shortcut-connected expert layer and zero-compute
# experts (PR 49; its rows are below): the expert model's and one for the
# identity experts' scaled add
SCMOE_CLASSES = MOE_CLASSES | {"lm_zero"}


@pytest.fixture(scope="module", params=["tiny", "tiny_sdxl"])
def pipe(request):
    return registry.load_pipeline(f"names-{request.param}.safetensors",
                                  family_name=request.param)


def latent(pipe):
    return jnp.zeros((1, 8, 8, pipe.family.latent_channels), jnp.float32)


def compiled_op_names(fn):
    return OP_NAME.findall(jax.jit(fn).lower().compile().as_text())


# --- 1. kernel classes from the names the programs carry -------------------

def test_the_vocabulary_is_the_issues_and_classify_needs_no_jax():
    classes = {row[0] for row in trace.KERNEL_CLASSES} | {trace.SAMPLER}
    assert classes == DENOISE_CLASSES | VAE_CLASSES | CLIP_CLASSES \
        | LM_CLASSES | MOE_CLASSES | SSM_CLASSES | DSA_CLASSES \
        | SAMBAY_CLASSES | SCMOE_CLASSES | {"vae_attn"}
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; from comfyui_distributed_tpu.utils.trace import "
         "classify; print(classify('jit(core)/mul'), 'jax' in sys.modules)"],
        capture_output=True, text=True, timeout=60,
        env={**os.environ, "PYTHONPATH": REPO})
    assert r.stdout.split() == ["sampler", "False"], r.stderr[-1000:]


UNET = "jit(core)/while/body/closed_call/UNet/"


@pytest.mark.parametrize("path, want", [
    # the innermost module segment decides
    (UNET + "down_2_attn_0/blocks_0/attn1/to_q/dot_general", "attn_proj"),
    (UNET + "down_2_attn_0/blocks_9/attn1/bnhd,bmhd->bhnm/dot_general",
     "attn_self"),
    (UNET + "mid_attn/blocks_0/attn2/bhnm,bmhd->bnhd/dot_general:",
     "attn_cross"),
    (UNET + "up_1_attn_2/blocks_0/attn2/to_out/add", "attn_proj"),
    (UNET + "up_1_attn_2/blocks_0/ff/geglu/proj/dot_general", "ff"),
    (UNET + "up_1_attn_2/blocks_0/ff/out/dot_general", "ff"),
    # the fused GEGLU kernel (PR 39) is called inside the module's scope
    # (behind a conditional), alone and under the fan-out program's
    # shard_map: the reader of ``proj_ff_device_s_per_image`` and the
    # account's ``ff`` own it
    (UNET + "up_1_attn_2/blocks_0/ff/geglu/cond/branch_1_fun/"
     "jit(_fused_geglu)/geglu/pallas_call", "ff"),
    (UNET + "down_1_attn_0/blocks_1/ff/geglu/cond/branch_1_fun/shard_map/"
     "jit(_fused_geglu)/geglu/pallas_call", "ff"),
    (UNET + "up_1_attn_2/blocks_0/ff/geglu/cond", "ff"),
    (UNET + "up_1_attn_2/blocks_0/ff/geglu/convert_element_type", "ff"),
    (UNET + "up_1_attn_2/blocks_0/norm3/mul", "norm"),
    (UNET + "up_1_attn_2/norm/GroupNorm_0/reduce_sum", "norm"),
    (UNET + "up_1_attn_2/proj_in/dot_general", "attn_proj"),
    (UNET + "up_1_attn_2/blocks_0/add", "attn_proj"),
    (UNET + "down_0_res_1/in_conv/conv_general_dilated", "resblock"),
    (UNET + "down_0_res_1/emb_proj/dot_general", "resblock"),
    (UNET + "down_0_res_1/out_norm/GroupNorm_0/rsqrt", "norm"),
    (UNET + "mid_res_0/jit(silu)/logistic", "resblock"),
    (UNET + "down_1_ds/conv/conv_general_dilated", "resample"),
    (UNET + "up_2_us/jit(_resize)/dot_general", "resample"),
    (UNET + "conv_in/conv_general_dilated", "resample"),
    (UNET + "concatenate", "resample"),
    (UNET + "time_fc1/dot_general", "embed"),
    (UNET + "label_fc2/add", "embed"),
    # a fusion CPU XLA names without the program prefix
    ("UNet/down_1_res_0/in_norm/GroupNorm_0/add", "norm"),
    # the denoise program's own operations
    ("jit(core)/while/body/closed_call/mul", "sampler"),
    ("jit(core)/vmap(jit(_normal))/jit(_normal_real)/erf_inv", "sampler"),
    ("jit(step)/sub", "sampler"),
    ("jit(<lambda>)/jit(<lambda>)/VAE.decode/decoder/mid_attn/q/dot_general",
     "vae_attn"),
    ("jit(<lambda>)/jit(<lambda>)/VAE.decode/decoder/mid_attn/norm/"
     "GroupNorm_0/sub", "norm"),
    ("jit(<lambda>)/jit(<lambda>)/VAE.decode/decoder/up_3_res_2/conv1/"
     "conv_general_dilated", "vae_res"),
    ("jit(<lambda>)/jit(<lambda>)/VAE.decode/decoder/up_2_us/"
     "conv_general_dilated", "vae_conv"),
    ("jit(<lambda>)/jit(<lambda>)/VAE.decode/post_quant_conv/add",
     "vae_conv"),
    ("jit(<lambda>)/VAE.encode/encoder/conv_in/add", "vae_conv"),
    ("jit(<unknown>)/CLIPTextModel/layers_3/q/dot_general", "clip_attn"),
    ("jit(<unknown>)/CLIPTextModel/layers_3/bhnm,bmhd->bnhd/dot_general",
     "clip_attn"),
    ("jit(<unknown>)/CLIPTextModel/layers_3/fc2/dot_general", "clip_mlp"),
    ("jit(<unknown>)/CLIPTextModel/layers_3/ln1/mul", "norm"),
    ("jit(<unknown>)/CLIPTextModel/token_embedding/gather", "embed"),
    # a Pallas kernel's custom call carries the scope it was called
    # under: the few-row kernel's (PR 33) is the projection's or the MLP's
    ("jit(lm_generate)/LoopLM/while/body/closed_call/layers/while/body/"
     "closed_call/self_attn/q_proj/fewrow_dense_q_proj_k_proj_v_proj/"
     "pallas_call", "lm_proj"),
    ("jit(lm_generate)/PanguUltraMoE/while/body/closed_call/moe_layers/"
     "while/body/closed_call/mlp/shared_experts/down_proj/fewrow_dense/"
     "pallas_call", "lm_mlp"),
    # an operation directly under a phase scope (PR 38: every language
    # model has them) is the program's glue, as one directly under the
    # model's scope is
    ("jit(lm_generate)/LoopLM/prefill/dynamic_slice", "lm_proj"),
    ("jit(lm_generate)/LoopLM/decode/while", "lm_proj"),
    ("jit(lm_generate)/PanguUltraMoE/prefill/jit(_roll_static)/concatenate",
     "lm_proj"),
    ("jit(lm_generate)/PanguUltraMoE/decode/while/body/closed_call/add",
     "lm_proj"),
    ("jit(lm_generate)/ExaoneMoe/prefill/dynamic_slice", "lm_proj"),
    # and the innermost module still decides under one
    ("jit(lm_generate)/LoopLM/decode/while/body/closed_call/layers/while/"
     "body/closed_call/mlp/down_proj/fewrow_dense/pallas_call", "lm_mlp"),
    ("jit(lm_generate)/PanguUltraMoE/prefill/moe_layers/while/body/mlp/"
     "experts/while/body/cond/branch_1_fun/dot_general", "lm_experts"),
    # nothing of ours
    ("jit(<lambda>)/jit(<lambda>)/mul", "other"),
    ("reduce_sum", "other"), ("", "other"),
])
def test_classify(path, want):
    assert trace.classify(path) == want


def test_every_op_of_the_compiled_denoise_falls_in_one_class(pipe):
    lat = latent(pipe)
    ctx, _ = pipe.encode_prompt(["a cat"])
    names = compiled_op_names(lambda: pipe.sample(
        lat, ctx, ctx, np.zeros((1,), np.uint64), steps=2, cfg=7.0,
        sampler_name="euler", scheduler="karras"))
    assert len(names) > 3000
    by_class = collections.defaultdict(list)
    for n in names:
        by_class[trace.classify(n)].append(n)
    assert set(by_class) - {"other"} == DENOISE_CLASSES
    # the listed remainder: a reduction's combiner, which XLA names by
    # its primitive alone, and what this test's own jit wraps around
    # ``core`` (the sampling keys)
    for n in by_class["other"]:
        assert "/" not in n or ("jit(core)" not in n and "UNet" not in n), n
    # attention proper holds both einsums and the softmax, and nothing
    # of the projections
    attn = {n.rsplit("/", 1)[-1] for n in by_class["attn_self"]}
    assert {"dot_general", "exp", "reduce_max"} <= attn
    assert not any(re.search(r"/to_(q|k|v|out)/", n)
                   for n in by_class["attn_self"] + by_class["attn_cross"])


def test_the_fused_geglus_own_operations_are_class_ff(monkeypatch):
    """A transformer block traced as a TPU would trace it (the kernel
    behind the interpreter): the call sits inside ``GEGLU``'s scope,
    behind the conditional, so every operation it lowers to carries
    ``.../ff/geglu/...`` and, under the UNet's scope, is class ``ff``;
    ``ff/out`` is a plain Dense beside it."""
    import functools
    from comfyui_distributed_tpu.models import layers
    from comfyui_distributed_tpu.ops.pallas import geglu
    monkeypatch.setattr(geglu, "geglu", functools.partial(
        geglu.geglu, interpret=True))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    block = layers.TransformerBlock(num_heads=2, name="blocks_0")
    x, ctx = jnp.zeros((2, 64, 128)), jnp.zeros((2, 8, 64))
    params = block.init(jax.random.PRNGKey(0), x, ctx)
    names = OP_NAME.findall(
        jax.jit(block.apply).lower(params, x, ctx).compile().as_text())
    ff = [n for n in names if "/ff/" in n]
    fused = [n for n in ff
             if "/ff/geglu/cond/branch_1_fun/jit(_fused_geglu)/" in n]
    assert fused and any("/ff/out/dot_general" in n for n in ff)
    assert not any("/ff/geglu/proj/" in n for n in ff)
    assert {trace.classify(n.replace("jit(apply)/", UNET + "up_1_attn_2/"))
            for n in ff} == {"ff"}


@pytest.mark.parametrize("program, want", [("vae", VAE_CLASSES),
                                           ("clip", CLIP_CLASSES)])
def test_every_op_of_the_compiled_vae_and_text_encoder(pipe, program, want):
    lat = latent(pipe)
    fn = {"vae": lambda: pipe.vae_decode(lat),
          "clip": lambda: pipe.encode_prompt(["a cat"])[0]}[program]
    by_class = collections.defaultdict(list)
    for n in compiled_op_names(fn):
        by_class[trace.classify(n)].append(n)
    assert set(by_class) - {"other"} == want
    # the remainder: combiners, and the programs' own operations around
    # the module (latent scaling, clipping, pooling)
    for n in by_class["other"]:
        assert "/" not in n or not re.search(r"VAE|CLIPTextModel", n), n


def test_no_module_of_the_models_is_named_like_another_models_class():
    """The table reads module names: the names it relies on are the ones
    the model files declare."""
    src = {f: open(os.path.join(REPO, "comfyui_distributed_tpu", "models",
                                f"{f}.py"), encoding="utf-8").read()
           for f in ("layers", "unet", "vae", "clip")}
    for name in ("attn1", "attn2", "to_q", "to_k", "to_v", "to_out",
                 "geglu", "ff", "proj_in", "proj_out", "in_conv",
                 "out_conv", "emb_proj"):
        assert f'name="{name}"' in src["layers"], name
    for name in ("time_fc1", "label_fc1", "conv_in", "conv_out", "mid_attn"):
        assert f'name="{name}"' in src["unet"], name
    for name in ("mid_attn", "post_quant_conv", "decoder", "conv_out"):
        assert f'name="{name}"' in src["vae"], name
    for name in ("fc1", "ln1", "ln_final", "token_embedding"):
        assert f'name="{name}"' in src["clip"], name


def test_the_denoise_program_is_enqueued_by_a_plain_call():
    """PR 23 was refused for this: a helper and a lambda between
    ``sample`` and ``core(...)`` made the program's first call seconds
    slower on the chip's host (PERF.md, PR 24).  The wait and the note
    stand beside the call."""
    import inspect
    src = inspect.getsource(registry.DiffusionPipeline.sample)
    call = src.index("out = core(self.unet_params")
    assert src.index("wait_previous_denoise()") < call \
        < src.index("note_denoise(out)")
    assert "lambda: core" not in src
    registry.note_denoise(jnp.ones((2,)))
    registry.wait_previous_denoise()        # a ready array: returns
    assert registry._last_denoise is not None
    registry.note_denoise(None)


# --- 2. host spans on the profiler's clock ---------------------------------

def host_annotations(trace_dir):
    from jax.profiler import ProfileData
    found = [os.path.join(b, f) for b, _, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    assert len(found) == 1
    out = {}
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name != ts.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(trace.HOST_PREFIX):
                    out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


def test_stage_is_an_annotation_only_while_a_device_trace_runs(
        tmp_path, monkeypatch):
    opened = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **kw):
        opened.append(name)
        return real(name, **kw)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    with trace.stage("before_the_trace"):
        pass
    assert opened == []
    trace.start_device_trace(str(tmp_path / "t"))
    try:
        root = trace.start_span("job", attrs={"prompt_id": "p-7"})
        with trace.use_span(root):
            with trace.stage("encode"):
                time.sleep(0.002)
            with trace.span("KSampler", node="3"):
                with trace.device_wait():
                    time.sleep(0.002)
            trace.event_span("queue_wait", time.time() - 1.0, time.time(),
                             parent=root)
    finally:
        trace.stop_device_trace()
    found = host_annotations(str(tmp_path / "t"))
    assert {"dtpu/encode", "dtpu/KSampler", "dtpu/device_wait",
            "dtpu/queue_wait"} <= set(found)
    assert "dtpu/before_the_trace" not in found
    stats = found["dtpu/encode"][0]
    assert stats["prompt_id"] == "p-7" and stats["trace_id"] == root.trace_id
    opened.clear()
    with trace.stage("after_the_trace"):
        pass
    assert opened == []
    # the program's own summary of that trace: no device plane on the CPU
    summary = trace.profile_summary()
    assert summary["dir"] == str(tmp_path / "t") and summary["chips"] == []
    assert {"encode", "KSampler", "device_wait"} <= set(
        summary["host_spans"])
    with open(tmp_path / "t" / "summary.json") as f:
        assert json.load(f)["host_spans"] == summary["host_spans"]


def test_own_stage_leaves_out_the_threads_device_waits():
    trace.GLOBAL_STAGES.reset()
    root = trace.start_span("job")
    with trace.use_span(root):
        with trace.stage("dispatch", own=True):
            time.sleep(0.01)
            with trace.device_wait():
                time.sleep(0.03)
            with trace.span("KSampler"):
                pass
    root.end()
    st = trace.GLOBAL_STAGES.snapshot()
    assert st["device_wait"]["total_s"] >= 0.03
    assert 0.009 <= st["dispatch"]["total_s"] < 0.03
    spans = {s["name"]: s for s in trace.GLOBAL_TRACES.export(root.trace_id)}
    # the stage's span lies beside what ran inside it
    assert spans["dispatch"]["parent_id"] == root.span_id
    assert spans["KSampler"]["parent_id"] == root.span_id
    assert spans["dispatch"]["duration_s"] >= 0.04
    assert spans["dispatch"]["attrs"]["device_wait_s"] >= 0.03


# --- 3. counters that tell a compile from a load ---------------------------

_COUNTER_PROBE = """
import json, jax, jax.numpy as jnp
from comfyui_distributed_tpu.runtime.manager import \\
    enable_persistent_compile_cache
from comfyui_distributed_tpu.utils import trace
print(enable_persistent_compile_cache(min_compile_secs=0.0))
trace.install_jax_monitoring()
jax.jit(lambda x: jnp.tanh(x @ x) + 1)(jnp.ones((64, 64))).block_until_ready()
print(json.dumps(trace.GLOBAL_RETRACES.mark()))
"""


def test_retrace_seconds_and_uncached_compiles_cold_then_warm(tmp_path):
    def run():
        r = subprocess.run(
            [sys.executable, "-c", _COUNTER_PROBE], capture_output=True,
            text=True, timeout=120, cwd=str(tmp_path),
            env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                 "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
        assert r.returncode == 0, r.stderr[-2000:]
        lines = r.stdout.strip().splitlines()
        return lines[-2], json.loads(lines[-1])
    used, cold = run()
    _, warm = run()
    # the cache rule of PR 21 stands: the directory given from outside
    assert used == str(tmp_path / "cache")
    for m in (cold, warm):
        assert m["traces"] >= 1 and m["trace_s"] > 0 and m["lower_s"] > 0
        assert m["compiles_uncached"] == m["compiles"] - m["cache_loads"]
    assert cold["cache_loads"] == 0 and cold["cache_load_s"] == 0
    assert cold["compiles_uncached"] == cold["compiles"] >= 1
    assert cold["compile_s"] > 0
    assert warm["compiles"] == cold["compiles"]
    assert warm["cache_loads"] >= 1 and warm["cache_load_s"] > 0
    assert warm["compiles_uncached"] < cold["compiles_uncached"]


def test_a_jit_traced_inside_anothers_trace_counts_its_seconds_once():
    trace.install_jax_monitoring()

    @jax.jit
    def inner(x):
        time.sleep(0.3)
        return x + 1

    @jax.jit
    def outer(x):
        time.sleep(0.05)
        return inner(x) * 2

    mark = trace.GLOBAL_RETRACES.mark()
    outer(jnp.ones((3,))).block_until_ready()
    got = trace.GLOBAL_RETRACES.since(mark)
    assert got["traces"] >= 2 and isinstance(got["traces"], int)
    # summed as JAX reports them the two events are 0.3 + 0.35 s
    assert 0.35 <= got["trace_s"] < 0.5
    assert got["lower_s"] > 0 and got["compiles"] >= 1


def test_retrace_counters_add_up_over_threads_without_a_lock_per_event():
    import threading
    stats = trace.RetraceStats()
    traces = stats._FIELDS.index("traces")
    lower_s = stats._FIELDS.index("lower_s")

    def work():
        for _ in range(5000):
            stats.add(traces, lower_s, 0.001)
    threads = [threading.Thread(target=work) for _ in range(8)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    got = stats.mark()
    assert got["traces"] == 40000
    assert got["lower_s"] == pytest.approx(40.0)
    assert got["compiles_uncached"] == 0


# --- 4. the program reduces its own trace ----------------------------------

def made_up_events():
    k = 1000
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops",
             "names": ["while.1", "fusion.1", "fusion.2", "copy.3", "pad.0"],
             "paths": ["jit(core)/while",
                       UNET + "mid_attn/blocks_0/attn1/bnhd,bmhd->bhnm/"
                       "dot_general:",
                       UNET + "mid_res_0/in_norm/GroupNorm_0/reduce_sum:",
                       "", "jit(pad)/pad:"],
             "name_idx": [4, 0, 1, 2, 3, 1],
             "start_ns": [0, 100 * k, 110 * k, 130 * k, 160 * k, 400 * k],
             "dur_ns": [20 * k, 100 * k, 10 * k, 20 * k, 30 * k, 50 * k]},
            {"name": "XLA Modules",
             "names": ["jit_pad(1)", "jit_core(12)", "jit_core(7)"],
             "name_idx": [0, 1, 2],
             "start_ns": [0, 100 * k, 400 * k],
             "dur_ns": [20 * k, 100 * k, 50 * k]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "exec", "names": ["dtpu/dispatch", "dtpu/KSampler",
                                       "dtpu/exec_idle", "python frame"],
             "name_idx": [0, 1, 2, 3],
             "start_ns": [190 * k, 200 * k, 300 * k, 0],
             "dur_ns": [100 * k, 40 * k, 60 * k, 900 * k]}]}]}


def test_summary_of_a_made_up_trace():
    s = ts.summarize(made_up_events())
    assert s["names_found"] is True
    # executions that touch the slice's edges are cut, not counted
    assert set(s["programs"]) == {"jit_core"}
    core = s["programs"]["jit_core"]
    assert core["count"] == 1 and core["mean_s"] == pytest.approx(100e-6)
    # the while holds two operations and is no leaf; copy.3 has no path
    assert core["classes"] == {
        "attn_self": pytest.approx(10e-6), "norm": pytest.approx(20e-6),
        "other": pytest.approx(30e-6), "gaps": pytest.approx(40e-6)}
    assert sum(core["classes"].values()) == pytest.approx(core["mean_s"])
    assert core["top_other"] == [{"op": "copy.3", "s": pytest.approx(30e-6)}]
    assert s["busy_s"] == pytest.approx(130e-6)
    assert s["window_s"] == pytest.approx(450e-6)
    # the gaps inside the whole execution (100-110, 120-130, 150-160,
    # 190-200) are the program's; between executions 20-100 lies under
    # no span, and 200-400 is cut at the spans' edges: KSampler
    # (innermost) 200-240, dispatch 240-290, exec_idle 300-360, nothing
    # 290-300 and 360-400
    assert s["gaps_in_programs_s"] == pytest.approx(40e-6)
    assert s["idle"] == {
        "none": pytest.approx(130e-6), "KSampler": pytest.approx(40e-6),
        "dispatch": pytest.approx(50e-6),
        "exec_idle": pytest.approx(60e-6)}
    assert s["idle_under"]["dispatch"] == pytest.approx(90e-6)
    assert sum(s["idle"].values()) + s["gaps_in_programs_s"] == \
        pytest.approx(s["window_s"] - s["busy_s"])
    assert s["host_spans"] == ["KSampler", "dispatch", "exec_idle"]


def test_summary_without_names_says_so_and_counts_the_profilers_time():
    ev = made_up_events()
    del ev["planes"][0]["lines"][0]["paths"]
    s = ts.summarize(ev, traced_s=500e-6)
    assert s["names_found"] is False
    assert s["programs"]["jit_core"]["classes"]["other"] == \
        pytest.approx(60e-6)
    assert s["window_s"] == pytest.approx(500e-6)
    assert s["idle"]["slice_edge"] == pytest.approx(50e-6)
    assert ts.summarize({"planes": []})["chips"] == []


# --- 4b. one owner an instant: the account (PR 38) -------------------------

LM = "jit(lm_generate)/ExaoneMoe/"


def accounted_events():
    """One whole execution of a language model with phases, 100-300 us:
    two operations that overlap in part (fusion.1 110-150, fusion.2
    140-170) under ``prefill``; an idle stretch at the phase's edge
    (170-180); a ``while`` (180-280) that holds fusion.3 (180-220) and
    fusion.4 (230-280), which has the zero-duration custom call that marks
    a prefetch at its own start; a copy under no phase (285-290).  And one
    whole execution of a denoise, whose paths carry no phase."""
    k = 1000
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops",
         "names": ["pad.0", "fusion.1", "fusion.2", "while.1", "fusion.3",
                   "fusion.4", "custom-call.9", "copy.5", "fusion.6"],
         "paths": ["jit(pad)/pad:",
                   LM + "prefill/moe_layers/while/body/self_attn/q_proj/"
                   "dot_general:",
                   LM + "prefill/moe_layers/while/body/mlp/experts/"
                   "dot_general:",
                   LM + "decode/while",
                   LM + "decode/while/body/dense_layers/while/body/mlp/"
                   "gate_proj/dot_general:",
                   LM + "decode/while/body/moe_layers/while/body/self_attn/"
                   "bnhd,bmhd->bhnm/dot_general:",
                   "", "",
                   UNET + "mid_attn/blocks_0/attn1/bnhd,bmhd->bhnm/"
                   "dot_general:"],
         "name_idx": [0, 1, 2, 3, 4, 5, 6, 7, 8, 0],
         "start_ns": [0, 110 * k, 140 * k, 180 * k, 180 * k, 230 * k,
                      230 * k, 285 * k, 410 * k, 500 * k],
         "dur_ns": [20 * k, 40 * k, 30 * k, 100 * k, 40 * k, 50 * k, 0,
                    5 * k, 30 * k, 20 * k]},
        {"name": "XLA Modules",
         "names": ["jit_pad(1)", "jit_lm_generate(3)", "jit_core(7)",
                   "jit_pad(2)"],
         "name_idx": [0, 1, 2, 3],
         "start_ns": [0, 100 * k, 400 * k, 500 * k],
         "dur_ns": [20 * k, 200 * k, 50 * k, 20 * k]}]}]}


def us(x):
    return pytest.approx(x * 1e-6)


def test_every_nanosecond_of_an_execution_has_one_owner():
    s = ts.summarize(accounted_events())
    lm = s["programs"]["jit_lm_generate"]
    account = lm["account"]
    # the instant two operations share is the one's that began first; the
    # while is no operation, the marker owns nothing; fusion.4 counts
    assert account["by_class"] == {
        "lm_proj": us(40), "lm_experts": us(20), "lm_mlp": us(40),
        "lm_attn": us(50), "other": us(5), "idle": us(45)}
    assert sum(account["by_class"].values()) == pytest.approx(lm["mean_s"])
    # ``classes`` keeps its meaning: an operation inside which another
    # event begins is a container there, so fusion.1 (the overlap) and
    # fusion.4 (the marker) are left out and read as gaps
    assert lm["classes"] == {"lm_experts": us(30), "lm_mlp": us(40),
                             "other": us(5), "gaps": us(125)}
    assert lm["phases"] == {"prefill": us(30), "decode": us(40)}
    assert account["overlap_s"] == us(10) and account["dropped_s"] == us(90)
    assert lm["classes"]["gaps"] + account["overlap_s"] \
        - account["dropped_s"] == pytest.approx(account["by_class"]["idle"])
    # an idle stretch is the phase's whose operation ENDS it; the stretch
    # after the last operation is nobody's: each phase's rows add up to
    # its wall seconds (100-170, 170-280, 280-300), the phases to the whole
    assert account["by_phase"] == {
        "prefill": {"lm_proj": us(40), "lm_experts": us(20), "idle": us(10)},
        "decode": {"lm_mlp": us(40), "lm_attn": us(50), "idle": us(20)},
        "none": {"other": us(5), "idle": us(15)}}
    wall = {p: sum(r.values()) for p, r in account["by_phase"].items()}
    assert wall == {"prefill": us(70), "decode": us(110), "none": us(20)}
    assert sum(wall.values()) == pytest.approx(lm["mean_s"])
    # the stretches by the operations on either side, the costliest first
    # (a tie in the order they began)
    paths = accounted_events()["planes"][0]["lines"][0]["paths"]
    assert account["top_idle"] == [
        {"s": us(10), "n": 1.0, "before": ts.STARTS, "after": paths[1]},
        {"s": us(10), "n": 1.0, "before": paths[2], "after": paths[4]},
        {"s": us(10), "n": 1.0, "before": paths[4], "after": paths[5]},
        {"s": us(10), "n": 1.0, "before": "copy.5", "after": ts.ENDS},
        {"s": us(5), "n": 1.0, "before": paths[5], "after": "copy.5"}]
    # a program whose paths carry no phase has none in its account either
    core = s["programs"]["jit_core"]
    assert "phases" not in core and "by_phase" not in core["account"]
    assert core["account"]["by_class"] == {"attn_self": us(30),
                                           "idle": us(20)}
    assert core["account"]["overlap_s"] == core["account"]["dropped_s"] == 0
    assert [(r["before"], r["after"]) for r in core["account"]["top_idle"]] \
        == [(ts.STARTS, paths[8]), (paths[8], ts.ENDS)]


def test_the_account_is_a_mean_over_the_chips_that_saw_the_program_whole():
    ev = accounted_events()
    second = json.loads(json.dumps(ev["planes"][0]))
    second["name"] = "/device:TPU:1"
    ops = second["lines"][0]
    ops["dur_ns"][2] = 50_000           # fusion.2 to 190: no idle at 170-180
    ev["planes"].append(second)
    s = ts.summarize(ev)
    lm = s["programs"]["jit_lm_generate"]
    one, two = (c["programs"]["jit_lm_generate"]["account"]
                for c in s["chips"])
    assert two["by_class"]["lm_experts"] == us(40)      # 150-190
    # fusion.3 began at 180, inside fusion.2: it owns from 190 on
    assert two["by_class"]["lm_mlp"] == us(30)
    assert two["by_phase"]["decode"]["idle"] == us(10)
    for key in ("lm_experts", "lm_mlp", "idle"):
        assert lm["account"]["by_class"][key] == pytest.approx(
            (one["by_class"][key] + two["by_class"][key]) / 2)
    assert lm["account"]["by_phase"]["decode"]["idle"] == us(15)
    assert lm["account"]["overlap_s"] == us((10 + 20) / 2)
    assert sum(lm["account"]["by_class"].values()) \
        == pytest.approx(lm["mean_s"])
    # a chip that did not see the program whole leaves no row at all
    ev["planes"][1]["lines"][1]["start_ns"][1] = 5_000
    ev["planes"][1]["lines"][1]["dur_ns"][1] = 295_000
    assert "jit_lm_generate" not in ts.summarize(ev)["programs"]


def test_an_execution_that_holds_no_operation_is_idle_from_end_to_end():
    ev = accounted_events()
    ops = ev["planes"][0]["lines"][0]
    for key in ("name_idx", "start_ns", "dur_ns"):
        del ops[key][8]                 # fusion.6, the denoise's one
    core = ts.summarize(ev)["programs"]["jit_core"]
    assert core["account"]["by_class"] == {"idle": us(50)}
    assert core["account"]["top_idle"] == [
        {"s": us(50), "n": 1.0, "before": ts.STARTS, "after": ts.ENDS}]


def cycles_events():
    """Three requests on chip 0, each a text encoder, the sampler's inputs,
    the denoise and a decode (the first request's encoder is cut by the
    slice's start, the last one's decode by its end); chip 1 sees that
    first encoder, then the denoise and the decode of each, as a fan-out's
    other chips do."""
    k = 1000

    def plane(chip, names, starts, durs):
        uniq = sorted(set(names))
        return {"name": f"/device:TPU:{chip}", "lines": [
            {"name": "XLA Ops", "names": ["fusion.1"], "paths": [""],
             "name_idx": [0] * len(starts), "start_ns": starts,
             "dur_ns": durs},
            {"name": "XLA Modules", "names": [f"{n}({i})"
                                              for i, n in enumerate(uniq)],
             "name_idx": [uniq.index(n) for n in names],
             "start_ns": starts, "dur_ns": durs}]}

    request = ["jit__unknown", "jit_sampler_inputs", "jit_core",
               "jit__lambda"]
    starts = [r * 100 * k + at * k for r in range(3)
              for at in (0, 10, 20, 70)]
    durs = [5 * k, 2 * k, 45 * k, 10 * k] * 3
    other = [(n, s, d) for n, s, d in zip(request * 3, starts, durs)
             if n in ("jit_core", "jit__lambda") or s == 0]
    return {"planes": [
        plane(0, request * 3, starts, durs),
        plane(1, [n for n, _, _ in other], [s for _, s, _ in other],
              [d for _, _, d in other])]}


def test_programs_per_denoise_counts_whole_cycles_on_every_chip():
    """From the first whole denoise's start to the last one's: 4 + 4
    programs in 2 cycles on chip 0, 2 + 2 in 2 on chip 1; the slice's
    edges (an encoder before the first denoise, a decode behind the last)
    count in no cycle.  The summary's other fields are what they were."""
    ev = cycles_events()
    s = ts.summarize(ev)
    assert [(c["denoise_cycles"], c["programs_in_cycles"])
            for c in s["chips"]] == [(2, 8), (2, 4)]
    assert s["programs_per_denoise"] == 3.0
    assert s["programs"]["jit_core"]["count"] == 3.0
    # one chip alone: a request's four programs
    assert ts.summarize({"planes": ev["planes"][:1]})[
        "programs_per_denoise"] == 4.0
    # a slice that holds one whole denoise has no cycle to count
    assert "programs_per_denoise" not in ts.summarize(accounted_events())


@pytest.mark.parametrize("recorded, pinned", [
    ("tpu_v5e_unet_block_scan.xplane.pb",
     "tpu_v5e_unet_block_scan.summary_pr35.json"),
    ("sd15_512_one_request.events.json.gz",
     "sd15_512_one_request.summary_pr35.json")])
def test_what_was_there_reads_as_the_parents_summary_to_the_digit(
        recorded, pinned):
    """``classes``, ``phases``, ``top_other``, ``idle``, ``idle_under`` of
    a recorded chip slice and of the benchmark's recorded request (PR 22's
    SD1.5: 88,502 operations, no paths) are what PR 35's ``summarize``
    gave (pinned then), and every program's account adds up."""
    if recorded.endswith(".pb"):
        events, traced_s = ts.read_events(os.path.join(DATA, recorded)), 0.004
    else:
        import gzip
        with gzip.open(os.path.join(REPO, "benchmarks", "chip", "testdata",
                                    recorded), "rt") as f:
            events, traced_s = json.load(f), 0.0
    s = ts.summarize(events, traced_s)
    with open(os.path.join(DATA, pinned), encoding="utf-8") as f:
        want = json.load(f)
    got = {"programs": {n: {k: v for k, v in p.items() if k != "account"}
                        for n, p in s["programs"].items()},
           **{k: s[k] for k in want if k != "programs"}}
    assert json.loads(json.dumps(got)) == want
    for name, program in s["programs"].items():
        account = program["account"]
        assert abs(sum(account["by_class"].values()) - program["mean_s"]) \
            < 1e-12, name
        assert program["classes"]["gaps"] + account["overlap_s"] \
            - account["dropped_s"] == pytest.approx(
                account["by_class"]["idle"], abs=1e-12), name
        assert account["overlap_s"] >= 0 and account["dropped_s"] >= 0


def test_what_gaps_held_in_the_recorded_request_was_operations_not_idle():
    """PR 22's recorded SD1.5 denoise: 341 fusions have the zero-duration
    custom call of a prefetch at their own start nanosecond, so
    ``classes`` leaves 0.016 s of them out and calls it ``gaps``; the
    device stood idle inside the execution for 0.0005 s."""
    import gzip
    with gzip.open(os.path.join(REPO, "benchmarks", "chip", "testdata",
                                "sd15_512_one_request.events.json.gz"),
                   "rt") as f:
        core = ts.summarize(json.load(f))["programs"]["jit_core"]
    assert core["classes"]["gaps"] == pytest.approx(0.016442827, abs=1e-9)
    assert core["account"]["dropped_s"] == pytest.approx(0.015984444,
                                                         abs=1e-9)
    assert core["account"]["by_class"]["idle"] == pytest.approx(
        0.000458383, abs=1e-9)
    assert core["account"]["overlap_s"] == 0.0
    top = core["account"]["top_idle"][0]
    assert top["n"] == 180.0 and "dynamic_slice.83" in top["before"]


RECORDED = os.path.join(DATA, "tpu_v5e_unet_block_scan.xplane.pb")


def test_summary_of_a_recorded_chip_slice():
    """A scan over one SpatialTransformer and one ResBlock of layers.py,
    inside a module named UNet, traced on the TPU v5e (PR 24's probe,
    Python tracer off): the operations' paths come from the ``tf_op``
    statistic of their metadata entries, which only the protobuf itself
    gives, and they are the module paths Flax put there."""
    assert os.path.getsize(RECORDED) < 200_000
    table = ts.read_op_metadata(RECORDED)["/device:TPU:0"]
    paths = {st.get(ts.OP_NAME, "") for st in table.values()}
    assert any("/UNet/down_0_attn_0/blocks_0/attn1/" in p for p in paths)
    s = ts.summarize(ts.read_events(RECORDED), traced_s=0.004)
    assert s["names_found"] and ts.OP_NAME in s["op_stat_names"]
    assert s["host_spans"] == ["dispatch"]
    core = s["programs"]["jit_core"]
    assert core["count"] == 1
    assert core["mean_s"] == pytest.approx(4.5638e-05, abs=1e-10)
    assert set(core["classes"]) == {
        "attn_self", "attn_cross", "attn_proj", "ff", "norm", "resblock",
        "sampler", "other", "gaps"}
    assert sum(core["classes"].values()) == pytest.approx(core["mean_s"])
    assert core["classes"]["attn_self"] == pytest.approx(3.545e-06, abs=1e-10)
    assert core["classes"]["attn_cross"] == pytest.approx(1.624e-06,
                                                          abs=1e-10)
    assert core["classes"]["resblock"] == pytest.approx(9.464e-06, abs=1e-10)
    # at this toy size the weights' copies into fast memory show: they
    # carry no path, and ``top_other`` names them
    assert core["classes"]["other"] == pytest.approx(3.562e-06, abs=1e-10)
    assert all(o["op"].startswith("%copy-done") for o in core["top_other"])
    assert s["idle"]["dispatch"] == pytest.approx(0.003057694, abs=1e-9)
    assert s["gaps_in_programs_s"] == pytest.approx(1.059e-06, abs=1e-10)


def test_the_recorded_slice_shows_the_gate_in_front_of_ff_out():
    """What XLA makes of GEGLU as written (the same PR 24 probe, c = 64):
    the product under ``ff/geglu/proj`` writes the WHOLE ``[B, T, 8c]``
    projection, and the fusion under ``ff/out/dot_general`` reads it: the
    split, the erf and the gating multiply run in front of the next
    product.  Both are class ``ff``, as the fused kernel's call is
    (`test_classify`), so the accepted reader sees either lowering."""
    ops = next(ln for plane in ts.read_events(RECORDED)["planes"]
               for ln in plane["lines"] if ln["name"] == ts.OPS_LINE)
    named = {p.rstrip(":").split("/blocks_0/")[-1]: n
             for n, p in zip(ops["names"], ops["paths"])}
    proj, out = named["ff/geglu/proj/dot_general"], named["ff/out/dot_general"]
    assert proj.startswith("%convolution_add_fusion.5 = bf16[2,256,512]")
    assert out.startswith("%add_add_fusion.2 = bf16[2,256,64]")
    assert "bf16[2,256,512]{2,1,0:T(8,128)(2,1)S(1)} " \
        "%convolution_add_fusion.5" in out
    assert {trace.classify(p) for p in ops["paths"] if "/ff/" in p} == {"ff"}


# --- 5. the executor's self times add up -----------------------------------

def make_prompt(seed):
    return {
        "7": {"class_type": "CheckpointLoaderSimple",
              "inputs": {"ckpt_name": "tiny.safetensors"}},
        "5": {"class_type": "CLIPTextEncode",
              "inputs": {"text": f"cat {seed}", "clip": ["7", 1]}},
        "6": {"class_type": "CLIPTextEncode",
              "inputs": {"text": "", "clip": ["7", 1]}},
        "9": {"class_type": "EmptyLatentImage",
              "inputs": {"width": 32, "height": 32, "batch_size": 1}},
        "8": {"class_type": "KSampler",
              "inputs": {"model": ["7", 0], "positive": ["5", 0],
                         "negative": ["6", 0], "latent_image": ["9", 0],
                         "seed": seed, "steps": 2, "cfg": 2.0,
                         "sampler_name": "euler", "scheduler": "normal",
                         "denoise": 1.0}},
        "1": {"class_type": "VAEDecode",
              "inputs": {"samples": ["8", 0], "vae": ["7", 2]}},
        "3": {"class_type": "SaveImage",
              "inputs": {"images": ["1", 0], "filename_prefix": f"s{seed}"}},
    }


def wait_history(state, pids, timeout=120):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(p in state._history for p in pids):
            return
        time.sleep(0.005)
    raise AssertionError("prompts never finished")


def test_executor_self_times_add_up_and_requests_carry_their_instants(
        tmp_path):
    trace.GLOBAL_STAGES.reset()
    t0 = time.perf_counter()
    st = ServerState(config_path=str(tmp_path / "cfg.json"),
                     input_dir=str(tmp_path / "in"),
                     output_dir=str(tmp_path / "out"), overlap=True,
                     coalesce=False)
    wait_history(st, [st.enqueue_prompt(make_prompt(0), "warm")])
    pids = []
    for seed in range(1, 9):
        pids.append(st.enqueue_prompt(make_prompt(seed), "c"))
        time.sleep(0.03 if seed % 2 else 0.0)
    wait_history(st, pids)
    # the executor waits in an exec_idle that is still open: a wake-up
    # with nothing queued closes it (and opens the next)
    st._queue_event.set()
    wall = time.perf_counter() - t0
    time.sleep(0.1)
    stages = trace.GLOBAL_STAGES.snapshot()
    assert stages["dispatch"]["count"] == stages["dispatch_wait"]["count"] \
        == 9
    # exec_idle + dispatch (the host's own) + dispatch_wait (the device
    # waits inside it) are the executor thread's whole time, once each
    covered = sum(stages[k]["total_s"]
                  for k in ("exec_idle", "dispatch", "dispatch_wait"))
    assert 0.98 * wall <= covered <= wall + 0.1
    # every thread's waits: the executor's are among them
    assert stages["device_wait"]["total_s"] \
        >= stages["dispatch_wait"]["total_s"]
    for name in ("history_write", "queue_to_device", "d2h", "d2h_copy",
                 "encode", "queue_wait", "compute", "job_e2e"):
        assert stages[name]["count"] >= 9, name
    rec = trace.GLOBAL_TRACES.get(pids[-1])
    root = [s for s in rec["spans"] if s["name"] == "job"][0]
    inst = root["attrs"]["instants"]
    order = ["enqueued", "popped", "dispatched", "device_ready", "encoded",
             "in_history"]
    assert [k for k in order if k in inst] == order
    assert [inst[k] for k in order] == sorted(inst[k] for k in order)
    names = {s["name"] for s in rec["spans"]}
    assert {"dispatch", "device_wait", "d2h", "d2h_copy", "history_write",
            "queue_to_device", "execute"} <= names


# --- the seventh language model's scopes (PR 49) -------------------------------

@pytest.mark.parametrize("path, want, phase", [
    ("prefill/layers/while/body/closed_call/input_layernorm_0/rsqrt",
     "lm_norm", "prefill"),
    ("prefill/layers/while/body/closed_call/self_attn_0/q_a_proj/dot_general",
     "lm_proj", "prefill"),
    ("prefill/layers/while/body/self_attn_1/q_b_proj/mul", "lm_proj",
     "prefill"),
    ("decode/while/body/layers/while/body/self_attn_1/kv_a_layernorm/mul",
     "lm_norm", "decode"),
    ("decode/while/body/layers/while/body/self_attn_0/kv_cache/"
     "dynamic_update_slice", "lm_cache", "decode"),
    ("decode/while/body/layers/while/body/self_attn_1/absorb_q/dot_general",
     "lm_proj", "decode"),
    ("prefill/layers/while/body/self_attn_0/kv_b_proj/dot_general",
     "lm_proj", "prefill"),
    ("prefill/layers/while/body/self_attn_0/rotary/cos", "lm_attn",
     "prefill"),
    ("prefill/layers/while/body/self_attn_1/while/body/bnhd,bmhd->bhnm/"
     "dot_general", "lm_attn", "prefill"),
    ("decode/while/body/layers/while/body/self_attn_0/o_proj/fewrow_dense/"
     "pallas_call", "lm_proj", "decode"),
    ("prefill/layers/while/body/post_attention_layernorm_0/mul", "lm_norm",
     "prefill"),
    ("prefill/layers/while/body/mlps_0/gate_proj/dot_general", "lm_mlp",
     "prefill"),
    ("decode/while/body/layers/while/body/mlps_1/gate_proj/"
     "fewrow_dense_gate_proj_up_proj/pallas_call", "lm_mlp", "decode"),
    ("decode/while/body/layers/while/body/mlps_1/add", "lm_mlp", "decode"),
    ("prefill/layers/while/body/mlp/router/top_k", "lm_experts", "prefill"),
    ("prefill/layers/while/body/mlp/dispatch/sort", "lm_experts", "prefill"),
    ("decode/while/body/layers/while/body/mlp/experts/while/body/cond/"
     "branch_1_fun/dot_general", "lm_experts", "decode"),
    ("decode/while/body/layers/while/body/mlp/reshape", "lm_experts",
     "decode"),
    ("decode/while/body/layers/while/body/mlp/zero_experts/reduce_sum",
     "lm_zero", "decode"),
    ("prefill/layers/while/body/mlp/zero_experts/add", "lm_zero", "prefill"),
    ("prefill/layers/while/body/add", "lm_proj", "prefill"),
    ("decode/while/body/final_norm/mul", "lm_norm", "decode"),
    ("decode/while/body/lm_head/fewrow_dense/pallas_call", "lm_head",
     "decode"),
    ("decode/while/body/sample/argmax", "lm_head", "decode"),
    ("prefill/embed_tokens/gather", "embed", "prefill"),
])
def test_the_seventh_models_scopes_fall_in_their_classes_and_phases(
        path, want, phase):
    name = "jit(lm_generate)/LongcatFlash/" + path
    assert trace.classify(name) == want
    assert trace.phase_of(name) == phase
    # under another family's model the zero experts' scope is nobody's
    if want == "lm_zero":
        assert trace.classify(name.replace("LongcatFlash", "PanguUltraMoE")) \
            != "lm_zero"
