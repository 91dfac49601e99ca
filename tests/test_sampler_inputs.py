"""A request's sampler inputs come from ONE program (PR 52).

Between a request's text encode and the denoise the executor enqueues one
cached jitted program fed with host values (``registry.sampler_inputs``):
the per-sample keys, SDXL's ADM vectors, the contexts at ``total`` rows.
Made eagerly they were twenty tiny programs an SD1.5 request and fifty an
SDXL one.  Held here: the same bits as the eager arithmetic (restated
below as it stood before), nothing compiled by a second request, and a
guard that fails when an eager device operation comes back."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.models import samplers as smp
from comfyui_distributed_tpu.models.layers import timestep_embedding
from comfyui_distributed_tpu.ops.base import (Conditioning, OpContext,
                                              SeedValue)
from comfyui_distributed_tpu.ops.basic import (_prepare_sample_inputs,
                                               _sdxl_vector_cond)
from comfyui_distributed_tpu.parallel import mesh as mesh_mod
from comfyui_distributed_tpu.utils import trace
from comfyui_distributed_tpu.workflow import WorkflowExecutor, parse_workflow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TXT2IMG = os.path.join(REPO, "workflows", "distributed-txt2img.json")


def pipeline(monkeypatch, family):
    monkeypatch.setenv(registry.FAMILY_ENV, family)
    registry.clear_pipeline_cache()
    return registry.load_pipeline(f"inputs-{family}.safetensors")


def conditionings(pipe):
    pos, pooled = pipe.encode_prompt(["a fox"])
    neg, npooled = pipe.encode_prompt([""])
    return (Conditioning(context=pos, pooled=pooled),
            Conditioning(context=neg, pooled=npooled))


# --- the keys: sample_keys(seeds, idx), bit for bit --------------------------

def latent(rows, **meta):
    return {"samples": np.zeros((rows, 8, 8, 4), np.float32), **meta}


@pytest.mark.parametrize("seed, lat", [
    # 64-bit seeds that differ only in the high word
    (7, latent(2)),
    (7 + (1 << 32), latent(2)),
    (7 + (5 << 32), latent(2)),
    # fan-out replica seeds (seed + r) with the fold index tiled per replica
    (SeedValue(11, distributed=True), latent(8, fanout=4, local_batch=2)),
    # a coalesced group's per-prompt list, prompt-major
    (SeedValue(3, per_prompt=np.asarray([3, 1 << 40, 9], np.uint64)),
     latent(6, local_batch=2)),
    # LatentBatchSeedBehavior 'fixed': one stream for the local batch
    (5, latent(3, seed_fixed_batch=True)),
], ids=["low", "high_word_1", "high_word_5", "fanout_replicas", "coalesced",
        "seed_fixed_batch"])
def test_the_programs_keys_are_sample_keys_bit_for_bit(monkeypatch, seed, lat):
    pipe = pipeline(monkeypatch, "tiny")
    pos, neg = conditionings(pipe)
    prep = _prepare_sample_inputs(OpContext(), pipe, seed, lat, pos, neg)
    want = smp.sample_keys(prep.seeds, prep.sample_idx)
    assert prep.keys.dtype == want.dtype == jnp.uint32
    assert np.array_equal(np.asarray(prep.keys), np.asarray(want))
    # and ``sample`` makes the same from the seeds when given none
    alone = registry.sampler_inputs(pipe, len(prep.seeds), prep.seeds,
                                    prep.sample_idx)[0]
    assert np.array_equal(np.asarray(alone), np.asarray(want))
    registry.clear_pipeline_cache()


def test_seeds_that_differ_in_the_high_word_alone_get_other_keys(monkeypatch):
    pipe = pipeline(monkeypatch, "tiny")
    seeds = np.asarray([7, 7 + (1 << 32), 7 + (5 << 32)], np.uint64)
    keys = np.asarray(registry.sampler_inputs(
        pipe, 3, seeds, np.zeros((3,), np.uint32))[0])
    assert len({tuple(k) for k in keys}) == 3
    # a device array of seeds keeps its 32-bit route
    dev = registry.sampler_inputs(pipe, 3, jnp.asarray([7, 8, 9]))[0]
    assert np.array_equal(np.asarray(dev),
                          np.asarray(smp.sample_keys(jnp.asarray([7, 8, 9]))))
    registry.clear_pipeline_cache()


# --- the ADM vector: the eager arithmetic as it stood before PR 52 -----------

def eager_vector(pooled, sizes, want, batch):
    sizes = jnp.asarray([[float(v) for v in sizes]], jnp.float32)
    emb = timestep_embedding(sizes.reshape(-1), 256).reshape(1, -1)
    vec = jnp.concatenate([pooled, emb], axis=-1)
    if vec.shape[-1] < want:
        vec = jnp.pad(vec, ((0, 0), (0, want - vec.shape[-1])))
    return jnp.repeat(vec[:, :want], batch, axis=0)


class Family:
    def __init__(self, name, want):
        self.name = name
        self.unet = type("U", (), {"adm_in_channels": want})()


class Stand:
    """What the ops layer needs of a pipeline, and no cache."""

    def __init__(self, name="sdxl", want=2816):
        self.family = Family(name, want)


POOLED = np.linspace(-1.0, 1.0, 1280, dtype=np.float32)[None]


@pytest.mark.parametrize("name, want, size_cond, batch, sizes", [
    ("sdxl", 2816, (1024, 1024, 0, 0, 512, 512), 2,
     (1024, 1024, 0, 0, 512, 512)),
    ("sdxl", 2816, None, 2, (512, 640, 0, 0, 512, 640)),
    ("sdxl_refiner", 2560, None, 1, (512, 640, 0, 0, 6.0)),
    ("sdxl_refiner", 2560, (512, 512, 0, 0, 2.5), 3, (512, 512, 0, 0, 2.5)),
    ("sdxl", 3000, None, 2, (512, 640, 0, 0, 512, 640)),
    ("sdxl", 1500, None, 2, (512, 640, 0, 0, 512, 640)),
], ids=["size_cond", "from_the_latent", "refiner_default_score",
        "refiner_five_scalars", "padded", "cut"])
def test_the_programs_vector_is_the_eager_one(name, want, size_cond, batch,
                                              sizes):
    cond = Conditioning(context=None, pooled=POOLED, size_cond=size_cond)
    got = _sdxl_vector_cond(Stand(name, want), cond, batch, 512, 640)
    ref = eager_vector(POOLED, sizes, want, batch)
    assert got.shape == ref.shape == (batch, want)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_a_conditioning_without_a_pooled_vector_rides_zeros():
    got = _sdxl_vector_cond(Stand(), Conditioning(context=None), 2, 64, 64)
    ref = eager_vector(jnp.zeros((1, 1280)), (64, 64, 0, 0, 64, 64), 2816, 2)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_every_entry_rides_its_own_vector_and_rows_it_has_are_kept(
        monkeypatch):
    pipe = pipeline(monkeypatch, "tiny_sdxl")
    pos, neg = conditionings(pipe)
    prep = _prepare_sample_inputs(OpContext(), pipe, 0, latent(3), pos, neg)
    want = pipe.family.unet.adm_in_channels
    assert np.array_equal(
        np.asarray(prep.y), np.asarray(eager_vector(
            pos.pooled, (64, 64, 0, 0, 64, 64), want, 3)))
    assert np.array_equal(np.asarray(prep.context),
                          np.repeat(np.asarray(pos.context), 3, axis=0))
    assert np.array_equal(np.asarray(prep.uncond),
                          np.repeat(np.asarray(neg.context), 3, axis=0))
    # one row asked of a one-row conditioning: the array itself
    one = _prepare_sample_inputs(OpContext(), pipe, 0, latent(1), pos, neg)
    assert one.context is pos.context and one.uncond is neg.context
    registry.clear_pipeline_cache()


# --- nothing compiles for a second request, nothing eager comes back ---------

def graph(seed):
    g = parse_workflow(TXT2IMG)
    for node in g.nodes.values():
        if node.class_type == "EmptyLatentImage":
            node.inputs.update(width=32, height=32, batch_size=1)
        elif node.class_type == "KSampler":
            node.inputs.update(steps=2)
        elif node.class_type == "DistributedSeed":
            node.inputs.update(seed=seed)
    return g


@pytest.fixture
def one_chip():
    return mesh_mod.MeshRuntime(
        mesh=mesh_mod.build_mesh(devices=jax.devices()[:1]))


@pytest.mark.parametrize("family", ["tiny", "tiny_sdxl"])
def test_a_second_request_at_another_seed_compiles_nothing(
        monkeypatch, one_chip, assert_nothing_compiled, family):
    monkeypatch.setenv(registry.FAMILY_ENV, family)
    registry.clear_pipeline_cache()
    first = WorkflowExecutor(OpContext(runtime=one_chip)).execute(graph(1))
    second = WorkflowExecutor(OpContext(runtime=one_chip)).execute(
        graph((1 << 40) + 2))
    assert_nothing_compiled(second.retraces)
    assert not np.array_equal(first.images[0], second.images[0])
    registry.clear_pipeline_cache()


def programs_under(trace_dir, span):
    """The jitted calls (``PjitFunction(<name>)``, the runtime's own mark
    around every dispatch from Python, an eager primitive's too) that lie
    inside the host annotation ``dtpu/<span>``, by name."""
    from jax.profiler import ProfileData
    (pb,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    found = []
    for plane in ProfileData.from_file(pb).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in line.events]
            for s, e, name in events:
                if name != trace.HOST_PREFIX + span:
                    continue
                # the runtime marks a call twice, one mark inside the other
                calls, until = [], -1
                for a, b, n in sorted(events):
                    if s <= a and b <= e and n.startswith("PjitFunction") \
                            and a >= until:
                        calls.append(n[len("PjitFunction("):-1])
                        until = b
                found.append(calls)
    return found


@pytest.mark.parametrize("family", ["tiny", "tiny_sdxl"])
def test_a_warm_sampler_node_enqueues_one_program_beside_the_denoise(
        monkeypatch, tmp_path, one_chip, family):
    """From the end of the second ``CLIPTextEncode`` to ``core``'s enqueue:
    no eager device operation, and exactly one jitted program."""
    from jax._src import dispatch
    monkeypatch.setenv(registry.FAMILY_ENV, family)
    registry.clear_pipeline_cache()
    WorkflowExecutor(OpContext(runtime=one_chip)).execute(graph(1))

    eager, inside = [], []
    real_apply = dispatch.apply_primitive
    from comfyui_distributed_tpu.ops.base import get_op
    sampler = get_op("KSampler")
    real_execute = sampler.execute

    def counting(prim, *args, **params):
        if inside:
            eager.append(prim.name)
        return real_apply(prim, *args, **params)

    def execute(*args, **kwargs):
        inside.append(True)
        try:
            return real_execute(*args, **kwargs)
        finally:
            inside.pop()

    monkeypatch.setattr(dispatch, "apply_primitive", counting)
    monkeypatch.setattr(sampler, "execute", execute)
    trace.start_device_trace(str(tmp_path / "t"))
    try:
        root = trace.start_span("job", attrs={"prompt_id": "p-52"})
        with trace.use_span(root):
            WorkflowExecutor(OpContext(runtime=one_chip)).execute(graph(2))
    finally:
        trace.stop_device_trace()
    assert eager == []
    assert programs_under(str(tmp_path / "t"), "KSampler") \
        == [["sampler_inputs", "core"]]
    registry.clear_pipeline_cache()


def test_under_a_mesh_the_keys_stay_uncommitted_beside_a_laid_out_batch(
        monkeypatch):
    """``core`` was compiled to take the keys as an uncommitted array
    beside a batch laid over the mesh; keys that came out of one program
    with the mesh's committed contexts would be committed too, and the
    denoise would be lowered and compiled again."""
    monkeypatch.setenv(registry.FAMILY_ENV, "tiny_sdxl")
    registry.clear_pipeline_cache()
    seen = {}
    real = registry.DiffusionPipeline.sample

    def sample(self, latents, context, uncond, seeds, **kw):
        seen.update(latents=latents, context=context, y=kw["y"],
                    keys=kw["keys"], seeds=seeds, idx=kw["sample_idx"])
        return real(self, latents, context, uncond, seeds, **kw)

    monkeypatch.setattr(registry.DiffusionPipeline, "sample", sample)
    four = mesh_mod.MeshRuntime(
        mesh=mesh_mod.build_mesh(devices=jax.devices()[:4]))
    res = WorkflowExecutor(OpContext(runtime=four)).execute(graph(3))
    assert len(res.images) == 4
    for name in ("latents", "context", "y"):
        assert seen[name].committed and len(seen[name].sharding.device_set) \
            == 4, name
    assert not seen["keys"].committed
    assert np.array_equal(
        np.asarray(seen["keys"]),
        np.asarray(smp.sample_keys(seen["seeds"], seen["idx"])))
    registry.clear_pipeline_cache()
