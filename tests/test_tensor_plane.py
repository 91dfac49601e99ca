"""Device-resident tensor plane: transfer counters, retrace guards,
buffer donation, warmup and the persistent compile cache.

The acceptance contract for the data plane (ISSUE 1): on a repeated SPMD
txt2img workflow the KSampler -> VAEDecode -> DistributedCollector spine
moves ZERO bytes through host (the XLA program IS the data plane; the only
fetch is the PNG edge), and the second run re-traces NOTHING (compilation
is a one-time cost).  All measurable on the virtual CPU mesh.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.ops.base import (
    DeviceImage,
    DeviceLatent,
    OpContext,
    as_device_array,
    as_image_array,
)
from comfyui_distributed_tpu.parallel import mesh as mesh_mod
from comfyui_distributed_tpu.utils import trace as trace_mod
from comfyui_distributed_tpu.workflow import WorkflowExecutor, parse_workflow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TXT2IMG = "/root/repo/workflows/distributed-txt2img.json"


@pytest.fixture(autouse=True)
def tiny_family(monkeypatch):
    monkeypatch.setenv(registry.FAMILY_ENV, "tiny")
    yield


@pytest.fixture
def ctx():
    return OpContext(runtime=mesh_mod.MeshRuntime(mesh=mesh_mod.build_mesh()))


def _scaled_txt2img(width=64, height=64, steps=2, batch=1):
    g = parse_workflow(TXT2IMG)
    g.nodes["5"].inputs.update(width=width, height=height,
                               batch_size=batch)
    g.nodes["3"].inputs.update(steps=steps)
    return g


def _nodes_by_type(g):
    return {g.nodes[n].class_type: n for n in g.nodes}


class TestDeviceWrappers:
    def test_jnp_consumption_stays_on_device(self):
        """jnp.asarray takes the __jax_array__ fast path: no d2h."""
        img = DeviceImage(jnp.ones((2, 8, 8, 3)), fanout=2)
        before = trace_mod.GLOBAL_TRANSFERS.total("d2h")
        arr = jnp.asarray(img)
        assert isinstance(arr, jax.Array)
        assert trace_mod.GLOBAL_TRANSFERS.total("d2h") == before
        assert as_device_array(img) is img.data

    def test_numpy_consumption_is_counted(self):
        img = DeviceImage(jnp.ones((2, 8, 8, 3)))
        before = trace_mod.GLOBAL_TRANSFERS.total("d2h")
        arr = np.asarray(img)
        assert arr.shape == (2, 8, 8, 3) and arr.dtype == np.float32
        assert trace_mod.GLOBAL_TRANSFERS.total("d2h") - before \
            == arr.nbytes

    def test_as_image_array_is_a_counted_host_edge(self):
        lat = DeviceLatent(jnp.zeros((1, 4, 4, 4)), local_batch=1)
        before = trace_mod.GLOBAL_TRANSFERS.total("d2h")
        out = as_image_array(lat)
        assert out.shape == (1, 4, 4, 4)
        assert trace_mod.GLOBAL_TRANSFERS.total("d2h") > before

    def test_host_input_pays_one_h2d_put(self):
        before = trace_mod.GLOBAL_TRANSFERS.total("h2d")
        arr = as_device_array(np.zeros((2, 4, 4, 4), np.float32))
        assert isinstance(arr, jax.Array)
        assert trace_mod.GLOBAL_TRANSFERS.total("h2d") - before \
            == 2 * 4 * 4 * 4 * 4

    def test_metadata_rides_the_wrapper(self):
        img = DeviceImage(jnp.ones((4, 8, 8, 3)), local_batch=2, fanout=2)
        assert img.fanout == 2 and img.local_batch == 2
        assert len(img) == 4 and img.ndim == 4


class TestWorkflowTensorPlane:
    def test_spine_moves_zero_host_bytes(self, ctx):
        """KSampler -> VAEDecode -> Collector in SPMD mode: 0 d2h bytes;
        the ONLY fetch is the Preview/Save PNG edge."""
        g = _scaled_txt2img()
        res = WorkflowExecutor(ctx).execute(g)
        by_type = _nodes_by_type(g)
        spine = [by_type["KSampler"], by_type["VAEDecode"],
                 by_type["DistributedCollector"]]
        assert res.host_transfer_bytes("d2h", nodes=spine) == 0, \
            res.transfers
        # the true host edge did fetch (8 replicas x 16x16x3 float32)
        preview = by_type["PreviewImage"]
        assert res.transfers[preview]["d2h_bytes"] \
            == 8 * 16 * 16 * 3 * 4
        assert len(res.images) == 8

    def test_collector_output_stays_on_device(self, ctx):
        g = _scaled_txt2img()
        res = WorkflowExecutor(ctx).execute(g)
        coll_out = res.outputs[_nodes_by_type(g)["DistributedCollector"]][0]
        assert isinstance(coll_out, DeviceImage)
        assert coll_out.shape[0] == 8

    def test_second_run_retraces_nothing(self, ctx, assert_nothing_compiled):
        """The CI retrace guard: a repeated workflow must hit every jit
        cache — nothing lowered, compiled or loaded."""
        g = _scaled_txt2img()
        WorkflowExecutor(ctx).execute(g)
        res2 = WorkflowExecutor(OpContext(runtime=ctx.runtime)).execute(g)
        assert_nothing_compiled(res2.retraces)

    def test_second_run_at_another_size_is_seen_to_retrace(self, ctx):
        """The guard bites: the same workflow at another latent size
        traces its programs again, and that reads as lowering time."""
        WorkflowExecutor(ctx).execute(_scaled_txt2img())
        res2 = WorkflowExecutor(OpContext(runtime=ctx.runtime)).execute(
            _scaled_txt2img(width=96, height=96))
        assert res2.retraces["lower_s"] > 0, res2.retraces
        assert res2.retraces["compiles"] > 0, res2.retraces

    def test_results_unchanged_by_tensor_plane(self, ctx):
        """Determinism across runs survives the device-resident rewrite
        (same guarantee test_workflow::test_determinism makes, asserted
        here against the transfer-free path)."""
        r1 = WorkflowExecutor(ctx).execute(_scaled_txt2img())
        r2 = WorkflowExecutor(
            OpContext(runtime=ctx.runtime)).execute(_scaled_txt2img())
        assert np.allclose(np.stack(r1.images), np.stack(r2.images))


class TestDonation:
    def _pipe(self):
        return registry.load_pipeline("donation_test.safetensors",
                                      family_name="tiny")

    def _inputs(self, pipe, batch=1):
        ctx_arr, _ = pipe.encode_prompt(["x"])
        context = jnp.repeat(ctx_arr, batch, axis=0)
        lat = jnp.zeros((batch, 8, 8, pipe.family.latent_channels),
                        jnp.float32)
        return lat, context

    def test_donated_latent_buffer_is_invalidated(self):
        pipe = self._pipe()
        lat, context = self._inputs(pipe)
        out = pipe.sample(lat, context, context,
                          np.zeros((1,), np.uint64), steps=1, cfg=7.5,
                          sampler_name="euler", scheduler="normal",
                          donate_latents=True)
        jax.block_until_ready(out)
        assert lat.is_deleted(), \
            "donate_latents=True must hand the input buffer to XLA"

    def test_undonated_latent_buffer_survives(self):
        pipe = self._pipe()
        lat, context = self._inputs(pipe)
        out = pipe.sample(lat, context, context,
                          np.zeros((1,), np.uint64), steps=1, cfg=7.5,
                          sampler_name="euler", scheduler="normal",
                          donate_latents=False)
        jax.block_until_ready(out)
        assert not lat.is_deleted()
        np.asarray(lat)  # still readable

    def test_donation_does_not_change_numerics(self):
        pipe = self._pipe()
        lat, context = self._inputs(pipe)
        kw = dict(steps=2, cfg=7.5, sampler_name="euler",
                  scheduler="normal")
        a = np.asarray(pipe.sample(lat, context, context,
                                   np.zeros((1,), np.uint64),
                                   donate_latents=False, **kw))
        lat2, _ = self._inputs(pipe)
        b = np.asarray(pipe.sample(lat2, context, context,
                                   np.zeros((1,), np.uint64),
                                   donate_latents=True, **kw))
        assert np.allclose(a, b)

    def test_ksampler_never_donates_a_shared_graph_buffer(self, ctx):
        """The SAME latent output feeding TWO KSampler nodes (fan
        topology): the second consumer must still see a live buffer —
        prep donates only buffers it freshly created."""
        from comfyui_distributed_tpu.ops.base import get_op
        pipe = self._pipe()
        ks = get_op("KSampler")
        octx = OpContext()
        lat_d = {"samples": np.zeros((1, 8, 8, 4), np.float32),
                 "local_batch": 1, "fanout": 1}
        ctx_arr, _ = pipe.encode_prompt(["x"])
        from comfyui_distributed_tpu.ops.base import Conditioning
        cond = Conditioning(context=ctx_arr)
        (first,) = ks.execute(octx, pipe, 7, 1, 7.5, "euler", "normal",
                              positive=cond, negative=cond,
                              latent_image=lat_d)
        # both consumers read the same upstream dict
        (a,) = ks.execute(octx, pipe, 8, 1, 7.5, "euler", "normal",
                          positive=cond, negative=cond, latent_image=first)
        (b,) = ks.execute(octx, pipe, 9, 1, 7.5, "euler", "normal",
                          positive=cond, negative=cond, latent_image=first)
        np.asarray(a["samples"]), np.asarray(b["samples"])  # both live


class TestWarmupAndCompileCache:
    def test_warmup_precompiles_the_serving_shape(
            self, assert_nothing_compiled):
        pipe = registry.load_pipeline("warmup_test.safetensors",
                                      family_name="tiny")
        t = pipe.warmup(height=64, width=64, batch=1, steps=2)
        assert t["total_s"] > 0 and "sample_s" in t
        # an identically-shaped request afterwards re-traces nothing
        trace_mod.install_jax_monitoring()
        mark = trace_mod.GLOBAL_RETRACES.mark()
        pipe.warmup(height=64, width=64, batch=1, steps=2)
        assert_nothing_compiled(trace_mod.GLOBAL_RETRACES.since(mark))

    # One rule (runtime/manager.enable_persistent_compile_cache), checked
    # in a fresh interpreter each time: the session's own cache config
    # (conftest) must not leak into the answer, nor this test into it.
    _PROBE = (
        "import jax\n"
        "from comfyui_distributed_tpu.runtime.manager import "
        "enable_persistent_compile_cache as en\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "used = en()\n"
        "import json; print(json.dumps({'before': before, 'used': used, "
        "'after': jax.config.jax_compilation_cache_dir}))\n")

    def _probe(self, cwd, **env):
        import json
        import subprocess
        import sys
        base = {k: v for k, v in os.environ.items()
                if k != "JAX_COMPILATION_CACHE_DIR"}
        r = subprocess.run(
            [sys.executable, "-c", self._PROBE], cwd=str(cwd),
            env={**base, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                 **env},
            capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    def test_cache_dir_from_outside_is_left_to_jax(self, tmp_path):
        """JAX_COMPILATION_CACHE_DIR set => the program sets NO cache
        directory in code: the config holds what JAX read from the env
        before and after the call."""
        d = str(tmp_path / "placed_from_outside")
        got = self._probe(tmp_path, JAX_COMPILATION_CACHE_DIR=d)
        assert got == {"before": d, "used": d, "after": d}

    def test_default_cache_dir_is_the_checkout_whatever_cwd_and_home(
            self, tmp_path):
        """Unset => <checkout>/.jax_cache, resolved from the package's
        own location: neither the CWD nor HOME moves it."""
        want = os.path.join(REPO, ".jax_cache")
        for cwd, home in ((tmp_path, tmp_path / "h1"),
                          (REPO, tmp_path / "h2")):
            got = self._probe(cwd, HOME=str(home))
            assert got["before"] is None
            assert got["used"] == want and got["after"] == want
            assert not os.path.exists(home)      # nothing under ~

    def test_no_other_cache_knob(self, tmp_path):
        """The repo's own cache knob is gone: DTPU_COMPILE_CACHE_DIR
        places nothing."""
        got = self._probe(tmp_path,
                          DTPU_COMPILE_CACHE_DIR=str(tmp_path / "x"))
        assert got["used"] == os.path.join(REPO, ".jax_cache")


class TestShardMap:
    def test_all_gather_over_the_data_axis(self):
        from comfyui_distributed_tpu.parallel import collectives as coll
        mesh = mesh_mod.build_mesh()
        x = np.arange(8 * 2, dtype=np.float32).reshape(8, 2)
        xs = coll.shard_batch(x, mesh)
        full = np.asarray(coll.all_gather_data(xs, mesh))
        assert np.allclose(full, x)
