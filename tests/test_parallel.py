"""Mesh runtime + collectives on the 8-device virtual CPU mesh."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from comfyui_distributed_tpu.parallel import collectives as coll
from comfyui_distributed_tpu.parallel import mesh as mesh_mod
from comfyui_distributed_tpu.utils.constants import (
    DATA_AXIS, MESH_SHAPE_ENV, SEQ_AXIS, TENSOR_AXIS, TP_ENV)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def mesh8():
    return mesh_mod.build_mesh({DATA_AXIS: -1})


class TestMesh:
    def test_eight_fake_devices(self):
        assert jax.device_count() == 8

    def test_default_all_data(self, mesh8):
        assert mesh8.shape[DATA_AXIS] == 8
        assert mesh8.shape[TENSOR_AXIS] == 1

    def test_axes_resolution(self):
        m = mesh_mod.build_mesh({DATA_AXIS: 2, TENSOR_AXIS: 2, SEQ_AXIS: 2})
        assert dict(m.shape) == {DATA_AXIS: 2, TENSOR_AXIS: 2, SEQ_AXIS: 2}

    def test_fill_axis(self):
        m = mesh_mod.build_mesh({DATA_AXIS: -1, TENSOR_AXIS: 4})
        assert m.shape[DATA_AXIS] == 2

    def test_tp_env_resolves_the_serving_mesh(self, monkeypatch):
        """DTPU_TP=2, the serve path's switch: tensor=2 and the rest of
        the devices on the data axis (no runtime goes live here, so the
        compile cache stays on)."""
        monkeypatch.delenv(MESH_SHAPE_ENV, raising=False)
        monkeypatch.setenv(TP_ENV, "2")
        assert mesh_mod.axes_from_env() == {TENSOR_AXIS: 2, DATA_AXIS: -1}
        m = mesh_mod.build_mesh(devices=jax.devices()[:4])
        assert m.shape[DATA_AXIS] == 2 and m.shape[TENSOR_AXIS] == 2
        monkeypatch.setenv(TP_ENV, "1")
        assert mesh_mod.axes_from_env() is None

    def test_bad_product_raises(self):
        with pytest.raises(ValueError):
            mesh_mod.build_mesh({DATA_AXIS: 3})
        with pytest.raises(ValueError):
            mesh_mod.build_mesh({DATA_AXIS: -1, TENSOR_AXIS: -1})

    def test_describe_devices(self):
        d = mesh_mod.describe_devices()
        assert d["num_devices"] == 8
        assert d["platform"] == "cpu"
        assert len(d["devices"]) == 8

    def test_runtime_status(self):
        rt = mesh_mod.MeshRuntime(mesh=mesh_mod.build_mesh())
        st = rt.status()
        assert st["num_participants"] == 8
        rt.enabled = False
        assert rt.num_participants == 1

    def test_runtime_singleton(self):
        mesh_mod.set_runtime(None)
        a = mesh_mod.get_runtime()
        assert mesh_mod.get_runtime() is a
        mesh_mod.set_runtime(None)


class TestSeeds:
    def test_replica_seeds_master_first(self):
        s = coll.replica_seeds(100, 4, batch_per_replica=2)
        # replica-major: master(100,100), worker1(101,101)...
        assert s.tolist() == [100, 100, 101, 101, 102, 102, 103, 103]

    def test_parity_with_reference_offsets(self):
        # reference: master = seed, worker i = seed + i + 1
        s = coll.replica_seeds(7, 3, 1)
        master, w0, w1 = s.tolist()
        assert master == 7 and w0 == 7 + 0 + 1 and w1 == 7 + 1 + 1

    def test_sample_keys_distinct(self):
        seeds = jnp.asarray(coll.replica_seeds(5, 2, 3))
        keys = coll.sample_keys(seeds)
        flat = np.asarray(keys).reshape(keys.shape[0], -1)
        assert len({tuple(k) for k in flat}) == 6  # all distinct streams

    def test_sample_keys_deterministic(self):
        seeds = jnp.asarray(coll.replica_seeds(5, 2, 2))
        k1, k2 = coll.sample_keys(seeds), coll.sample_keys(seeds)
        assert np.array_equal(np.asarray(k1), np.asarray(k2))


class TestCollectives:
    def test_shard_gather_round_trip(self, mesh8, rng):
        x = rng.standard_normal((16, 4, 4, 3)).astype(np.float32)
        sharded = coll.shard_batch(x, mesh8)
        assert sharded.sharding.spec == P(DATA_AXIS)
        back = coll.gather_batch(sharded)
        assert np.array_equal(back, x)  # ordering preserved exactly

    def test_all_gather_replicates(self, mesh8, rng):
        x = rng.standard_normal((8, 4)).astype(np.float32)
        sharded = coll.shard_batch(x, mesh8)
        full = coll.all_gather_data(sharded, mesh8)
        assert full.shape == (8, 4)
        assert np.allclose(coll.gather_batch(full), x)

    def test_psum_data(self, mesh8):
        x = np.ones((8, 3), dtype=np.float32)
        out = coll.psum_data(coll.shard_batch(x, mesh8), mesh8)
        assert np.allclose(coll.gather_batch(out), 8.0)

    def test_pad_to_multiple(self):
        assert coll.pad_to_multiple(0, 8) == 0
        assert coll.pad_to_multiple(1, 8) == 8
        assert coll.pad_to_multiple(8, 8) == 8
        assert coll.pad_to_multiple(17, 8) == 24

    def test_sharded_compute_end_to_end(self, mesh8, rng):
        """A jitted elementwise op on a sharded batch keeps its sharding and
        produces the same numbers as host numpy."""
        x = rng.standard_normal((16, 8)).astype(np.float32)
        sharded = coll.shard_batch(x, mesh8)
        f = jax.jit(lambda a: jnp.tanh(a) * 2.0)
        out = f(sharded)
        assert np.allclose(coll.gather_batch(out), np.tanh(x) * 2.0, atol=1e-6)


class TestRequireBackend:
    """parallel/mesh.require_backend: serve/worker/run initialise the
    backend in their own process and refuse a platform nobody asked for.
    The device query is stubbed; nothing here probes a backend."""

    class _Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    def _stub(self, monkeypatch, platform, kind, n=1):
        monkeypatch.setattr(
            mesh_mod.jax, "devices",
            lambda *a: [self._Dev(platform, kind)] * n)

    def test_cpu_without_being_asked_exits_nonzero(self, monkeypatch):
        """JAX falls back to the CPU by itself when no TPU comes up; a
        server must not carry on there and exit 0."""
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        self._stub(monkeypatch, "cpu", "cpu")
        with pytest.raises(SystemExit) as e:
            mesh_mod.require_backend()
        assert e.value.code not in (0, None)
        assert "no TPU" in str(e.value.code)
        assert "JAX_PLATFORMS=cpu" in str(e.value.code)

    def test_explicit_cpu_is_honoured(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        self._stub(monkeypatch, "cpu", "cpu", n=8)
        assert mesh_mod.require_backend() == {
            "platform": "cpu", "kind": "cpu", "count": 8}

    def test_tpu_passes_whatever_the_env_says(self, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        self._stub(monkeypatch, "tpu", "TPU v5 lite", n=4)
        assert mesh_mod.require_backend() == {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 4}

    def test_other_accelerator_is_not_a_tpu(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "")
        self._stub(monkeypatch, "gpu", "H100")
        with pytest.raises(SystemExit):
            mesh_mod.require_backend()

    @pytest.mark.parametrize("cmd", ["serve", "worker", "run"])
    def test_cli_entry_points_refuse_an_unasked_cpu(self, monkeypatch,
                                                    cmd, tmp_path):
        """`cli serve|worker|run` with no TPU and no JAX_PLATFORMS=cpu
        exit non-zero before any server state exists."""
        from comfyui_distributed_tpu import cli
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.delenv("DTPU_COORDINATOR", raising=False)
        self._stub(monkeypatch, "cpu", "cpu")
        argv = {"serve": ["serve", "--port", "1"],
                "worker": ["worker", "--port", "1"],
                "run": ["run", str(tmp_path / "wf.json")]}[cmd]
        with pytest.raises(SystemExit) as e:
            cli.main(argv)
        assert "no TPU" in str(e.value.code)

    def test_force_cpu_platform_resizes_the_virtual_mesh(self):
        """force_cpu_platform clears the live CPU backend, so a different
        device count applies in-process (run in a child: the suite's
        8-device harness must stay as it is)."""
        import subprocess
        import sys
        code = ("import jax\n"
                "from comfyui_distributed_tpu.parallel.mesh import "
                "force_cpu_platform\n"
                "assert force_cpu_platform(4) == 4\n"
                "assert len(jax.devices()) == 4\n"
                "assert force_cpu_platform(2) == 2\n"
                "assert len(jax.devices()) == 2\n"
                "print('OK')\n")
        r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                           env={**os.environ, "JAX_PLATFORMS": "cpu",
                                "XLA_FLAGS": ""},
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]


class TestServingTensorParallel:
    """VERDICT r4 §2.3: tp must reach SERVING, not just the train step —
    a tensor-axis mesh lays the UNet params out via params_shardings and
    the sampled result must match the replicated-weights oracle."""

    @pytest.mark.xfail(
        strict=False,
        reason="upstream XLA CPU SPMD concat miscompile (JAX 0.4.37) — "
               "the serving oracle below is green because the repo's "
               "layout pins route around it; when this XPASSes (fixed "
               "jax) the pins become optional, not wrong")
    def test_upstream_sharded_concat_miscompile(self):
        """The MINIMAL repro behind the oracle mismatch (ROADMAP
        tp-concat-cpu-miscompile): on the CPU backend, jit-compiling
        ``concat([x @ w_col_sharded, x], -1)`` with ``w`` column-sharded
        over a tensor axis returns wrong values in BOTH halves of the
        concat (JAX 0.4.37); a replicate with_sharding_constraint before
        the concat restores exactness.  Kept as xfail(strict=False): the
        day a jax upgrade fixes it this XPASSes — re-enable the serving
        oracle test then."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        # this jit takes tensor-sharded inputs WITHOUT set_runtime, so
        # mesh._tp_compile_cache_guard never sees it: keep its sharded
        # executables out of the persistent cache by hand (sticky, like
        # the guard — this test is slow-tier, where the TP oracle's
        # set_runtime would disable the cache moments later anyway)
        jax.config.update("jax_enable_compilation_cache", False)
        mesh = mesh_mod.build_mesh(
            {DATA_AXIS: 2, TENSOR_AXIS: 2, SEQ_AXIS: 1},
            devices=jax.devices()[:4])
        w = jax.random.normal(jax.random.PRNGKey(0), (8, 8), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8), jnp.float32)

        def f(w, x):
            return jnp.concatenate([x @ w, x], axis=-1)

        ref = np.asarray(jax.jit(f)(w, x))
        ws = jax.device_put(w, NamedSharding(mesh, P(None, TENSOR_AXIS)))
        out = np.asarray(jax.jit(f)(ws, x))
        np.testing.assert_allclose(ref, out, rtol=1e-5, atol=1e-5)

    def test_tp_sharded_sample_matches_replicated_oracle(self, monkeypatch):
        """Green since ISSUE 16: the UNet pins the skip concat and the
        CFG row-stack to seam-safe layouts (parallel/sharding.py
        ``constrain_rows``/``stack_rows``) so the upstream XLA CPU SPMD
        concat miscompile (still repro'd above) never sees a sharded
        concat dim, and ``_ensure_tp_sharded`` drops the pipeline's jit
        cache on layout transitions so the constraint gates re-trace
        against the live mesh."""
        monkeypatch.setenv("DTPU_TP_MIN_SHARD_ELEMENTS", "2")
        from comfyui_distributed_tpu.models import registry
        registry.clear_pipeline_cache()
        mesh_mod.set_runtime(None)
        try:
            pipe = registry.load_pipeline("tp-serve.ckpt",
                                          family_name="tiny")
            ctx_c, _ = pipe.encode_prompt(["a lighthouse"])
            ctx_u, _ = pipe.encode_prompt([""])
            lat = jnp.zeros((2, 8, 8, 4), jnp.float32)
            seeds = np.asarray([3, 4], np.uint64)

            def run():
                return np.asarray(pipe.sample(
                    lat, jnp.concatenate([ctx_c] * 2),
                    jnp.concatenate([ctx_u] * 2), seeds, steps=3,
                    cfg=5.0, sampler_name="euler", scheduler="normal"))

            oracle = run()                       # replicated weights
            lat_img = jnp.ones((1, 8, 8, 4), jnp.float32) * 0.3
            dec_oracle = np.asarray(pipe.vae_decode(lat_img))
            assert pipe._tp_mesh is None
            mesh = mesh_mod.build_mesh(
                {DATA_AXIS: 2, TENSOR_AXIS: 2, SEQ_AXIS: 1},
                devices=jax.devices()[:4])
            mesh_mod.set_runtime(mesh_mod.MeshRuntime(mesh=mesh))
            tp = run()                           # tp-laid-out weights
            assert pipe._tp_mesh is mesh
            # CLIP + VAE towers lay out too and stay on-oracle
            dec_tp = np.asarray(pipe.vae_decode(lat_img))
            np.testing.assert_allclose(dec_tp, dec_oracle,
                                       rtol=2e-4, atol=2e-4)
            # some leaves actually sharded over tensor
            sharded = [
                x for x in jax.tree_util.tree_leaves(pipe.unet_params)
                if hasattr(x, "sharding")
                and x.sharding.spec != P()
                and TENSOR_AXIS in str(x.sharding.spec)]
            assert sharded, "no parameter leaf was tensor-sharded"
            np.testing.assert_allclose(tp, oracle, rtol=2e-4, atol=2e-4)
        finally:
            mesh_mod.set_runtime(None)
            registry.clear_pipeline_cache()


class TestDryrunMultichip:
    """The driver's multi-chip artifact runs the PRODUCT paths: sharded
    train step + executor fan-out inference (VERDICT r4 #3), and the
    16-device factorization exercises tensor=4 x seq=4 — axis extents
    > 2 — plus a ragged padded batch (VERDICT r4 #8).  Subprocess: the
    dryrun re-pins the backend device count, which must not disturb
    this process's 8-device mesh."""

    @pytest.mark.parametrize("n", [8, 16])
    def test_dryrun_green(self, n):
        import os
        import subprocess
        import sys
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS",)}
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = "/root/repo" + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        out = subprocess.run(
            [sys.executable, "-c",
             f"from __graft_entry__ import dryrun_multichip; "
             f"dryrun_multichip({n})"],
            cwd="/root/repo", env=env, capture_output=True, text=True,
            timeout=540)
        assert out.returncode == 0, out.stderr[-2000:]
        assert f"n={n}" in out.stdout and "inference" in out.stdout
        if n == 16:
            assert "'tensor': 4" in out.stdout and "'seq': 4" in out.stdout
        assert "tp_engaged=True" in out.stdout
