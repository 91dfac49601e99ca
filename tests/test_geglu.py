"""The UNet's GEGLU as one product with its gate on the output side (PR
39): the kernel against the module as written (Pallas interpreter, CPU),
the rule that sends a call site to it (``layers.geglu_path``), its
backward pass, the mesh wrapper, and the counter of the paths taken."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from comfyui_distributed_tpu.models import layers
from comfyui_distributed_tpu.ops.pallas import geglu as gg
from comfyui_distributed_tpu.parallel.mesh import build_mesh
from comfyui_distributed_tpu.utils import trace


def _operands(rows, c, bias=True, dtype=jnp.bfloat16, seed=0, lead=None):
    rng = np.random.default_rng(seed + rows + c)
    x = jnp.asarray(rng.standard_normal(lead or (rows, c)), dtype)
    kernel = jnp.asarray(rng.standard_normal((c, 8 * c)) / np.sqrt(c), dtype)
    b = jnp.asarray(rng.standard_normal((8 * c,)) * 0.1, dtype) \
        if bias else None
    return x, kernel, b


def _oracle(x, kernel, bias):
    """The plain expression in float32 on the same (rounded) operands."""
    h = jnp.dot(x.astype(jnp.float32), kernel.astype(jnp.float32),
                precision="highest")
    if bias is not None:
        h = h + bias.astype(jnp.float32)
    a, g = jnp.split(h, 2, axis=-1)
    return np.asarray(a * jax.nn.gelu(g, approximate=False))


def _rel_err(out, ref):
    return float(np.abs(np.asarray(out, np.float32) - ref).max()
                 / np.abs(ref).max())


@pytest.fixture(autouse=True)
def no_live_mesh(monkeypatch):
    """The rule reads the live mesh.  A file that ran earlier in this
    worker may have left a server's runtime over the eight virtual
    devices, under which 256 rows are 32 a chip and stay with XLA (found
    in PR 41: any ``ServerState`` test ahead of this file failed two
    tests here).  This file's tests start from no mesh and set their
    own."""
    from comfyui_distributed_tpu.parallel import mesh as mesh_mod
    monkeypatch.setattr(mesh_mod, "_runtime", None)


@pytest.fixture
def interpreted(monkeypatch):
    """The module asks for the compiled kernel; a CPU test puts the
    interpreter behind the same name, explicitly."""
    monkeypatch.setattr(gg, "geglu",
                        functools.partial(gg.geglu, interpret=True))
    return gg


class TestKernel:
    @pytest.mark.parametrize("bias", [True, False])
    @pytest.mark.parametrize("rows,c", [(256, 320), (128, 640),
                                        (128, 1280), (384, 320)])
    def test_matches_the_module_as_written(self, rows, c, bias):
        """The published widths with few rows, bf16 operands: the kernel
        is at least as near the float32 expression as the XLA path (its
        gate sees the fp32 accumulator: one rounding fewer) and within a
        bf16 rounding of that path."""
        x, kernel, b = _operands(rows, c, bias)
        out = gg.geglu(x, kernel, b, True)
        assert out.shape == (rows, 4 * c) and out.dtype == jnp.bfloat16
        ref = _oracle(x, kernel, b)
        err, err_xla = _rel_err(out, ref), _rel_err(
            gg.xla_geglu(x, kernel, b), ref)
        assert err <= err_xla and err < 4e-3
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(gg.xla_geglu(x, kernel, b), np.float32),
            rtol=4e-2, atol=4e-2)

    def test_leading_dimensions_ride_along(self):
        x, kernel, b = _operands(256, 320, lead=(2, 128, 320))
        out = gg.geglu(x, kernel, b, True)
        assert out.shape == (2, 128, 1280)
        np.testing.assert_array_equal(
            np.asarray(out.reshape(256, 1280), np.float32),
            np.asarray(gg.geglu(x.reshape(256, 320), kernel, b, True),
                       np.float32))

    def test_float32_operands(self):
        """The tiny family's module is float32: same kernel, to float32's
        accuracy."""
        x, kernel, b = _operands(128, 128, dtype=jnp.float32)
        np.testing.assert_allclose(
            np.asarray(gg.geglu(x, kernel, b, True)), _oracle(x, kernel, b),
            rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("rows,c", [(100, 320), (2 * 4126, 640),
                                        (128, 48)])
    def test_rows_or_columns_off_the_blocks_are_refused(self, rows, c):
        """Nothing is padded: what does not divide a block is the rule's
        to keep away (it sends these to ``xla``), and the kernel says so."""
        assert gg.block_sizes(rows, c, 4 * c) is None
        assert layers.geglu_path("tpu", rows, c) == "xla"
        x, kernel, b = _operands(rows, c)
        with pytest.raises(ValueError, match="geglu"):
            gg.geglu(x, kernel, b, True)

    def test_erf_is_the_exact_one(self):
        """`_erf` against ``jax.lax.erf`` (Mosaic lowers none): a few
        float32 ulps, nowhere near the tanh form's 1e-3."""
        x = jnp.linspace(-6.0, 6.0, 200001, dtype=jnp.float32)
        assert float(jnp.abs(gg._erf(x) - jax.lax.erf(x)).max()) < 5e-7
        g = jnp.linspace(-8.0, 8.0, 100001, dtype=jnp.float32)
        exact = jax.nn.gelu(g, approximate=False)
        assert float(jnp.abs(gg.gate(jnp.ones_like(g), g) - exact).max()) \
            < 2e-6
        assert float(jnp.abs(jax.nn.gelu(g, approximate=True)
                             - exact).max()) > 1e-4

    @pytest.mark.parametrize("rows,c,blocks", [
        (8192, 640, (1024, 512)), (2048, 1280, (1024, 512)),
        (8192, 320, (1024, 256)), (2048, 640, (1024, 512)),
        (512, 1280, (512, 512)), (128, 1280, (128, 512)),
        (2048, 32, (1024, 128))])
    def test_blocks_come_from_the_shape(self, rows, c, blocks):
        assert gg.block_sizes(rows, c, 4 * c) == blocks


class TestGegluPath:
    """`geglu_path`: the path is a function of platform, shapes and mesh.
    The CFG-stacked shapes of the two benchmarked configurations are among
    the rows."""

    D4 = {"data": 4, "tensor": 1, "seq": 1}

    @pytest.mark.parametrize("platform,rows,c,mesh,path", [
        ("tpu", 2 * 4096, 640, None, "fused"),      # SDXL, 10 blocks
        ("tpu", 2 * 1024, 1280, None, "fused"),     # SDXL, 60 blocks
        ("tpu", 2 * 4096, 320, None, "fused"),      # SD1.5 outer level
        ("tpu", 2 * 1024, 640, None, "fused"),
        ("tpu", 2 * 256, 1280, None, "fused"),
        ("tpu", 2 * 64, 1280, None, "fused"),       # SD1.5 middle block
        ("tpu", 8 * 4096, 640, D4, "fused"),        # data=4 fan-out
        ("tpu", 8 * 1024, 1280, D4, "fused"),
        ("tpu", 16 * 1024, 640, None, "fused"),     # a CB bucket
        ("tpu", 2 * 9216, 320, None, "fused"),      # SD2.1 at 768
        # what shard_map could not split stays where XLA partitions it
        ("tpu", 2 * 1024, 1280,                     # mlp on tensor
         {"data": 1, "tensor": 2, "seq": 1}, "xla"),
        ("tpu", 4 * 1024, 1280,
         {"data": 2, "tensor": 2, "seq": 1}, "xla"),
        ("tpu", 2 * 4096, 640,                      # a live seq axis
         {"data": 1, "tensor": 1, "seq": 4}, "xla"),
        ("tpu", 2 * 64, 1280, D4, "xla"),           # 32 rows a chip
        ("tpu", 1026, 640, D4, "xla"),              # rows not over data
        # rows or columns off the blocks
        ("tpu", 2 * 4126, 640, None, "xla"),        # GLIGEN's fuser
        ("tpu", 2 * 77, 768, None, "xla"),
        ("tpu", 2 * 1024, 48, None, "xla"),
        ("cpu", 2 * 4096, 640, None, "xla"),        # any CPU run
        ("cpu", 2 * 1024, 1280, None, "xla"),
        ("cpu", 8 * 4096, 640, D4, "xla"),
        ("gpu", 2 * 4096, 640, None, "xla"),
    ])
    def test_rule_table(self, platform, rows, c, mesh, path):
        assert layers.geglu_path(platform, rows, c, mesh) == path

    def test_no_option_reaches_the_rule(self):
        import inspect
        src = inspect.getsource(layers.geglu_path) \
            + inspect.getsource(gg.block_sizes)
        assert "environ" not in src and "default_backend" not in src


class TestModule:
    def _ff(self, c, dtype=jnp.bfloat16, rows=(2, 128)):
        ff = layers.FeedForward(dtype=dtype)
        x = jnp.asarray(np.random.default_rng(c).standard_normal(
            (*rows, c)), jnp.float32)
        return ff, ff.init(jax.random.PRNGKey(0), x), x

    def test_the_leaf_keeps_its_name_shape_and_dtype(self, interpreted,
                                                     monkeypatch):
        """One published leaf ``geglu/proj/kernel [c, 8c]`` on either
        path: nothing is split at init, and the fused call reads it."""
        ff, params, x = self._ff(128)
        shapes = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)),
                                        params)
        assert shapes == {"params": {
            "geglu": {"proj": {"kernel": ((128, 1024), "float32"),
                               "bias": ((1024,), "float32")}},
            "out": {"kernel": ((512, 128), "float32"),
                    "bias": ((128,), "float32")}}}
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        on_tpu = ff.init(jax.random.PRNGKey(0), x)
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(on_tpu)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("c", [128, 320])
    def test_fused_module_equals_the_module_as_written(self, interpreted,
                                                       monkeypatch, c):
        ff, params, x = self._ff(c)
        want = ff.apply(params, x)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        before = trace.GEGLU_PATHS.snapshot().get("fused", 0)
        got = jax.jit(ff.apply)(params, x)
        assert trace.GEGLU_PATHS.snapshot()["fused"] == before + 1
        assert "pallas_call" in str(jax.make_jaxpr(ff.apply)(params, x))
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=3e-2, atol=3e-2)

    def test_rows_off_the_blocks_stay_the_module_as_written(
            self, monkeypatch):
        """GLIGEN's N + 30 tokens on a TPU: ``xla``, the same program as
        on the CPU."""
        ff, params, x = self._ff(128, rows=(2, 100))
        want = str(jax.make_jaxpr(ff.apply)(params, x))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        before = trace.GEGLU_PATHS.snapshot().get("xla", 0)
        assert str(jax.make_jaxpr(ff.apply)(params, x)) == want
        assert trace.GEGLU_PATHS.snapshot()["xla"] == before + 1
        assert "pallas_call" not in want

    @pytest.mark.parametrize("bias", [True, False])
    def test_gradients_are_the_xla_paths(self, bias):
        """`parallel/train.py` differentiates through GEGLU: the kernel's
        VJP is the module-as-written's on the same operands."""
        x, kernel, b = _operands(128, 128, bias, dtype=jnp.float32)

        def loss(f):
            return lambda *ops: jnp.sum(f(*ops) ** 2)

        argnums = (0, 1, 2) if bias else (0, 1)
        want = jax.grad(loss(gg.xla_geglu), argnums)(x, kernel, b)
        got = jax.jit(jax.grad(loss(
            lambda x, k, b: gg.geglu(x, k, b, True)), argnums))(x, kernel, b)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-4, atol=2e-4)

    def test_counter_after_tracing_the_tiny_unet(self, monkeypatch):
        """Each GEGLU call site adds one to the counter of the path it
        took, at trace time: the tiny UNet at a 64x64 latent has seven
        transformer blocks (three at 4096 tokens x 32, four at 1024 x
        64), all ``xla`` on the CPU and all ``fused`` as a TPU would
        trace it (nothing is lowered here); and the counter is on the
        metrics' payload."""
        from comfyui_distributed_tpu.models.unet import TINY_CONFIG, UNet
        x = jax.ShapeDtypeStruct((2, 64, 64, 4), jnp.float32)
        ts = jax.ShapeDtypeStruct((2,), jnp.float32)
        ctx = jax.ShapeDtypeStruct((2, 16, 64), jnp.float32)
        model = UNet(TINY_CONFIG)
        before = trace.GEGLU_PATHS.snapshot()
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), x, ts,
                                ctx)
        # making the weights' shapes traces the model too: no call site
        assert trace.GEGLU_PATHS.snapshot() == before

        def traced_paths():
            before = trace.GEGLU_PATHS.snapshot()
            jax.eval_shape(model.apply, params, x, ts, ctx)
            after = trace.GEGLU_PATHS.snapshot()
            return {k: n - before.get(k, 0) for k, n in after.items()
                    if n - before.get(k, 0)}

        assert traced_paths() == {"xla": 7}
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert traced_paths() == {"fused": 7}
        assert trace.counters_snapshot()["geglu_paths"]["fused"] >= 7


class TestOnMesh:
    """Under a multi-device mesh the kernel runs inside shard_map, each
    device on its own rows; the result is the unsharded call's."""

    @pytest.mark.parametrize("batch,spec", [
        (4, P("data", None, None)),     # the fan-out program
        (2, P()),                       # a batch that does not divide
    ])
    def test_shard_map_equals_unsharded(self, interpreted, batch, spec):
        x, kernel, b = _operands(512, 128, lead=(batch, 128, 128))
        mesh = build_mesh({"data": 4, "tensor": 1, "seq": 1},
                          devices=jax.devices()[:4])
        whole = layers._geglu_on_mesh(x, kernel, b, None)
        xs = jax.device_put(x, NamedSharding(mesh, spec))
        out = jax.jit(lambda x, k, b: layers._geglu_on_mesh(
            x, k, b, mesh))(xs, kernel, b)
        assert out.sharding.is_equivalent_to(NamedSharding(mesh, spec), 3)
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(whole, np.float32))

    def test_live_mesh_routes_through_shard_map(self, interpreted,
                                                monkeypatch):
        """`GEGLU` picks the live runtime's mesh up on its own, and
        without one calls the kernel directly."""
        from comfyui_distributed_tpu.parallel import mesh as mesh_mod
        seen = []
        real = jax.shard_map
        monkeypatch.setattr(jax, "shard_map", lambda *a, **kw: (
            seen.append(kw["in_specs"]), real(*a, **kw))[1])
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ff = layers.FeedForward(dtype=jnp.bfloat16)
        x = jnp.asarray(np.random.default_rng(1).standard_normal(
            (4, 128, 128)), jnp.float32)
        prev = mesh_mod._runtime
        try:
            mesh_mod.set_runtime(None)
            params = ff.init(jax.random.PRNGKey(0), x)
            alone = ff.apply(params, x)
            assert not seen
            mesh_mod.set_runtime(mesh_mod.MeshRuntime(mesh=build_mesh(
                {"data": 4, "tensor": 1, "seq": 1},
                devices=jax.devices()[:4])))
            meshed = ff.apply(params, x)
        finally:
            mesh_mod.set_runtime(prev)
        assert [[tuple(s) for s in specs] for specs in seen] \
            == [[("data", None, None), (), ()]]
        np.testing.assert_allclose(np.asarray(meshed, np.float32),
                                   np.asarray(alone, np.float32),
                                   rtol=1e-6, atol=1e-6)


def test_geglu_paths_on_the_metrics_route(tmp_path, monkeypatch):
    """``GET /distributed/metrics`` carries the counter beside
    ``attention_paths``."""
    from comfyui_distributed_tpu.models import registry
    from tests.test_server import run_with_client
    monkeypatch.setenv(registry.FAMILY_ENV, "tiny")
    trace.GEGLU_PATHS.bump("xla", 0)

    async def body(client, state):
        m = await (await client.get("/distributed/metrics")).json()
        assert m["geglu_paths"] == trace.GEGLU_PATHS.snapshot()
        assert "attention_paths" in m
    run_with_client(body, tmp_path, start_exec_thread=False)
