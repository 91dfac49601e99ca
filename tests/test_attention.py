"""Ring attention (seq-parallel) and the Pallas flash kernel vs the plain
softmax oracle — exact-match requirements on the 8-device virtual mesh
(SURVEY.md §4: collectives testable single-process)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.parallel.mesh import build_mesh
from comfyui_distributed_tpu.parallel.ring import (
    attention_reference,
    ring_attention,
)


def _qkv(rng, B=2, N=32, H=4, D=16, M=None):
    M = M or N
    q = rng.standard_normal((B, N, H, D)).astype(np.float32)
    k = rng.standard_normal((B, M, H, D)).astype(np.float32)
    v = rng.standard_normal((B, M, H, D)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


class TestRingAttention:
    @pytest.mark.parametrize("seq_size", [1, 2, 4])
    def test_matches_reference(self, rng, seq_size):
        mesh = build_mesh({"data": 1, "tensor": 1, "seq": seq_size,
                           }, devices=jax.devices()[:seq_size])
        q, k, v = _qkv(rng)
        out = ring_attention(q, k, v, mesh)
        ref = attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("seq_size", [2, 4])
    def test_causal_matches_reference(self, rng, seq_size):
        mesh = build_mesh({"data": 1, "tensor": 1, "seq": seq_size,
                           }, devices=jax.devices()[:seq_size])
        q, k, v = _qkv(rng, N=64)
        out = ring_attention(q, k, v, mesh, causal=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_rejects_indivisible_sequence(self, rng):
        mesh = build_mesh({"data": 1, "tensor": 1, "seq": 4},
                          devices=jax.devices()[:4])
        q, k, v = _qkv(rng, N=30)
        with pytest.raises(ValueError, match="not divisible"):
            ring_attention(q, k, v, mesh)

    def test_cross_attention_matches_reference(self, rng):
        """Nk != Nq (cross-attention): both axes shard over the ring."""
        mesh = build_mesh({"data": 1, "tensor": 1, "seq": 4},
                          devices=jax.devices()[:4])
        q, k, v = _qkv(rng, N=64, M=32)
        out = ring_attention(q, k, v, mesh)
        ref = attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_rejects_indivisible_kv(self, rng):
        """ADVICE r1: k/v divisibility was unvalidated."""
        mesh = build_mesh({"data": 1, "tensor": 1, "seq": 4},
                          devices=jax.devices()[:4])
        q, k, v = _qkv(rng, N=64, M=30)
        with pytest.raises(ValueError, match="k/v length"):
            ring_attention(q, k, v, mesh)

    def test_rejects_causal_cross_attention(self, rng):
        """ADVICE r1: causal cross-attention was silently mis-masked."""
        mesh = build_mesh({"data": 1, "tensor": 1, "seq": 4},
                          devices=jax.devices()[:4])
        q, k, v = _qkv(rng, N=64, M=32)
        with pytest.raises(ValueError, match="causal ring"):
            ring_attention(q, k, v, mesh, causal=True)

    def test_sharded_inputs_roundtrip(self, rng):
        """Works with inputs actually placed with the seq sharding (the way
        the sp train/inference path feeds it)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = build_mesh({"data": 1, "tensor": 1, "seq": 4},
                          devices=jax.devices()[:4])
        q, k, v = _qkv(rng, N=64)
        sh = NamedSharding(mesh, P(None, "seq", None, None))
        qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
        out = ring_attention(qs, ks, vs, mesh)
        ref = attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestRingIntegration:
    """attn_impl='ring' wired through the model stack (VERDICT r2 #4): the
    sequence-parallel path must be reachable from model configs and match
    the single-device math through real modules, not just standalone."""

    @pytest.fixture
    def seq_mesh(self, monkeypatch):
        from comfyui_distributed_tpu.parallel import mesh as mesh_mod
        monkeypatch.setenv("DTPU_RING_MIN_TOKENS", "1")
        mesh = build_mesh({"data": 2, "tensor": 1, "seq": 2},
                          devices=jax.devices()[:4])
        prev = mesh_mod._runtime
        mesh_mod.set_runtime(mesh_mod.MeshRuntime(mesh=mesh))
        yield mesh
        mesh_mod.set_runtime(prev)

    def test_spatial_transformer_ring_matches_xla(self, rng, seq_mesh):
        from comfyui_distributed_tpu.models.layers import SpatialTransformer
        x = jnp.asarray(rng.standard_normal((2, 8, 8, 32)), jnp.float32)
        ctx = jnp.asarray(rng.standard_normal((2, 16, 32)), jnp.float32)
        st_x = SpatialTransformer(num_heads=2, dtype=jnp.float32,
                                  attn_impl="xla")
        st_r = SpatialTransformer(num_heads=2, dtype=jnp.float32,
                                  attn_impl="ring")
        params = st_x.init(jax.random.PRNGKey(0), x, ctx)
        out_x = st_x.apply(params, x, ctx)
        out_r = st_r.apply(params, x, ctx)   # same params: impl-agnostic
        np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_x),
                                   rtol=2e-5, atol=2e-5)

    def test_unet_forward_ring_matches_oracle(self, rng, seq_mesh):
        import dataclasses
        from comfyui_distributed_tpu.models.unet import TINY_CONFIG, UNet
        x = jnp.asarray(rng.standard_normal((2, 8, 8, 4)), jnp.float32)
        ts = jnp.asarray([3.0, 7.0], jnp.float32)
        ctx = jnp.asarray(rng.standard_normal((2, 16, 64)), jnp.float32)
        m_x = UNet(TINY_CONFIG)
        m_r = UNet(dataclasses.replace(TINY_CONFIG, attn_impl="ring"))
        params = m_x.init(jax.random.PRNGKey(0), x, ts, ctx)
        out_x = m_x.apply(params, x, ts, ctx)
        out_r = m_r.apply(params, x, ts, ctx)
        np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_x),
                                   rtol=1e-4, atol=1e-4)

    @pytest.fixture
    def seq_mesh_default(self, monkeypatch):
        """seq>=2 mesh with the DEFAULT ring threshold — no
        DTPU_RING_MIN_TOKENS override anywhere in the test."""
        from comfyui_distributed_tpu.parallel import mesh as mesh_mod
        monkeypatch.delenv("DTPU_RING_MIN_TOKENS", raising=False)
        mesh = build_mesh({"data": 1, "tensor": 1, "seq": 2},
                          devices=jax.devices()[:2])
        prev = mesh_mod._runtime
        mesh_mod.set_runtime(mesh_mod.MeshRuntime(mesh=mesh))
        yield mesh
        mesh_mod.set_runtime(prev)

    @pytest.fixture
    def ring_counter(self, monkeypatch):
        """Counts actual ring_attention invocations — 'ring engaged' must
        be an observation, not an assumption (the impl silently falls
        back to xla below the token floor)."""
        from comfyui_distributed_tpu.parallel import ring as ring_mod
        calls = {"n": 0}
        real = ring_mod.ring_attention

        def counting(*a, **kw):
            calls["n"] += 1
            return real(*a, **kw)

        monkeypatch.setattr(ring_mod, "ring_attention", counting)
        return calls

    def test_sd_scale_spatial_transformer_default_threshold(
            self, rng, seq_mesh_default, ring_counter):
        """VERDICT r3 #3: a real SpatialTransformer at SD-scale tokens
        (64x64 latent = 4096 tokens, SD1.5's 512px working size) with the
        DEFAULT token floor: ring must actually engage on the
        self-attention (counted) and match the xla path; the 77-token
        cross-attention context silently stays on xla (77 % seq != 0)."""
        from comfyui_distributed_tpu.models.layers import SpatialTransformer
        x = jnp.asarray(rng.standard_normal((1, 64, 64, 32)), jnp.float32)
        ctx = jnp.asarray(rng.standard_normal((1, 77, 32)), jnp.float32)
        st_x = SpatialTransformer(num_heads=2, dtype=jnp.float32,
                                  attn_impl="xla")
        st_r = SpatialTransformer(num_heads=2, dtype=jnp.float32,
                                  attn_impl="ring")
        params = st_x.init(jax.random.PRNGKey(0), x, ctx)
        out_x = st_x.apply(params, x, ctx)
        assert ring_counter["n"] == 0       # xla path never rings
        out_r = st_r.apply(params, x, ctx)
        assert ring_counter["n"] >= 1       # 4096-token self-attn rang
        np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_x),
                                   rtol=2e-5, atol=2e-5)

    def test_sd_scale_unet_forward_default_threshold(
            self, rng, seq_mesh_default, ring_counter):
        """One full UNet forward at a 64x64 latent with the default
        floor: level-0 attention (4096 tokens) and level-1 (1024) both
        ring; output matches the xla UNet bit-for-tolerance."""
        import dataclasses

        from comfyui_distributed_tpu.models.unet import TINY_CONFIG, UNet
        x = jnp.asarray(rng.standard_normal((1, 64, 64, 4)), jnp.float32)
        ts = jnp.asarray([5.0], jnp.float32)
        ctx = jnp.asarray(rng.standard_normal((1, 16, 64)), jnp.float32)
        m_x = UNet(TINY_CONFIG)
        m_r = UNet(dataclasses.replace(TINY_CONFIG, attn_impl="ring"))
        params = m_x.init(jax.random.PRNGKey(0), x, ts, ctx)
        out_x = m_x.apply(params, x, ts, ctx)
        assert ring_counter["n"] == 0
        out_r = m_r.apply(params, x, ts, ctx)
        assert ring_counter["n"] >= 2       # both resolution levels rang
        np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_x),
                                   rtol=1e-4, atol=1e-4)

    def test_short_cross_attention_falls_back(self, rng, seq_mesh,
                                              monkeypatch):
        """77-token text context doesn't divide seq=2: impl='ring' must
        silently use the xla math instead of erroring."""
        from comfyui_distributed_tpu.models.layers import Attention
        x = jnp.asarray(rng.standard_normal((2, 16, 32)), jnp.float32)
        ctx = jnp.asarray(rng.standard_normal((2, 77, 32)), jnp.float32)
        attn = Attention(num_heads=2, dtype=jnp.float32, attn_impl="ring")
        params = attn.init(jax.random.PRNGKey(0), x, ctx)
        ref = Attention(num_heads=2, dtype=jnp.float32, attn_impl="xla")
        np.testing.assert_allclose(
            np.asarray(attn.apply(params, x, ctx)),
            np.asarray(ref.apply(params, x, ctx)), rtol=1e-6, atol=1e-6)


def _fa():
    import importlib
    return importlib.import_module(
        "comfyui_distributed_tpu.ops.pallas.flash_attention")


def _oracle(q, k, v):
    """Plain fp32 numpy softmax attention over the inputs as given."""
    q, k, v = (np.asarray(x, np.float32) for x in (q, k, v))
    s = np.einsum("bnhd,bmhd->bhnm", q, k) / np.sqrt(q.shape[-1])
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhnm,bmhd->bnhd", p / p.sum(-1, keepdims=True), v)


def _rel_err(out, ref):
    return float(np.abs(np.asarray(out, np.float32) - ref).max()
                 / np.abs(ref).max())


@pytest.fixture
def interpreted(monkeypatch):
    """The model stack asks for the compiled kernel; a CPU test puts the
    interpreter behind the same name, explicitly."""
    import functools
    from comfyui_distributed_tpu.ops.pallas import geglu
    fa = _fa()
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))
    # a UNet traced as on a TPU sends its feed-forwards to a kernel too
    monkeypatch.setattr(geglu, "geglu", functools.partial(
        geglu.geglu, interpret=True))
    return fa


class TestFlashAttention:
    # cut-down copies of every head width and layout the rule can send
    # the kernel: (B, N, H, D), M, dtype
    CASES = [
        ((2, 256, 2, 40), 256, "bfloat16"),     # SD1.5 top level
        ((2, 256, 4, 64), 256, "bfloat16"),     # SDXL / SD2.1
        ((1, 256, 2, 80), 256, "bfloat16"),
        ((1, 384, 1, 160), 384, "bfloat16"),
        ((1, 256, 3, 64), 256, "bfloat16"),     # an odd head count
        ((1, 200, 2, 16), 200, "float32"),      # lengths off the blocks
        ((2, 64, 2, 16), 77, "float32"),        # M = 77 cross-attention
        ((1, 300, 2, 64), 1100, "float32"),     # ToMe: N < M, tail masked
        ((1, 1100, 2, 40), 300, "float32"),     # N > M
        ((1, 130, 2, 64), 1030, "bfloat16"),    # GLIGEN: M off the block
        ((16, 128, 2, 80), 128, "bfloat16"),    # a CB bucket's rows
        ((1, 128, 1, 128), 128, "bfloat16"),    # a head 128 wide
        ((1, 128, 8, 32), 128, "float32"),
    ]

    @pytest.mark.parametrize("shape,m,dtype", CASES)
    def test_error_against_fp32_oracle_within_xla_attentions(
            self, shape, m, dtype):
        """The kernel is held to the precision of the path it replaces:
        its error against an fp32 oracle is at most 1.25 x
        `xla_attention`'s against the same oracle (fp32 inputs: both are
        rounding noise, so a floor of a few ulps stands in)."""
        from comfyui_distributed_tpu.models.layers import xla_attention
        b, n, h, d = shape
        rng = np.random.default_rng(n * 31 + m + d)
        q, k, v = (jnp.asarray(rng.standard_normal(s), dtype) for s in
                   ((b, n, h, d), (b, m, h, d), (b, m, h, d)))
        ref = _oracle(q, k, v)
        out = _fa().flash_attention(q, k, v, interpret=True)
        assert out.shape == q.shape and out.dtype == q.dtype
        err = _rel_err(out, ref)
        err_xla = _rel_err(xla_attention(q, k, v, d ** -0.5), ref)
        assert err <= max(1.25 * err_xla, 4e-6), (err, err_xla)

    def test_matches_reference(self, rng):
        q, k, v = _qkv(rng, B=1, N=200, H=2, D=16)
        out = _fa().flash_attention(q, k, v, interpret=True)
        ref = attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_cross_attention_shapes(self, rng):
        q, k, v = _qkv(rng, B=2, N=64, H=2, D=16, M=77)
        out = _fa().flash_attention(q, k, v, interpret=True)
        ref = attention_reference(q, k, v)
        assert out.shape == (2, 64, 2, 16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_layers_dispatch(self, rng, interpreted):
        """attn_impl='pallas' routes through the kernel and matches xla."""
        from comfyui_distributed_tpu.models.layers import (
            scaled_dot_product_attention)
        q, k, v = _qkv(rng, B=1, N=48, H=2, D=16)
        out_p = scaled_dot_product_attention(q, k, v, impl="pallas")
        out_x = scaled_dot_product_attention(q, k, v, impl="xla")
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                                   rtol=2e-4, atol=2e-4)

    def test_interpret_mode_is_never_chosen_for_the_caller(self):
        """`interpret` is an explicit argument that defaults to False: no
        backend check picks the interpreter behind the caller's back."""
        import inspect
        fa = _fa()
        sig = inspect.signature(fa.flash_attention)
        assert sig.parameters["interpret"].default is False
        assert "default_backend" not in inspect.getsource(fa)

    def test_long_sequence_streams_through_vmem(self):
        """SD2.1 at 768x768 is 9216 tokens.  K/V are streamed block by
        block, so what a program holds in VMEM does not grow with the
        sequence and no length raises (the old kernel held a head's whole
        K and V and refused past a budget)."""
        fa = _fa()
        assert not hasattr(fa, "VMEM_BUDGET_BYTES")
        rng = np.random.default_rng(5)
        q, k, v = (jnp.asarray(rng.standard_normal((1, 9216, 1, 64)),
                               jnp.float32) for _ in range(3))
        out = fa.flash_attention(q[:, :512], k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   _oracle(q[:, :512], k, v),
                                   rtol=2e-4, atol=2e-5)
        # the blocks are the 4096-token ones: nothing scales with M
        assert fa.block_sizes(9216, 9216) == fa.block_sizes(4096, 4096)
        full = jax.eval_shape(fa.flash_attention, q, k, v)
        assert full.shape == (1, 9216, 1, 64)

    def test_cache_key_does_not_carry_the_checkouts_path(self):
        """The kernel's custom call is serialised with its source
        locations and hashed as it is; the one cache rule keeps the
        checkout's path out of them, so a checkout elsewhere loads what
        this one compiled."""
        import re
        from comfyui_distributed_tpu.runtime.manager import (
            enable_persistent_compile_cache)
        enable_persistent_compile_cache(min_compile_secs=0.0)
        regex = jax.config.jax_hlo_source_file_canonicalization_regex
        assert re.sub(regex, "", _fa().__file__) \
            == "comfyui_distributed_tpu/ops/pallas/flash_attention.py"
        assert re.sub(regex, "", jax.__file__) == jax.__file__

    @pytest.mark.parametrize("n,cap,block", [
        (4096, 2048, 2048), (4096, 1024, 1024), (1024, 2048, 1024),
        (9216, 1024, 1024), (256, 2048, 256), (64, 2048, 128),
        (77, 1024, 128), (4126, 1024, 512), (1100, 2048, 256)])
    def test_blocks_come_from_the_shape(self, n, cap, block):
        """As large as the length allows; a length off the blocks (GLIGEN's
        N + 30 grounding tokens) is padded by at most an eighth."""
        assert _fa()._pick_block(n, cap) == block


class TestAttentionPath:
    """`attention_path`: the path is a function of platform, shapes and
    mesh.  The six self-attention shapes of the two benchmarked
    configurations (CFG-stacked) and their M = 77 cross-attentions are
    among the rows."""

    D4 = {"data": 4, "tensor": 1, "seq": 1}

    # d rides along to show what the rows are; the rule does not read it
    @pytest.mark.parametrize("platform,b,n,m,h,d,mesh,path", [
        ("tpu", 2, 4096, 4096, 10, 64, None, "fused"),    # SDXL, 640 wide
        ("tpu", 2, 1024, 1024, 20, 64, None, "fused"),    # SDXL, 1280 wide
        ("tpu", 2, 4096, 4096, 8, 40, None, "fused"),     # SD1.5 top level
        ("tpu", 2, 1024, 1024, 8, 80, None, "fused"),
        ("tpu", 2, 256, 256, 8, 160, None, "xla_whole"),
        ("tpu", 2, 64, 64, 8, 160, None, "xla_whole"),
        ("tpu", 2, 4096, 77, 10, 64, None, "xla_whole"),  # text context
        ("tpu", 2, 1024, 77, 8, 80, None, "xla_whole"),
        ("tpu", 8, 4096, 4096, 10, 64, D4, "fused"),      # data=4 fan-out
        ("tpu", 8, 1024, 1024, 20, 64, D4, "fused"),
        ("tpu", 8, 4096, 77, 10, 64, D4, "xla_whole"),
        ("tpu", 4, 1024, 1024, 20, 64,                    # heads on tensor
         {"data": 2, "tensor": 2, "seq": 1}, "fused"),
        # what shard_map could not split stays where XLA partitions it
        ("tpu", 2, 4096, 4096, 10, 64,                    # a live seq axis
         {"data": 1, "tensor": 1, "seq": 4}, "xla_chunked"),
        ("tpu", 2, 1024, 1024, 20, 64,
         {"data": 2, "tensor": 1, "seq": 2}, "xla_whole"),
        ("tpu", 2, 1024, 1024, 20, 64, D4, "xla_whole"),  # 2 rows on data=4
        ("tpu", 4, 4096, 4096, 10, 64,                    # 10 heads on 4
         {"data": 1, "tensor": 4, "seq": 1}, "xla_chunked"),
        ("tpu", 2, 9216, 9216, 5, 64, None, "fused"),     # SD2.1 at 768
        ("tpu", 2, 2048, 4096, 8, 40, None, "fused"),     # ToMe: N < M
        ("tpu", 2, 4126, 4126, 10, 64, None, "fused"),    # GLIGEN fuser
        ("tpu", 16, 1024, 1024, 8, 80, None, "fused"),    # a CB bucket
        ("tpu", 2, 1023, 1023, 8, 80, None, "xla_whole"),  # under the line
        ("cpu", 2, 4096, 4096, 10, 64, None, "xla_chunked"),  # any CPU run
        ("cpu", 2, 1024, 1024, 20, 64, None, "xla_whole"),
        ("cpu", 8, 4096, 4096, 10, 64, D4, "xla_chunked"),
        ("gpu", 2, 4096, 4096, 10, 64, None, "xla_chunked"),
    ])
    def test_rule_table(self, platform, b, n, m, h, d, mesh, path):
        from comfyui_distributed_tpu.models.layers import attention_path
        assert attention_path(platform, b, n, m, h, mesh) == path

    def test_no_option_reaches_the_rule(self, monkeypatch):
        """The score ceiling governs only what the rule leaves to XLA."""
        from comfyui_distributed_tpu.models.layers import attention_path
        monkeypatch.setenv("DTPU_ATTN_SCORES_BYTES", "1")
        assert attention_path("tpu", 2, 4096, 4096, 10) == "fused"
        assert attention_path("tpu", 2, 256, 256, 8) == "xla_chunked"

    @pytest.mark.parametrize("axes,path", [
        (None, "fused"),
        ({"data": 2, "tensor": 2, "seq": 1}, "fused"),
        ({"data": 1, "tensor": 1, "seq": 4}, "xla_whole"),
    ])
    def test_training_step_differentiates_through_the_rule(
            self, rng, interpreted, monkeypatch, axes, path):
        """`parallel/train.py` takes `jax.value_and_grad` through the
        UNet, so whatever the rule picks on a TPU must have a VJP: the
        kernel's is `xla_attention`'s, recomputed.  The platform is
        forced, the interpreter stands behind the kernel."""
        from comfyui_distributed_tpu.models import layers
        from comfyui_distributed_tpu.parallel import mesh as mesh_mod
        from comfyui_distributed_tpu.utils import trace
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(layers, "FUSED_MIN_TOKENS", 128)
        q, k, v = _qkv(rng, B=4, N=128, H=2, D=16, M=160)

        def loss(attend):
            return lambda q, k, v: jnp.sum(attend(q, k, v) ** 2)

        want = jax.grad(loss(lambda q, k, v: layers.xla_attention(
            q, k, v, 0.25)), argnums=(0, 1, 2))(q, k, v)
        prev = mesh_mod._runtime
        before = trace.ATTENTION_PATHS.snapshot().get(path, 0)
        try:
            mesh_mod.set_runtime(axes and mesh_mod.MeshRuntime(
                mesh=build_mesh(axes, devices=jax.devices()[:4])))
            got = jax.jit(jax.grad(loss(
                layers.scaled_dot_product_attention),
                argnums=(0, 1, 2)))(q, k, v)
        finally:
            mesh_mod.set_runtime(prev)
        assert trace.ATTENTION_PATHS.snapshot()[path] == before + 1
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-5)

    def test_train_step_runs_with_the_kernel_in_the_unet(
            self, interpreted, monkeypatch):
        """The step of `parallel/train.py` over the tiny UNet at a 32x32
        latent, as a TPU would trace it: its three 1024-token
        self-attentions take the kernel, and the step's gradients are
        finite and those of the XLA path."""
        from comfyui_distributed_tpu.models.schedules import (
            make_discrete_schedule)
        from comfyui_distributed_tpu.models.unet import TINY_CONFIG, UNet
        from comfyui_distributed_tpu.parallel.train import diffusion_loss
        from comfyui_distributed_tpu.utils import trace
        model, ds = UNet(TINY_CONFIG), make_discrete_schedule()
        rng = np.random.default_rng(0)
        batch = {"latents": rng.normal(size=(2, 32, 32, 4)).astype(
            np.float32), "context": rng.normal(size=(
                2, 16, TINY_CONFIG.context_dim)).astype(np.float32)}
        params = model.init(jax.random.PRNGKey(0), batch["latents"],
                            jnp.zeros((2,)), batch["context"])

        def grads():
            return jax.jit(jax.grad(lambda p: diffusion_loss(
                model.apply, p, batch, jax.random.PRNGKey(1), ds)[0]))(
                    params)

        want = grads()
        before = trace.ATTENTION_PATHS.snapshot().get("fused", 0)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        got = grads()
        assert trace.ATTENTION_PATHS.snapshot()["fused"] == before + 3
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert np.isfinite(np.asarray(g)).all()
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-2, atol=2e-4)

    def test_counter_after_tracing_the_tiny_unet(self, monkeypatch):
        """Each attention call site adds one to the counter of the path
        it took, at trace time.  The tiny UNet at a 64x64 latent has seven
        transformer blocks: three at 4096 tokens, four at 1024, each with
        a self- and a 16-token cross-attention."""
        from comfyui_distributed_tpu.models.unet import TINY_CONFIG, UNet
        from comfyui_distributed_tpu.utils import trace
        x = jax.ShapeDtypeStruct((2, 64, 64, 4), jnp.float32)
        ts = jax.ShapeDtypeStruct((2,), jnp.float32)
        ctx = jax.ShapeDtypeStruct((2, 16, 64), jnp.float32)
        model = UNet(TINY_CONFIG)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), x, ts,
                                ctx)

        def traced_paths():
            before = trace.ATTENTION_PATHS.snapshot()
            jax.eval_shape(model.apply, params, x, ts, ctx)
            after = trace.ATTENTION_PATHS.snapshot()
            return {k: n - before.get(k, 0) for k, n in after.items()
                    if n - before.get(k, 0)}

        assert traced_paths() == {"xla_whole": 14}
        # what the same trace takes on a TPU (nothing is lowered here)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert traced_paths() == {"fused": 7, "xla_whole": 7}
        assert trace.counters_snapshot()["attention_paths"]["fused"] >= 7


class TestFusedOnMesh:
    """Under a multi-device mesh the kernel runs inside shard_map, each
    device on its own rows and heads; the result is the unsharded
    call's."""

    @pytest.mark.parametrize("axes,b,h", [
        ({"data": 4, "tensor": 1, "seq": 1}, 8, 2),   # the fan-out program
        ({"data": 2, "tensor": 2, "seq": 1}, 4, 4),   # heads on tensor
        ({"data": 4, "tensor": 1, "seq": 1}, 2, 2),   # rows do not divide
    ])
    def test_shard_map_equals_unsharded(self, rng, interpreted, axes, b, h):
        from comfyui_distributed_tpu.models import layers
        mesh = build_mesh(axes, devices=jax.devices()[:4])
        q, k, v = _qkv(rng, B=b, N=128, H=h, D=16, M=160)
        whole = interpreted.flash_attention(q, k, v)
        sharded = jax.jit(lambda q, k, v: layers._fused_on_mesh(
            q, k, v, mesh))(q, k, v)
        np.testing.assert_allclose(np.asarray(sharded), np.asarray(whole),
                                   rtol=1e-6, atol=1e-6)

    def test_rows_stay_on_their_devices(self, rng, interpreted):
        """Batch-sharded operands are not gathered: the output keeps the
        rows-on-data sharding it came in with."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from comfyui_distributed_tpu.models import layers
        mesh = build_mesh({"data": 4, "tensor": 1, "seq": 1},
                          devices=jax.devices()[:4])
        sh = NamedSharding(mesh, P("data"))
        q, k, v = (jax.device_put(x, sh)
                   for x in _qkv(rng, B=8, N=128, H=2, D=16))
        out = jax.jit(lambda q, k, v: layers._fused_on_mesh(
            q, k, v, mesh))(q, k, v)
        assert out.sharding.is_equivalent_to(
            NamedSharding(mesh, P("data", None, None, None)), 4)

    def test_live_mesh_routes_through_shard_map(self, rng, interpreted,
                                                monkeypatch):
        """`scaled_dot_product_attention` picks the live runtime's mesh up
        on its own, and without one calls the kernel directly."""
        from comfyui_distributed_tpu.models import layers
        from comfyui_distributed_tpu.parallel import mesh as mesh_mod
        seen = []
        real = jax.shard_map
        monkeypatch.setattr(jax, "shard_map", lambda *a, **kw: (
            seen.append(kw["in_specs"][0]), real(*a, **kw))[1])
        q, k, v = _qkv(rng, B=4, N=128, H=2, D=16)
        prev = mesh_mod._runtime
        try:
            mesh_mod.set_runtime(None)
            alone = layers.scaled_dot_product_attention(q, k, v,
                                                        impl="pallas")
            assert not seen
            mesh_mod.set_runtime(mesh_mod.MeshRuntime(mesh=build_mesh(
                {"data": 4, "tensor": 1, "seq": 1},
                devices=jax.devices()[:4])))
            meshed = layers.scaled_dot_product_attention(q, k, v,
                                                         impl="pallas")
        finally:
            mesh_mod.set_runtime(prev)
        assert [tuple(s) for s in seen] == [("data", None, None, None)]
        np.testing.assert_allclose(np.asarray(meshed), np.asarray(alone),
                                   rtol=1e-6, atol=1e-6)


class TestChunkedXLAAttention:
    """Query-chunked score materialization (the r4 on-chip HBM-OOM fix):
    softmax is per-query-row, so chunking N is numerically exact."""

    def test_chunked_matches_unchunked_exactly(self, monkeypatch):
        from comfyui_distributed_tpu.models.layers import xla_attention
        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.standard_normal((2, 256, 4, 16)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, 77, 4, 16)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, 77, 4, 16)), jnp.float32)
        scale = 0.25
        full = xla_attention(q, k, v, scale)
        # force chunking: ceiling below one row-block's scores
        monkeypatch.setenv("DTPU_ATTN_SCORES_BYTES",
                           str(4 * 2 * 4 * 64 * 77))
        chunked = xla_attention(q, k, v, scale)
        np.testing.assert_array_equal(np.asarray(full),
                                      np.asarray(chunked))

    def test_chunk_picks_divisor(self, monkeypatch):
        """N=96 with a ceiling for ~40 rows -> largest divisor <= 40 is
        32; result still exact."""
        from comfyui_distributed_tpu.models.layers import xla_attention
        rng = np.random.default_rng(11)
        q = jnp.asarray(rng.standard_normal((1, 96, 2, 8)), jnp.float32)
        kv = jnp.asarray(rng.standard_normal((1, 96, 2, 8)), jnp.float32)
        full = xla_attention(q, kv, kv, 0.35)
        monkeypatch.setenv("DTPU_ATTN_SCORES_BYTES",
                           str(4 * 1 * 2 * 40 * 96))
        chunked = xla_attention(q, kv, kv, 0.35)
        np.testing.assert_array_equal(np.asarray(full),
                                      np.asarray(chunked))

    def test_small_shapes_not_chunked_under_jit(self, monkeypatch):
        """The decision is trace-time static: tiny N never chunks even
        with a zero ceiling (N<=128 fast path), and the jitted result
        matches eager."""
        from comfyui_distributed_tpu.models.layers import xla_attention
        monkeypatch.setenv("DTPU_ATTN_SCORES_BYTES", "0")
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((1, 64, 2, 8)), jnp.float32)
        out_e = xla_attention(q, q, q, 0.3)
        out_j = jax.jit(lambda a: xla_attention(a, a, a, 0.3))(q)
        np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_j),
                                   rtol=2e-6, atol=2e-6)
