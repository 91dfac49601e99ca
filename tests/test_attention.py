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


class TestFlashAttention:
    def test_matches_reference(self, rng):
        from comfyui_distributed_tpu.ops.pallas.flash_attention import (
            flash_attention)
        q, k, v = _qkv(rng, B=1, N=200, H=2, D=16)
        out = flash_attention(q, k, v, interpret=True)
        ref = attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_cross_attention_shapes(self, rng):
        from comfyui_distributed_tpu.ops.pallas.flash_attention import (
            flash_attention)
        q, k, v = _qkv(rng, B=2, N=64, H=2, D=16, M=77)
        out = flash_attention(q, k, v, interpret=True)
        ref = attention_reference(q, k, v)
        assert out.shape == (2, 64, 2, 16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_layers_dispatch(self, rng, monkeypatch):
        """attn_impl='pallas' routes through the kernel and matches xla.
        The model stack asks for the compiled kernel; this CPU test puts
        the interpreter behind the same name explicitly."""
        import functools
        import importlib

        from comfyui_distributed_tpu.models.layers import (
            scaled_dot_product_attention)
        fa = importlib.import_module(
            "comfyui_distributed_tpu.ops.pallas.flash_attention")
        monkeypatch.setattr(fa, "flash_attention", functools.partial(
            fa.flash_attention, interpret=True))
        q, k, v = _qkv(rng, B=1, N=48, H=2, D=16)
        out_p = scaled_dot_product_attention(q, k, v, impl="pallas")
        out_x = scaled_dot_product_attention(q, k, v, impl="xla")
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                                   rtol=2e-4, atol=2e-4)

    def test_interpret_mode_is_never_chosen_for_the_caller(self):
        """`interpret` is an explicit argument that defaults to False: no
        backend check picks the interpreter behind the caller's back."""
        import importlib
        import inspect
        fa = importlib.import_module(
            "comfyui_distributed_tpu.ops.pallas.flash_attention")
        sig = inspect.signature(fa.flash_attention)
        assert sig.parameters["interpret"].default is False
        assert "default_backend" not in inspect.getsource(fa)

    def test_over_budget_shape_raises_naming_the_shape(self, rng,
                                                       monkeypatch):
        """A shape whose K/V do not fit the per-program VMEM budget is an
        error that names the shape — never another implementation handed
        back in the kernel's name."""
        import importlib
        fa = importlib.import_module(
            "comfyui_distributed_tpu.ops.pallas.flash_attention")
        # SDXL's largest self-attention fits, double buffers included
        assert fa.vmem_bytes(4096, fa.BLOCK_K, 128, 2) \
            <= fa.VMEM_BUDGET_BYTES
        # 16384 tokens of K and V per head do not
        q = jnp.zeros((1, 16384, 1, 128), jnp.bfloat16)
        called = []
        monkeypatch.setattr(fa.pl, "pallas_call",
                            lambda *a, **k: called.append(1))
        with pytest.raises(ValueError, match=r"\(1, 16384, 1, 128\).*VMEM"):
            fa.flash_attention(q, q, q, interpret=True)
        assert not called, "an over-budget shape reached pallas_call"


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
