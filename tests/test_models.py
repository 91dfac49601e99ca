"""Model zoo: UNet/VAE/CLIP shapes, tokenizer weighting, pipeline bundle."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import registry, tokenizer as tok_mod
from comfyui_distributed_tpu.models.clip import TINY_CLIP_CONFIG, CLIPTextModel
from comfyui_distributed_tpu.models.unet import TINY_CONFIG, UNet
from comfyui_distributed_tpu.models.upscalers import TINY_RRDB_CONFIG, RRDBNet
from comfyui_distributed_tpu.models.vae import TINY_VAE_CONFIG, VAE


@pytest.fixture(autouse=True)
def tiny_family(monkeypatch):
    monkeypatch.setenv(registry.FAMILY_ENV, "tiny")
    yield


class TestUNet:
    def test_forward_shape_and_dtype(self):
        unet = UNet(TINY_CONFIG)
        x = jnp.zeros((2, 8, 8, 4))
        ts = jnp.zeros((2,))
        ctx = jnp.zeros((2, 77, 64))
        params = unet.init(jax.random.PRNGKey(0), x, ts, ctx)["params"]
        out = unet.apply({"params": params}, x, ts, ctx)
        assert out.shape == (2, 8, 8, 4)
        assert out.dtype == jnp.float32

    def test_odd_spatial_dims_multiple_of_downscale(self):
        unet = UNet(TINY_CONFIG)
        x = jnp.zeros((1, 16, 8, 4))
        params = unet.init(jax.random.PRNGKey(0), x, jnp.zeros((1,)),
                           jnp.zeros((1, 77, 64)))["params"]
        out = unet.apply({"params": params}, x, jnp.zeros((1,)),
                         jnp.zeros((1, 77, 64)))
        assert out.shape == x.shape


class TestVAE:
    def test_encode_decode_round_trip_shapes(self):
        vae = VAE(TINY_VAE_CONFIG)
        img = jnp.zeros((1, 16, 16, 3))
        params = vae.init(jax.random.PRNGKey(0), img)["params"]
        lat = vae.apply({"params": params}, img, method=vae.encode)
        assert lat.shape == (1, 8, 8, 4)  # downscale 2 for tiny config
        dec = vae.apply({"params": params}, lat, method=vae.decode)
        assert dec.shape == img.shape
        assert float(jnp.min(dec)) >= 0.0 and float(jnp.max(dec)) <= 1.0

    def test_encode_stochastic_with_key(self):
        vae = VAE(TINY_VAE_CONFIG)
        img = jnp.ones((1, 16, 16, 3)) * 0.5
        params = vae.init(jax.random.PRNGKey(0), img)["params"]
        a = vae.apply({"params": params}, img, jax.random.PRNGKey(1),
                      method=vae.encode)
        b = vae.apply({"params": params}, img, method=vae.encode)
        assert a.shape == b.shape


class TestCLIP:
    def test_hidden_and_pooled(self):
        m = CLIPTextModel(TINY_CLIP_CONFIG)
        toks = jnp.zeros((2, 77), jnp.int32).at[:, 0].set(10)
        params = m.init(jax.random.PRNGKey(0), toks)["params"]
        hidden, pooled = m.apply({"params": params}, toks)
        assert hidden.shape == (2, 77, 64)
        assert pooled.shape == (2, 64)


class TestTokenizer:
    def test_weight_parsing(self):
        p = tok_mod.parse_weighted_prompt
        assert p("plain text") == [("plain text", 1.0)]
        frags = p("a (cat) dog")
        assert ("cat", pytest.approx(1.1)) in [(t, w) for t, w in frags]
        frags = p("a ((cat))")
        assert any(abs(w - 1.21) < 1e-6 for _, w in frags)
        frags = p("[down] up")
        assert any(abs(w - 1 / 1.1) < 1e-6 for _, w in frags)
        frags = p("(exact:1.5)")
        assert frags == [("exact", 1.5)]

    def test_unbalanced_is_literal(self):
        frags = tok_mod.parse_weighted_prompt("smile :) and (open")
        joined = "".join(t for t, _ in frags)
        assert "smile :)" in joined and "open" in joined

    def test_hash_tokenizer_stable_and_padded(self):
        t = tok_mod.HashTokenizer(vocab_size=4096)
        ids1, w1 = t.encode("hello world")
        ids2, _ = t.encode("hello world")
        assert np.array_equal(ids1, ids2)
        assert ids1.shape == (77,)
        assert ids1[0] == t.start
        assert t.end in ids1
        assert w1.shape == (77,)

    def test_weights_reach_tokens(self):
        t = tok_mod.HashTokenizer(vocab_size=4096)
        _, w = t.encode("a (strong:2.0) word")
        assert 2.0 in w.tolist()


class TestPipeline:
    def test_virtual_pipeline_deterministic(self):
        registry.clear_pipeline_cache()
        p1 = registry.load_pipeline("anything.safetensors")
        leaf1 = jax.tree_util.tree_leaves(p1.unet_params)[0]
        registry.clear_pipeline_cache()
        p2 = registry.load_pipeline("anything.safetensors")
        leaf2 = jax.tree_util.tree_leaves(p2.unet_params)[0]
        assert np.array_equal(np.asarray(leaf1), np.asarray(leaf2))
        registry.clear_pipeline_cache()

    def test_pipeline_cached(self):
        a = registry.load_pipeline("x.safetensors")
        b = registry.load_pipeline("x.safetensors")
        assert a is b

    def test_jit_cache_lru_bounded(self, monkeypatch):
        """A resolution sweep must not leak one executable per shape
        (VERDICT r2 weak #8): the per-pipeline jit cache is LRU-capped."""
        monkeypatch.setenv("DTPU_JIT_CACHE_CAP", "4")
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("lru.safetensors")
        assert p._jit_cache_cap == 4
        made = []
        for size in (8, 16, 24, 32, 40, 48):  # the sample() static-key shape
            key = ("sample", "euler", "karras", 2, 7.5, 1.0, True, False,
                   (1, size, size, 4), (1, 77, 64))
            made.append(p._cache_get_or_make(key, object))
        assert len(p._jit_cache) <= 4
        # oldest entries evicted, newest retained; a hit refreshes recency
        assert p._cache_get_or_make(key, object) is made[-1]
        first_key = ("sample", "euler", "karras", 2, 7.5, 1.0, True, False,
                     (1, 8, 8, 4), (1, 77, 64))
        assert p._cache_get_or_make(first_key, object) is not made[0]
        registry.clear_pipeline_cache()

    def test_vae_decode_tiled(self):
        """Tiled decode covers the canvas seamlessly: exact passthrough when
        one tile suffices; close to the full decode elsewhere (per-tile
        GroupNorm stats differ slightly — the feather hides seams)."""
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("tiled.safetensors")
        ds = p.family.vae.downscale
        lat = jnp.asarray(np.random.default_rng(0).standard_normal(
            (1, 24, 24, 4)).astype(np.float32))
        full = np.asarray(p.vae_decode(lat))
        # tile >= image -> identical path
        same = np.asarray(p.vae_decode_tiled(lat, tile_size=24 * ds))
        np.testing.assert_allclose(same, full, atol=1e-6)
        tiled = np.asarray(p.vae_decode_tiled(lat, tile_size=16 * ds,
                                              overlap=4 * ds))
        assert tiled.shape == full.shape
        assert np.isfinite(tiled).all()
        # same decoder, overlapping tiles: strongly correlated with full
        cc = np.corrcoef(tiled.ravel(), full.ravel())[0, 1]
        assert cc > 0.98, cc
        registry.clear_pipeline_cache()

    def test_encode_prompt_shapes(self):
        p = registry.load_pipeline("x.safetensors")
        ctx, pooled = p.encode_prompt(["a cat", "a dog"])
        assert ctx.shape == (2, 77, 64)
        assert pooled.shape == (2, 64)

    def test_full_txt2img_sample(self):
        """End-to-end tiny pipeline: prompt -> latents -> sample -> decode."""
        p = registry.load_pipeline("x.safetensors")
        ctx, _ = p.encode_prompt(["a cat"])
        unc, _ = p.encode_prompt([""])
        lat = jnp.zeros((1, 8, 8, 4))
        seeds = jnp.asarray([42], jnp.uint32)
        out = p.sample(lat, ctx, unc, seeds, steps=3, cfg=3.0,
                       sampler_name="euler", scheduler="normal")
        assert out.shape == lat.shape
        assert np.all(np.isfinite(np.asarray(out)))
        img = p.vae_decode(out)
        assert img.shape == (1, 16, 16, 3)

    def test_seed_determinism_and_divergence(self):
        p = registry.load_pipeline("x.safetensors")
        ctx, _ = p.encode_prompt(["a cat"])
        unc, _ = p.encode_prompt([""])
        lat = jnp.zeros((2, 8, 8, 4))
        s_a = jnp.asarray([7, 8], jnp.uint32)
        a = p.sample(lat, ctx[:1].repeat(2, 0), unc[:1].repeat(2, 0), s_a,
                     steps=2, cfg=1.0, sampler_name="euler_ancestral",
                     scheduler="normal")
        b = p.sample(lat, ctx[:1].repeat(2, 0), unc[:1].repeat(2, 0), s_a,
                     steps=2, cfg=1.0, sampler_name="euler_ancestral",
                     scheduler="normal")
        assert np.array_equal(np.asarray(a), np.asarray(b))
        # the two samples inside the batch differ (different seeds)
        assert not np.allclose(np.asarray(a)[0], np.asarray(a)[1])


class TestUpscaler:
    def test_rrdb_scale(self):
        net = RRDBNet(TINY_RRDB_CONFIG)
        x = jnp.zeros((1, 8, 8, 3))
        params = net.init(jax.random.PRNGKey(0), x)["params"]
        out = net.apply({"params": params}, x)
        assert out.shape == (1, 16, 16, 3)

    def test_registry_upscaler_virtual(self):
        net, params, scale = registry.load_upscaler("tiny_2x.pth")
        assert scale == 2
        out = net.apply({"params": params}, jnp.zeros((1, 4, 4, 3)))
        assert out.shape == (1, 8, 8, 3)


class TestSD21Family:
    def test_detect_family_stability_names(self):
        cases = {
            "v2-1_768-ema-pruned.safetensors": "sd21",
            "v2-1_512-ema-pruned.ckpt": "sd21_base",
            "512-base-ema.ckpt": "sd21_base",  # official SD2.0-base name
            "sd2_vpred_custom.safetensors": "sd21",
            "v1-5-pruned-emaonly.safetensors": "sd15",
            "sd_xl_base_1.0.safetensors": "sdxl",
            # SD1.5-architecture community finetunes with v2 in the NAME
            # must not be misrouted to the sd21 converter
            "anything-v2.ckpt": "sd15",
            "counterfeit-v2.5.safetensors": "sd15",
        }
        env = os.environ.pop(registry.FAMILY_ENV, None)
        try:
            for name, fam in cases.items():
                assert registry.detect_family(name) == fam, name
        finally:
            if env is not None:
                os.environ[registry.FAMILY_ENV] = env

    def test_sd21_configs(self):
        fam = registry.FAMILIES["sd21"]
        assert fam.unet.prediction_type == "v"
        assert fam.unet.context_dim == 1024
        assert fam.unet.use_linear_in_transformer
        assert fam.clips[0].layout == "openclip"
        assert fam.clips[0].output_layer == -2
        assert registry.FAMILIES["sd21_base"].unet.prediction_type == "eps"

    def test_openclip_family_pads_with_zero(self):
        """SD2.x pad convention: OpenCLIP towers pad with 0 after EOT;
        CLIP towers (SD1.x/SDXL) pad with EOT — ComfyUI tokenizer parity."""
        import dataclasses as dc
        fam_oc = dc.replace(
            registry.FAMILIES["tiny"], name="tiny_oc",
            clips=(dc.replace(TINY_CLIP_CONFIG, layout="openclip"),))
        pipe_oc = registry.DiffusionPipeline("toc", fam_oc, {}, [{}], {})
        ids, _ = pipe_oc.tokenizer.encode("hello")
        assert ids[-1] == 0
        pipe_clip = registry.DiffusionPipeline(
            "tcl", registry.FAMILIES["tiny"], {}, [{}], {})
        ids2, _ = pipe_clip.tokenizer.encode("hello")
        assert ids2[-1] == pipe_clip.tokenizer.end

    def test_v_prediction_pipeline_samples(self):
        """End-to-end sample through a v-prediction pipeline at tiny scale:
        the family's prediction_type must reach the denoiser (finite,
        deterministic output differing from the eps pipeline's)."""
        import dataclasses as dc
        fam_v = dc.replace(
            registry.FAMILIES["tiny"], name="tiny_v",
            unet=dc.replace(TINY_CONFIG, prediction_type="v"))
        seed = 7
        rng = jax.random.PRNGKey(seed)
        x = jnp.zeros((1, 8, 8, 4))
        ts = jnp.zeros((1,))
        ctx = jnp.zeros((1, 77, TINY_CONFIG.context_dim))
        unet_p = jax.jit(UNet(fam_v.unet).init)(rng, x, ts, ctx)["params"]
        clip_p = CLIPTextModel(fam_v.clips[0]).init(
            rng, jnp.zeros((1, 77), jnp.int32))["params"]
        vae_p = VAE(fam_v.vae).init(rng, jnp.zeros((1, 16, 16, 3)))["params"]

        def build(fam):
            return registry.DiffusionPipeline(
                "vtest", fam, unet_p, [clip_p], vae_p,
                prediction_type=fam.unet.prediction_type)

        pipe_v = build(fam_v)
        ctx_b, _ = pipe_v.encode_prompt(["x"])
        seeds = np.asarray([3], np.uint64)
        out_v = pipe_v.sample(x, ctx_b, ctx_b, seeds, steps=2, cfg=1.0,
                              sampler_name="euler", scheduler="normal")
        assert np.isfinite(np.asarray(out_v)).all()

        pipe_e = build(registry.FAMILIES["tiny"])
        out_e = pipe_e.sample(x, ctx_b, ctx_b, seeds, steps=2, cfg=1.0,
                              sampler_name="euler", scheduler="normal")
        assert not np.allclose(np.asarray(out_v), np.asarray(out_e)), \
            "v-pred pipeline produced identical output to eps — the " \
            "prediction_type never reached the denoiser"


class TestAdvancedOps:
    """CLIPSetLastLayer / VAELoader / KSamplerAdvanced (ComfyUI schemas)."""

    def _pipe(self):
        return registry.load_pipeline("adv-ops.ckpt")

    def test_clip_set_last_layer(self, monkeypatch):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        from comfyui_distributed_tpu.parallel import mesh
        # a multi-device runtime that an earlier file of this xdist worker
        # left live lays the base's and the clone's weights out apart, and
        # "shared, not copied" below is about the unsharded trees (which
        # files share a worker moves with every file a PR adds: PR 49)
        monkeypatch.setattr(mesh, "_runtime", None)
        pipe = self._pipe()
        op = get_op("CLIPSetLastLayer")
        (skip2,) = op.execute(OpContext(), pipe, -2)
        assert skip2 is not pipe
        assert all(c.output_layer == -2 for c in skip2.family.clips)
        c0, _ = pipe.encode_prompt(["hello"])
        c2, _ = skip2.encode_prompt(["hello"])
        assert not np.allclose(np.asarray(c0), np.asarray(c2))
        # weights are shared, not copied
        assert skip2.clip_params is pipe.clip_params
        # -1 (the default) is the identity
        (same,) = op.execute(OpContext(), pipe, -1)
        assert same is pipe
        # derived pipelines are cached by (base, tag)
        (again,) = op.execute(OpContext(), pipe, -2)
        assert again is skip2

    def test_vae_loader_virtual_and_file_forms(self, tmp_path):
        from comfyui_distributed_tpu.models import checkpoints as ckpt
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        op = get_op("VAELoader")
        (v1,) = op.execute(OpContext(), "fancy-vae.safetensors")
        (v2,) = op.execute(OpContext(), "fancy-vae.safetensors")
        assert v1 is v2                       # cached
        lat = jnp.zeros((1, 4, 4, v1.family.latent_channels))
        img = v1.vae_decode(lat)
        ds = v1.family.vae.downscale
        assert img.shape == (1, 4 * ds, 4 * ds, 3)

        # file forms: bare VAE keys and full-checkpoint prefix both load
        pipe = self._pipe()
        sd_prefixed = {k: v for k, v in ckpt.export_state_dict(
            pipe.unet_params, pipe.clip_params, pipe.vae_params,
            pipe.family).items() if k.startswith("first_stage_model.")}
        sd_bare = {k[len("first_stage_model."):]: v
                   for k, v in sd_prefixed.items()}
        # save through the framework helper: raw safetensors save_file
        # silently serializes non-contiguous views (export transposes)
        # as their underlying buffer bytes — corrupt weights
        ckpt.save_state_dict(sd_prefixed,
                             str(tmp_path / "prefixed.safetensors"))
        ckpt.save_state_dict(sd_bare, str(tmp_path / "bare.safetensors"))
        ctx = OpContext(models_dir=str(tmp_path))
        (vp,) = op.execute(ctx, "prefixed.safetensors")
        (vb,) = op.execute(ctx, "bare.safetensors")
        z = jnp.asarray(np.random.default_rng(0).standard_normal(
            (1, 4, 4, pipe.family.latent_channels)), jnp.float32)
        np.testing.assert_allclose(np.asarray(vp.vae_decode(z)),
                                   np.asarray(vb.vae_decode(z)),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(vp.vae_decode(z)),
                                   np.asarray(pipe.vae_decode(z)),
                                   rtol=1e-5, atol=1e-6)

    def test_ksampler_advanced_window_composition(self):
        """Two chained windows (0..3 with leftover noise, 3..6 without
        added noise) must reproduce the single 6-step run — ComfyUI's
        staged-sampling contract for deterministic samplers."""
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        pipe = self._pipe()
        ctx_arr, _ = pipe.encode_prompt(["a fox"])
        neg_arr, _ = pipe.encode_prompt([""])
        from comfyui_distributed_tpu.ops.base import Conditioning
        pos = Conditioning(context=ctx_arr, pooled=None)
        neg = Conditioning(context=neg_arr, pooled=None)
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        op = get_op("KSamplerAdvanced")
        octx = OpContext()

        (full,) = op.execute(octx, pipe, "enable", 55, 6, 1.5, "euler",
                             "normal", pos, neg, lat, 0, 10000, "disable")
        (s1,) = op.execute(octx, pipe, "enable", 55, 6, 1.5, "euler",
                           "normal", pos, neg, lat, 0, 3, "enable")
        (s2,) = op.execute(octx, pipe, "disable", 55, 6, 1.5, "euler",
                           "normal", pos, neg,
                           {"samples": np.asarray(s1["samples"])},
                           3, 10000, "disable")
        np.testing.assert_allclose(np.asarray(s2["samples"]),
                                   np.asarray(full["samples"]),
                                   rtol=1e-4, atol=1e-4)
        # the mid-point is a genuine intermediate, not the final result
        assert not np.allclose(np.asarray(s1["samples"]),
                               np.asarray(full["samples"]), atol=1e-3)


class TestUtilityOps:
    """Conditioning combinators, latent batch utilities, CheckpointSave."""

    def _pipe(self):
        return registry.load_pipeline("util-ops.ckpt")

    def test_conditioning_concat_average_combine(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        a = Conditioning(context=jnp.ones((1, 77, 64)),
                         pooled=jnp.ones((1, 64)))
        b = Conditioning(context=jnp.zeros((1, 77, 64)),
                         pooled=jnp.zeros((1, 64)))
        octx = OpContext()
        (cat,) = get_op("ConditioningConcat").execute(octx, a, b)
        assert cat.context.shape == (1, 154, 64)
        (avg,) = get_op("ConditioningAverage").execute(octx, a, b, 0.25)
        np.testing.assert_allclose(np.asarray(avg.context),
                                   np.full((1, 77, 64), 0.25), atol=1e-6)
        np.testing.assert_allclose(np.asarray(avg.pooled),
                                   np.full((1, 64), 0.25), atol=1e-6)
        # Combine bundles BOTH entries for a stacked sample-time eval
        # (true ComfyUI semantics — no longer the average approximation)
        (comb,) = get_op("ConditioningCombine").execute(octx, a, b)
        np.testing.assert_array_equal(np.asarray(comb.context),
                                      np.asarray(a.context))
        assert len(comb.siblings) == 1
        np.testing.assert_array_equal(np.asarray(comb.siblings[0].context),
                                      np.asarray(b.context))
        # combine of combines flattens
        (comb2,) = get_op("ConditioningCombine").execute(octx, comb, a)
        assert len(comb2.siblings) == 2

    def test_repeat_and_from_batch(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        octx = OpContext()
        lat = {"samples": np.arange(2 * 4 * 4 * 4, dtype=np.float32)
               .reshape(2, 4, 4, 4), "local_batch": 2, "fanout": 1}
        (rep,) = get_op("RepeatLatentBatch").execute(octx, lat, 3)
        assert rep["samples"].shape == (6, 4, 4, 4)
        assert rep["local_batch"] == 6
        np.testing.assert_array_equal(rep["samples"][2:4],
                                      lat["samples"])
        (sel,) = get_op("LatentFromBatch").execute(octx, lat, 1, 1)
        assert sel["samples"].shape == (1, 4, 4, 4)
        np.testing.assert_array_equal(sel["samples"][0], lat["samples"][1])
        # out-of-range clamps instead of crashing
        (sel2,) = get_op("LatentFromBatch").execute(octx, lat, 5, 9)
        assert sel2["samples"].shape == (1, 4, 4, 4)

    def test_repeat_latent_batch_keeps_replica_blocks(self):
        """A fanned batch is replica-major: repeating must stay WITHIN
        each replica's contiguous block, or downstream seed fold-ins and
        the collector's ordering attribute latents to the wrong replica."""
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        lat = np.stack([np.full((4, 4, 4), float(r)) for r in range(2)])
        d = {"samples": lat, "local_batch": 1, "fanout": 2}
        (rep,) = get_op("RepeatLatentBatch").execute(OpContext(), d, 2)
        assert rep["samples"].shape == (4, 4, 4, 4)
        assert rep["local_batch"] == 2 and rep["fanout"] == 2
        # block layout: [r0, r0, r1, r1] — NOT [r0, r1, r0, r1]
        got = rep["samples"][:, 0, 0, 0].tolist()
        assert got == [0.0, 0.0, 1.0, 1.0], got

    def test_conditioning_average_mismatched_lengths(self):
        """ComfyUI pads the shorter cond_from with zeros; pooled falls
        back to cond_from's when cond_to has none."""
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        a = Conditioning(context=jnp.ones((1, 154, 64)), pooled=None)
        b = Conditioning(context=jnp.ones((1, 77, 64)),
                         pooled=jnp.full((1, 64), 3.0))
        (avg,) = get_op("ConditioningAverage").execute(
            OpContext(), a, b, 0.5)
        assert avg.context.shape == (1, 154, 64)
        out = np.asarray(avg.context)
        np.testing.assert_allclose(out[:, :77], 1.0, atol=1e-6)
        np.testing.assert_allclose(out[:, 77:], 0.5, atol=1e-6)  # zero pad
        np.testing.assert_allclose(np.asarray(avg.pooled), 3.0, atol=1e-6)

    def test_latent_from_batch_slices_noise_mask(self):
        """ADVICE r3: the mask travels with its rows through a batch
        slice — dropping it would silently resample the whole image."""
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        lat = {"samples": np.zeros((4, 4, 4, 4), np.float32),
               "noise_mask": np.stack([np.full((8, 8), float(i))
                                       for i in range(4)])}
        (sel,) = get_op("LatentFromBatch").execute(OpContext(), lat, 2, 2)
        assert "noise_mask" in sel
        np.testing.assert_array_equal(
            np.asarray(sel["noise_mask"])[:, 0, 0], [2.0, 3.0])
        # a single mask broadcasts: forwarded untouched
        lat1 = {"samples": np.zeros((4, 4, 4, 4), np.float32),
                "noise_mask": np.ones((1, 8, 8), np.float32)}
        (sel1,) = get_op("LatentFromBatch").execute(OpContext(), lat1, 1, 2)
        assert np.asarray(sel1["noise_mask"]).shape[0] == 1
        # short (but >1) mask cycles the batch before slicing, ComfyUI-style
        lat2 = {"samples": np.zeros((4, 4, 4, 4), np.float32),
                "noise_mask": np.stack([np.full((8, 8), float(i))
                                        for i in range(2)])}
        (sel2,) = get_op("LatentFromBatch").execute(OpContext(), lat2, 2, 2)
        np.testing.assert_array_equal(
            np.asarray(sel2["noise_mask"])[:, 0, 0], [0.0, 1.0])

    def test_checkpoint_save_rejects_escaping_prefix(self, tmp_path):
        """ADVICE r3: a '../..'-style filename_prefix must not write
        outside the output root."""
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        pipe = self._pipe()
        out = tmp_path / "out"
        out.mkdir()
        octx = OpContext(output_dir=str(out))
        with pytest.raises(ValueError, match="escapes"):
            get_op("CheckpointSave").execute(octx, pipe, pipe, pipe,
                                             "../escaped/evil")
        assert not (tmp_path / "escaped").exists()
        # SaveImage shares the guard (same user-supplied prefix join)
        img = np.zeros((1, 8, 8, 3), np.float32)
        with pytest.raises(ValueError, match="escapes"):
            get_op("SaveImage").execute(octx, img, "../escaped/evil")
        assert not (tmp_path / "escaped").exists()
        # a legitimate subdirectory prefix still works
        get_op("SaveImage").execute(octx, img, "subdir/ok")
        assert (out / "subdir" / "ok_00000.png").exists()

    def test_checkpoint_save_round_trips(self, tmp_path):
        from comfyui_distributed_tpu.models import checkpoints as ckpt
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        pipe = self._pipe()
        octx = OpContext(output_dir=str(tmp_path))
        get_op("CheckpointSave").execute(octx, pipe, pipe, pipe,
                                         "checkpoints/exported")
        path = tmp_path / "checkpoints" / "exported.safetensors"
        assert path.exists()
        sd = ckpt.load_state_dict(str(path))
        ref = ckpt.export_state_dict(pipe.unet_params, pipe.clip_params,
                                     pipe.vae_params, pipe.family)
        for k, v in ref.items():
            np.testing.assert_array_equal(sd[k], np.asarray(v), err_msg=k)
        # and the file round-trips back into IDENTICAL param trees
        u2, c2, v2 = ckpt.convert_state_dict(sd, pipe.family)

        def trees_equal(a, b):
            fa = jax.tree_util.tree_leaves_with_path(a)
            fb = dict(jax.tree_util.tree_leaves_with_path(b))
            assert len(fa) == len(fb)
            for path_k, leaf in fa:
                np.testing.assert_array_equal(
                    np.asarray(leaf), np.asarray(fb[path_k]),
                    err_msg=str(path_k))

        trees_equal(u2, pipe.unet_params)
        trees_equal(c2[0], pipe.clip_params[0])
        trees_equal(v2, pipe.vae_params)


class TestInpainting:
    """noise_mask sampling (KSamplerX0Inpaint semantics), mask ops."""

    def _pipe(self):
        return registry.load_pipeline("inpaint.ckpt")

    def test_unmasked_region_anchored_to_source(self):
        """mask=1 resamples; mask=0 returns the source latent EXACTLY
        (the final output is re-anchored to the clean source there)."""
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        pipe = self._pipe()
        rng = np.random.default_rng(5)
        src = rng.standard_normal((1, 8, 8, 4)).astype(np.float32)
        mask = np.zeros((1, 16, 16), np.float32)   # image res (downscale 2)
        mask[:, :, 8:] = 1.0                       # right half inpainted
        lat = {"samples": src, "noise_mask": mask}
        ctx_arr, _ = pipe.encode_prompt(["replace"])
        from comfyui_distributed_tpu.ops.base import Conditioning
        pos = Conditioning(context=ctx_arr, pooled=None)
        (out,) = get_op("KSampler").execute(
            OpContext(), pipe, 11, 4, 1.5, "euler", "normal", pos, pos,
            lat, 1.0)
        o = np.asarray(out["samples"])
        np.testing.assert_array_equal(o[:, :, :4], src[:, :, :4])  # kept
        assert not np.allclose(o[:, :, 4:], src[:, :, 4:])         # redone
        assert out["noise_mask"] is mask  # mask stays on the latent

    def test_mask_wrapper_propagates_cfg_pp_side_channel(self, monkeypatch):
        """ADVICE r4 (medium): the inpaint mask wrapper must re-expose
        the CFG denoiser's ``last_uncond`` side-channel — otherwise CFG++
        samplers under a noise_mask fall back to the CFG result and
        silently degrade to plain-euler semantics.  A probe sampler
        reads the side-channel exactly like the CFG++ samplers do
        (getattr off the callable it was handed) and returns
        ``last_uncond - denoised``: zero everywhere pre-fix (fallback),
        nonzero INSIDE the mask post-fix (cfg!=1, cond!=uncond), and
        source-anchored outside either way."""
        from comfyui_distributed_tpu.models import samplers as smp_mod
        pipe = self._pipe()

        def probe_sampler(model, x, sigmas, extra_args=None, keys=None):
            den = model(x, sigmas[0], **(extra_args or {}))
            lu = getattr(model, "last_uncond", den)
            return lu - den

        monkeypatch.setitem(smp_mod.SAMPLERS, "_lu_probe", probe_sampler)
        rng = np.random.default_rng(7)
        src = rng.standard_normal((1, 8, 8, 4)).astype(np.float32)
        mask = np.zeros((1, 8, 8, 1), np.float32)
        mask[:, :, 4:] = 1.0                  # latent-res mask
        ctx_c, _ = pipe.encode_prompt(["a cat"])
        ctx_u, _ = pipe.encode_prompt([""])
        out = np.asarray(pipe.sample(
            jnp.asarray(src), ctx_c, ctx_u,
            np.asarray([11], np.uint64), steps=3, cfg=7.5,
            sampler_name="_lu_probe", scheduler="normal",
            noise_mask=jnp.asarray(mask)))
        # outside the mask the final re-anchor returns the source
        np.testing.assert_array_equal(out[:, :, :4], src[:, :, :4])
        # inside: uncond != cfg result -> the probe saw a REAL uncond
        assert np.abs(out[:, :, 4:]).max() > 1e-4, \
            "last_uncond side-channel lost by the mask wrapper"

    def test_no_mask_output_differs_everywhere(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        pipe = self._pipe()
        src = np.random.default_rng(6).standard_normal(
            (1, 8, 8, 4)).astype(np.float32)
        ctx_arr, _ = pipe.encode_prompt(["x"])
        pos = Conditioning(context=ctx_arr, pooled=None)
        (out,) = get_op("KSampler").execute(
            OpContext(), pipe, 11, 2, 1.5, "euler", "normal", pos, pos,
            {"samples": src}, 1.0)
        assert not np.allclose(np.asarray(out["samples"]), src)

    def test_set_latent_noise_mask_op(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32),
               "local_batch": 1, "fanout": 1}
        m = np.ones((16, 16), np.float32)
        (out,) = get_op("SetLatentNoiseMask").execute(OpContext(), lat, m)
        assert out["noise_mask"].shape == (1, 16, 16)
        assert out["local_batch"] == 1

    def test_set_mask_replaces_existing_mask(self):
        """A new mask must WIN over one already on the latent (forwarded
        by sampler outputs) — spread-order regression."""
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        old = np.zeros((1, 16, 16), np.float32)
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32),
               "noise_mask": old}
        new = np.ones((16, 16), np.float32)
        (out,) = get_op("SetLatentNoiseMask").execute(OpContext(), lat, new)
        assert out["noise_mask"].sum() == 16 * 16, "old mask survived"

    def test_masked_add_noise_disable_keeps_source_unnoised(self):
        """Stage-2 inpaint (add_noise=disable): the protected region's
        blend must use ZERO noise — the input latent already is the noised
        state (ComfyUI disable_noise semantics)."""
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        pipe = self._pipe()
        rng = np.random.default_rng(8)
        src = rng.standard_normal((1, 8, 8, 4)).astype(np.float32)
        mask = np.zeros((1, 16, 16), np.float32)
        mask[:, :, 8:] = 1.0
        ctx_arr, _ = pipe.encode_prompt(["x"])
        pos = Conditioning(context=ctx_arr, pooled=None)
        lat = {"samples": src, "noise_mask": mask}
        (out,) = get_op("KSamplerAdvanced").execute(
            OpContext(), pipe, "disable", 11, 4, 1.5, "euler", "normal",
            pos, pos, lat, 2, 10000, "disable")
        o = np.asarray(out["samples"])
        np.testing.assert_array_equal(o[:, :, :4], src[:, :, :4])
        assert not np.allclose(o[:, :, 4:], src[:, :, 4:])

    def test_vae_encode_for_inpaint(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        pipe = self._pipe()
        img = np.full((1, 16, 16, 3), 0.9, np.float32)
        mask = np.zeros((1, 16, 16), np.float32)
        mask[:, 6:10, 6:10] = 1.0
        (out,) = get_op("VAEEncodeForInpaint").execute(
            OpContext(), img, pipe, mask, 2)
        assert "noise_mask" in out
        # grown mask covers MORE area than the input mask
        assert out["noise_mask"].sum() > mask.sum()
        ds = pipe.family.vae.downscale
        assert out["samples"].shape == (1, 16 // ds, 16 // ds, 4)

    def test_mask_survives_latent_ops(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32),
               "noise_mask": np.ones((1, 16, 16), np.float32)}
        (up,) = get_op("LatentUpscaleBy").execute(OpContext(), lat,
                                                  "bilinear", 2.0)
        assert "noise_mask" in up


class TestTiledSR:
    def test_tiled_sr_matches_whole_image(self, monkeypatch):
        """Above the pixel threshold the SR net runs in overlapping
        feathered tiles; result must closely match the whole-image pass
        (identical away from seams — RRDB convs are local, unlike the
        VAE's global attention)."""
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        from comfyui_distributed_tpu.ops.basic import ImageUpscaleWithModel
        ul = get_op("UpscaleModelLoader").execute(
            OpContext(), "2x_tiny_sr.pth")[0]
        img = np.random.default_rng(4).uniform(
            0, 1, (1, 48, 64, 3)).astype(np.float32)
        op = get_op("ImageUpscaleWithModel")
        (whole,) = op.execute(OpContext(), ul, img)
        monkeypatch.setattr(ImageUpscaleWithModel, "TILE_THRESHOLD", 512)
        monkeypatch.setattr(ImageUpscaleWithModel, "TILE", 32)
        monkeypatch.setattr(ImageUpscaleWithModel, "OVERLAP", 8)
        (tiled,) = op.execute(OpContext(), ul, img)
        assert tiled.shape == whole.shape
        # interior agreement: small RRDB receptive-field halo at seams
        diff = np.abs(np.asarray(tiled) - np.asarray(whole))
        assert np.median(diff) < 1e-4, float(np.median(diff))
        assert np.mean(diff) < 0.02, float(np.mean(diff))


class TestBf16WeightStorage:
    def test_flag_casts_unet_clip_not_vae(self, monkeypatch):
        """DTPU_BF16_WEIGHTS: UNet/CLIP weight storage drops to bf16 (on
        TPU, fp32 storage doubles HBM weight traffic per step and SDXL
        fp32 wouldn't fit a 16 GB v5e); the VAE stays fp32.  Sampling
        still produces finite output with bf16-stored params."""
        import jax.numpy as jnp
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        monkeypatch.setenv("DTPU_BF16_WEIGHTS", "1")
        registry.clear_pipeline_cache()
        try:
            pipe = registry.load_pipeline("bf16-flag.ckpt",
                                          family_name="tiny")
            u = jax.tree_util.tree_leaves(pipe.unet_params)
            assert all(x.dtype == jnp.bfloat16 for x in u
                       if x.dtype in (jnp.float32, jnp.bfloat16))
            v = jax.tree_util.tree_leaves(pipe.vae_params)
            assert any(x.dtype == jnp.float32 for x in v)
            ctx_arr, _ = pipe.encode_prompt(["x"])
            pos = Conditioning(context=ctx_arr, pooled=None)
            lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
            (out,) = get_op("KSampler").execute(
                OpContext(), pipe, 3, 2, 1.5, "euler", "normal",
                pos, pos, lat, 1.0)
            assert np.isfinite(np.asarray(out["samples"])).all()
        finally:
            registry.clear_pipeline_cache()

    def test_default_off_for_tiny(self, monkeypatch):
        """tiny (fp32 module, deterministic CPU tests) keeps fp32 storage
        by default — only the real bf16-compute families opt in."""
        monkeypatch.delenv("DTPU_BF16_WEIGHTS", raising=False)
        registry.clear_pipeline_cache()
        pipe = registry.load_pipeline("fp32-default.ckpt",
                                      family_name="tiny")
        import jax.numpy as jnp
        u = jax.tree_util.tree_leaves(pipe.unet_params)
        assert all(x.dtype == jnp.float32 for x in u)
        registry.clear_pipeline_cache()


class TestSaveImageCounters:
    def test_second_run_does_not_overwrite(self, tmp_path):
        """ComfyUI save semantics: counters continue across runs — a
        re-queued workflow appends new files instead of clobbering."""
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        img = np.zeros((2, 8, 8, 3), np.float32)
        octx = OpContext(output_dir=str(tmp_path))
        get_op("SaveImage").execute(octx, img, "run")
        get_op("SaveImage").execute(octx, img + 0.5, "run")
        names = sorted(p.name for p in tmp_path.glob("run_*.png"))
        assert names == ["run_00000.png", "run_00001.png",
                         "run_00002.png", "run_00003.png"]


class TestVAEEncodeTiled:
    def test_tiled_encode_close_to_full(self):
        """Latent-space feathered blend of pixel tiles tracks the
        one-shot encode (per-tile GroupNorm stats differ slightly, like
        the tiled decode); one-tile inputs take the exact path."""
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("enc-tiled.ckpt")
        ds = p.family.vae.downscale
        img = jnp.asarray(np.random.default_rng(7).uniform(
            0, 1, (1, 48, 48, 3)).astype(np.float32))
        full = np.asarray(p.vae_encode(img))
        same = np.asarray(p.vae_encode_tiled(img, tile_size=48,
                                             overlap=8))
        np.testing.assert_allclose(same, full, atol=1e-6)
        tiled = np.asarray(p.vae_encode_tiled(img, tile_size=16 * ds,
                                              overlap=4 * ds))
        assert tiled.shape == full.shape
        assert np.isfinite(tiled).all()
        cc = np.corrcoef(tiled.ravel(), full.ravel())[0, 1]
        assert cc > 0.98, cc
        registry.clear_pipeline_cache()

    def test_op_fans_out_like_vaeencode(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        p = registry.load_pipeline("enc-tiled-op.ckpt")
        img = np.random.default_rng(8).uniform(
            0, 1, (1, 32, 32, 3)).astype(np.float32)
        octx = OpContext()
        octx.fanout = 4
        (lat,) = get_op("VAEEncodeTiled").execute(octx, img, p,
                                                  tile_size=16, overlap=4)
        assert lat["samples"].shape[0] == 4    # batch * fanout
        assert lat["fanout"] == 4 and lat["local_batch"] == 1
        # all replicas hold the SAME source latent (img2img sweep)
        s = np.asarray(lat["samples"])
        np.testing.assert_array_equal(s[0], s[3])


class TestImagePadForOutpaint:
    def test_pad_mask_and_feather(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        img = np.ones((1, 32, 32, 3), np.float32) * 0.25
        (out, mask) = get_op("ImagePadForOutpaint").execute(
            OpContext(), img, left=0, top=0, right=16, bottom=0,
            feathering=8)
        assert out.shape == (1, 32, 48, 3)
        assert mask.shape == (32, 48)
        # original content preserved; new area mid-gray
        np.testing.assert_array_equal(out[:, :, :32], img)
        np.testing.assert_allclose(out[:, :, 32:], 0.5)
        # mask: 1 over the new area, quadratic feather into the original
        np.testing.assert_allclose(mask[:, 32:], 1.0)
        assert mask[16, 31] == pytest.approx((7 / 8) ** 2)  # d=1 to edge
        assert mask[16, 25] == pytest.approx((1 / 8) ** 2)  # d=7, band rim
        assert mask[16, 23] == 0.0     # d=9 >= feathering: outside band
        assert mask[16, 0] == 0.0      # far side untouched (not extended)
        assert mask[0, 0] == 0.0       # unextended top edge: no feather

    def test_feeds_inpaint_encode(self):
        """Outpaint chain: pad -> VAEEncodeForInpaint consumes the pair
        (the mask rides along as noise_mask)."""
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("outpaint.ckpt")
        img = np.ones((1, 32, 32, 3), np.float32) * 0.25
        (out, mask) = get_op("ImagePadForOutpaint").execute(
            OpContext(), img, right=16, feathering=4)
        (lat,) = get_op("VAEEncodeForInpaint").execute(
            OpContext(), out, p, mask, grow_mask_by=0)
        assert "noise_mask" in lat
        ds = p.family.vae.downscale
        assert lat["samples"].shape[1:3] == (32 // ds, 48 // ds)
        registry.clear_pipeline_cache()


class TestInpaintEncodeFanout:
    def test_fanned_pixels_pass_through(self):
        """ADVICE-style regression: already-fanned pixels into
        VAEEncodeForInpaint must pass through, not re-tile (the
        fan-out-squaring bug the shared helper fixed for VAEEncode)."""
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        from comfyui_distributed_tpu.ops.basic import ImageBatch
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("inp-fan.ckpt")
        img = ImageBatch(np.full((4, 16, 16, 3), 0.5, np.float32),
                         local_batch=1, fanout=4)
        octx = OpContext()
        octx.fanout = 4
        (lat,) = get_op("VAEEncodeForInpaint").execute(
            octx, img, p, np.ones((16, 16), np.float32), 0)
        assert lat["samples"].shape[0] == 4          # NOT 16
        assert lat["fanout"] == 4 and lat["local_batch"] == 1
        assert "noise_mask" in lat
        registry.clear_pipeline_cache()


class TestRegionalPrompting:
    """ConditioningSetArea/SetMask + Combine -> stacked multi-cond eval.

    One-step oracle: with a single denoise step, the blended output's
    left half must match the left half of a run conditioned only on
    prompt A (same seed, same noise, same uncond — the blend is
    per-pixel linear in the per-entry denoised predictions; tolerance
    covers batch-size-dependent XLA reduction order)."""

    def _run(self, p, pos, neg, seed=11):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        lat = {"samples": np.zeros((1, 16, 16, 4), np.float32)}
        (out,) = get_op("KSampler").execute(
            OpContext(), p, seed, 1, 4.0, "euler", "normal", pos, neg,
            lat, 1.0)
        return np.asarray(out["samples"])

    def test_one_step_halves_match_single_cond_runs(self):
        from comfyui_distributed_tpu.ops.base import Conditioning, get_op
        from comfyui_distributed_tpu.ops.base import OpContext
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("region.ckpt")
        ca, _ = p.encode_prompt(["a red square"])
        cb, _ = p.encode_prompt(["a blue circle"])
        cn, _ = p.encode_prompt([""])
        A = Conditioning(context=ca, pooled=None)
        B = Conditioning(context=cb, pooled=None)
        N = Conditioning(context=cn, pooled=None)
        octx = OpContext()
        (setA,) = get_op("ConditioningSetAreaPercentage").execute(
            octx, A, width=0.5, height=1.0, x=0.0, y=0.0)
        (setB,) = get_op("ConditioningSetAreaPercentage").execute(
            octx, B, width=0.5, height=1.0, x=0.5, y=0.0)
        (comb,) = get_op("ConditioningCombine").execute(octx, setA, setB)

        blended = self._run(p, comb, N)
        only_a = self._run(p, A, N)
        only_b = self._run(p, B, N)
        assert not np.allclose(only_a, only_b)   # prompts actually differ
        # tolerance: the blended run's stacked batch (3 rows) and the
        # single runs (2 rows) take different XLA fusion paths — ULP-level
        # reduction-order noise, far below the prompt-difference signal
        np.testing.assert_allclose(blended[:, :, :8], only_a[:, :, :8],
                                   rtol=5e-4, atol=5e-4)
        np.testing.assert_allclose(blended[:, :, 8:], only_b[:, :, 8:],
                                   rtol=5e-4, atol=5e-4)
        registry.clear_pipeline_cache()

    def test_mask_node_and_multistep_finite(self):
        """SetMask with an image-res array mask through a multi-step
        sample: finite, differs from the single-cond run, and a
        full-coverage single mask equals the plain path exactly."""
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("region2.ckpt")
        ca, _ = p.encode_prompt(["meadow"])
        cb, _ = p.encode_prompt(["sky"])
        cn, _ = p.encode_prompt([""])
        A = Conditioning(context=ca, pooled=None)
        B = Conditioning(context=cb, pooled=None)
        N = Conditioning(context=cn, pooled=None)
        octx = OpContext()
        m = np.zeros((32, 32), np.float32)
        m[:16] = 1.0                                   # top half
        (setB,) = get_op("ConditioningSetMask").execute(octx, B, m, 0.8)
        (comb,) = get_op("ConditioningCombine").execute(octx, A, setB)
        out = self._run(p, comb, N, seed=3)
        assert np.isfinite(out).all()
        assert not np.allclose(out, self._run(p, A, N, seed=3))
        # full-coverage unit mask on a single entry == plain path
        ones = np.ones((32, 32), np.float32)
        (setA1,) = get_op("ConditioningSetMask").execute(octx, A, ones,
                                                         1.0)
        np.testing.assert_allclose(self._run(p, setA1, N, seed=3),
                                   self._run(p, A, N, seed=3),
                                   rtol=1e-6, atol=1e-6)
        registry.clear_pipeline_cache()


class TestRegionalPromptingFixups:
    """Review fixups: combined negatives, sibling controls, and
    Set-after-Combine must all reach sampling."""

    def _run(self, p, pos, neg, seed=21, steps=2):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (out,) = get_op("KSampler").execute(
            OpContext(), p, seed, steps, 4.0, "euler", "normal", pos,
            neg, lat, 1.0)
        return np.asarray(out["samples"])

    def test_combined_negative_reaches_sampling(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("multineg.ckpt")
        pos = Conditioning(context=p.encode_prompt(["castle"])[0])
        na = Conditioning(context=p.encode_prompt(["blurry"])[0])
        nb = Conditioning(context=p.encode_prompt(["cropped"])[0])
        (comb_n,) = get_op("ConditioningCombine").execute(OpContext(),
                                                          na, nb)
        combined = self._run(p, pos, comb_n)
        only_na = self._run(p, pos, na)
        assert np.isfinite(combined).all()
        # the second negative influences the output (pre-fix it was
        # silently dropped and combined == only_na)
        assert not np.allclose(combined, only_na)
        registry.clear_pipeline_cache()

    def test_sibling_control_reaches_sampling(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("sibctrl.ckpt")
        module, params = registry.load_controlnet("sib_cn.safetensors")
        params = jax.tree_util.tree_map(lambda a: a + 0.05, params)
        A = Conditioning(context=p.encode_prompt(["tree"])[0])
        B = Conditioning(context=p.encode_prompt(["river"])[0])
        N = Conditioning(context=p.encode_prompt([""])[0])
        octx = OpContext()
        hint = np.random.default_rng(2).uniform(
            0, 1, (1, 64, 64, 3)).astype(np.float32)
        (b_ctrl,) = get_op("ControlNetApply").execute(
            octx, B, (module, params), hint, 1.0)
        (comb,) = get_op("ConditioningCombine").execute(octx, A, b_ctrl)
        with_ctrl = self._run(p, comb, N)
        (comb_plain,) = get_op("ConditioningCombine").execute(octx, A, B)
        without = self._run(p, comb_plain, N)
        # the control on the SECOND combine input steers the sample
        # (pre-fix it was silently dropped and the runs were identical)
        assert not np.allclose(with_ctrl, without)
        registry.clear_pipeline_cache()

    def test_set_after_combine_masks_every_entry(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        octx = OpContext()
        A = Conditioning(context=jnp.ones((1, 7, 8)))
        B = Conditioning(context=jnp.zeros((1, 7, 8)))
        (comb,) = get_op("ConditioningCombine").execute(octx, A, B)
        m = np.ones((8, 8), np.float32)
        (masked,) = get_op("ConditioningSetMask").execute(octx, comb, m,
                                                          0.7)
        assert masked.area_mask is not None
        assert masked.area_strength == pytest.approx(0.7)
        assert all(s.area_mask is not None
                   and s.area_strength == pytest.approx(0.7)
                   for s in masked.siblings)

    def test_sibling_control_scoped_to_its_region(self):
        """A control on the right-region sibling must NOT steer the left
        region: per-entry strength blocks (one step; the left half of
        the blended output matches the control-free run)."""
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("scopectrl.ckpt")
        module, params = registry.load_controlnet("scope_cn.safetensors")
        params = jax.tree_util.tree_map(lambda a: a + 0.05, params)
        A = Conditioning(context=p.encode_prompt(["tree"])[0])
        B = Conditioning(context=p.encode_prompt(["river"])[0])
        N = Conditioning(context=p.encode_prompt([""])[0])
        octx = OpContext()
        hint = np.random.default_rng(4).uniform(
            0, 1, (1, 16, 16, 3)).astype(np.float32)
        (setA,) = get_op("ConditioningSetAreaPercentage").execute(
            octx, A, width=0.5, height=1.0, x=0.0, y=0.0)
        (b_ctrl,) = get_op("ControlNetApply").execute(
            octx, B, (module, params), hint, 1.0)
        (setB,) = get_op("ConditioningSetAreaPercentage").execute(
            octx, b_ctrl, width=0.5, height=1.0, x=0.5, y=0.0)
        (setB_plain,) = get_op("ConditioningSetAreaPercentage").execute(
            octx, B, width=0.5, height=1.0, x=0.5, y=0.0)
        (comb,) = get_op("ConditioningCombine").execute(octx, setA, setB)
        (comb0,) = get_op("ConditioningCombine").execute(octx, setA,
                                                         setB_plain)
        with_c = self._run(p, comb, N, steps=1)
        without = self._run(p, comb0, N, steps=1)
        # right region steered by the control...
        assert not np.allclose(with_c[:, :, 4:], without[:, :, 4:])
        # ...left region untouched (per-entry scale; ULP-level tolerance
        # for the batched-eval fusion differences)
        np.testing.assert_allclose(with_c[:, :, :4], without[:, :, :4],
                                   rtol=5e-4, atol=5e-4)
        registry.clear_pipeline_cache()

    def test_concat_and_average_apply_to_all_entries(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        octx = OpContext()
        A = Conditioning(context=jnp.ones((1, 7, 8)))
        B = Conditioning(context=jnp.zeros((1, 7, 8)))
        C = Conditioning(context=jnp.full((1, 5, 8), 2.0))
        (comb,) = get_op("ConditioningCombine").execute(octx, A, B)
        (cat,) = get_op("ConditioningConcat").execute(octx, comb, C)
        assert cat.context.shape == (1, 12, 8)
        assert len(cat.siblings) == 1
        assert cat.siblings[0].context.shape == (1, 12, 8)  # B + C too
        (avg,) = get_op("ConditioningAverage").execute(
            octx, comb, Conditioning(context=jnp.full((1, 7, 8), 4.0)),
            0.5)
        np.testing.assert_allclose(np.asarray(avg.context), 2.5)  # (1+4)/2
        np.testing.assert_allclose(np.asarray(avg.siblings[0].context),
                                   2.0)                           # (0+4)/2

    def test_controlnet_after_combine_steers_all_entries(self):
        """ControlNetApply downstream of Combine attaches to every entry
        (ComfyUI loops the cond list) — both regions steered."""
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        octx = OpContext()
        A = Conditioning(context=jnp.ones((1, 7, 8)))
        B = Conditioning(context=jnp.zeros((1, 7, 8)))
        (comb,) = get_op("ConditioningCombine").execute(octx, A, B)
        registry.clear_pipeline_cache()
        module, params = registry.load_controlnet("comb_cn.safetensors")
        hint = np.zeros((1, 16, 16, 3), np.float32)
        (ctl,) = get_op("ControlNetApply").execute(
            octx, comb, (module, params), hint, 0.9)
        assert ctl.control is not None
        assert all(s.control is not None and s.control[0][3] == 0.9
                   for s in ctl.siblings)      # 1-chain spec per entry
        registry.clear_pipeline_cache()


class TestTimestepRange:
    def test_schedule_percent_to_sigma(self):
        from comfyui_distributed_tpu.models import schedules as sch
        ds = sch.make_discrete_schedule()
        assert ds.percent_to_sigma(1.0) == 0.0
        assert ds.percent_to_sigma(0.0) > ds.sigmas[-1]    # ~inf
        mid = ds.percent_to_sigma(0.5)
        assert ds.sigmas[0] < mid < ds.sigmas[-1]

    def test_scheduled_prompts_change_sampling(self):
        """Two prompts scheduled over halves of the run produce a result
        different from either prompt alone; a [0,1] full-range schedule
        on a single prompt equals the plain path."""
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("sched.ckpt")
        A = Conditioning(context=p.encode_prompt(["oak tree"])[0])
        B = Conditioning(context=p.encode_prompt(["pine tree"])[0])
        N = Conditioning(context=p.encode_prompt([""])[0])
        octx = OpContext()

        def run(pos, seed=17, steps=4):
            lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
            (out,) = get_op("KSampler").execute(
                octx, p, seed, steps, 4.0, "euler", "normal", pos, N,
                lat, 1.0)
            return np.asarray(out["samples"])

        (a_early,) = get_op("ConditioningSetTimestepRange").execute(
            octx, A, 0.0, 0.5)
        (b_late,) = get_op("ConditioningSetTimestepRange").execute(
            octx, B, 0.5, 1.0)
        (sched,) = get_op("ConditioningCombine").execute(octx, a_early,
                                                         b_late)
        out = run(sched)
        assert np.isfinite(out).all()
        assert not np.allclose(out, run(A))
        assert not np.allclose(out, run(B))
        # full-range schedule == plain (always-active gate is exact)
        (a_full,) = get_op("ConditioningSetTimestepRange").execute(
            octx, A, 0.0, 1.0)
        np.testing.assert_allclose(run(a_full), run(A), rtol=1e-6,
                                   atol=1e-6)
        registry.clear_pipeline_cache()


class TestFreeU:
    def test_fourier_filter_lowpass(self):
        from comfyui_distributed_tpu.models.unet import _fourier_filter
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((1, 8, 8, 4)), jnp.float32)
        # scale=1: identity (within fft round-trip noise)
        same = _fourier_filter(x, 1, 1.0)
        np.testing.assert_allclose(np.asarray(same), np.asarray(x),
                                   atol=1e-5)
        # scale=0: the DC/low box is removed -> per-channel mean ~0
        killed = np.asarray(_fourier_filter(x, 1, 0.0))
        assert abs(killed.mean()) < 1e-5
        assert not np.allclose(killed, np.asarray(x))

    def test_freeu_changes_output_and_params_shared(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("freeu.ckpt")
        octx = OpContext()
        (p1,) = get_op("FreeU").execute(octx, p, 1.5, 1.6, 0.5, 0.5)
        (p2,) = get_op("FreeU_V2").execute(octx, p, 1.5, 1.6, 0.5, 0.5)
        assert p1.unet_params is p.unet_params        # params shared
        assert p1 is not p and p2 is not p1
        # same settings -> cached derived pipeline
        (p1b,) = get_op("FreeU").execute(octx, p, 1.5, 1.6, 0.5, 0.5)
        assert p1b is p1
        x = jnp.asarray(np.random.default_rng(1).standard_normal(
            (1, 8, 8, 4)), jnp.float32)
        ts = jnp.zeros((1,))
        ctx_a = jnp.asarray(np.random.default_rng(2).standard_normal(
            (1, 16, 64)), jnp.float32)
        base = np.asarray(p.unet.apply({"params": p.unet_params}, x, ts,
                                       ctx_a))
        v1 = np.asarray(p1.unet.apply({"params": p1.unet_params}, x, ts,
                                      ctx_a))
        v2 = np.asarray(p2.unet.apply({"params": p2.unet_params}, x, ts,
                                      ctx_a))
        # tiny's max width is model_channels*2 -> the b2/s2 pair engages
        assert not np.allclose(base, v1)
        assert not np.allclose(v1, v2)     # v2's mean-scaled boost differs
        assert np.isfinite(v1).all() and np.isfinite(v2).all()

    def test_freeu_sampling_e2e(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("freeu-e2e.ckpt")
        octx = OpContext()
        (pf,) = get_op("FreeU").execute(octx, p, 1.4, 1.6, 0.8, 0.4)
        pos = Conditioning(context=p.encode_prompt(["hills"])[0])
        neg = Conditioning(context=p.encode_prompt([""])[0])
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (out,) = get_op("KSampler").execute(
            octx, pf, 5, 2, 4.0, "euler", "normal", pos, neg, lat, 1.0)
        s = np.asarray(out["samples"])
        assert np.isfinite(s).all()
        (plain,) = get_op("KSampler").execute(
            octx, p, 5, 2, 4.0, "euler", "normal", pos, neg, lat, 1.0)
        assert not np.allclose(s, np.asarray(plain["samples"]))
        registry.clear_pipeline_cache()


class TestRescaleCFG:
    def test_node_patches_and_rides_derivations(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("rescale.ckpt")
        octx = OpContext()
        (pr,) = get_op("RescaleCFG").execute(octx, p, 0.7)
        assert pr is not p and pr.cfg_rescale == 0.7
        assert pr.unet_params is p.unet_params
        # rides further derivations (clip-skip AND LoRA chains)
        (pc,) = get_op("CLIPSetLastLayer").execute(octx, pr, -2)
        assert getattr(pc, "cfg_rescale", 0.0) == 0.7
        (pl, _) = get_op("LoraLoader").execute(octx, pr, pr,
                                               "style.safetensors", 0.5,
                                               0.5)
        assert getattr(pl, "cfg_rescale", 0.0) == 0.7
        # multiplier 0 is a no-op passthrough
        (p0,) = get_op("RescaleCFG").execute(octx, p, 0.0)
        assert p0 is p
        # sampling: finite and different from the unpatched run
        pos = Conditioning(context=p.encode_prompt(["dunes"])[0])
        neg = Conditioning(context=p.encode_prompt([""])[0])
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (a,) = get_op("KSampler").execute(octx, pr, 9, 2, 7.0, "euler",
                                          "normal", pos, neg, lat, 1.0)
        (b,) = get_op("KSampler").execute(octx, p, 9, 2, 7.0, "euler",
                                          "normal", pos, neg, lat, 1.0)
        assert np.isfinite(np.asarray(a["samples"])).all()
        assert not np.allclose(np.asarray(a["samples"]),
                               np.asarray(b["samples"]))
        registry.clear_pipeline_cache()


class TestCustomSampling:
    """SamplerCustom chain: KSamplerSelect + scheduler/sigma nodes."""

    def test_sigma_nodes(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("custom-sig.ckpt")
        octx = OpContext()
        (sig,) = get_op("BasicScheduler").execute(octx, p, "karras", 8,
                                                  1.0)
        assert sig.shape == (9,) and sig[-1] == 0.0
        assert np.all(np.diff(sig) < 1e-7)
        (ksig,) = get_op("KarrasScheduler").execute(octx, 6, 10.0, 0.1,
                                                    7.0)
        assert ksig.shape == (7,)
        assert ksig[0] == pytest.approx(10.0) and ksig[-1] == 0.0
        hi, lo = get_op("SplitSigmas").execute(octx, sig, 3)
        assert hi.shape == (4,) and lo.shape == (6,)
        assert hi[-1] == lo[0]
        (flipped,) = get_op("FlipSigmas").execute(octx, sig)
        assert flipped[0] == pytest.approx(1e-4)     # leading 0 -> eps
        assert flipped[-1] == sig[0]
        # denoise<=0: 1-entry sigmas -> SamplerCustom is a no-op
        # (ComfyUI passes the latent through unchanged)
        (sig0,) = get_op("BasicScheduler").execute(octx, p, "karras", 8,
                                                   0.0)
        assert sig0.shape[0] < 2
        from comfyui_distributed_tpu.ops.base import Conditioning
        c = Conditioning(context=p.encode_prompt(["x"])[0])
        lat0 = {"samples": np.full((1, 8, 8, 4), 0.25, np.float32)}
        (sampler0,) = get_op("KSamplerSelect").execute(octx, "euler")
        noop, _ = get_op("SamplerCustom").execute(
            octx, p, True, 1, 4.0, c, c, lat0, sampler0, sig0)
        np.testing.assert_array_equal(np.asarray(noop["samples"]),
                                      lat0["samples"])
        with pytest.raises(ValueError):
            get_op("KSamplerSelect").execute(octx, "not_a_sampler")

    def test_sampler_custom_matches_ksampler(self):
        """SamplerCustom with BasicScheduler sigmas must reproduce the
        KSampler result for the same (sampler, scheduler, steps, seed) —
        the custom chain is the exploded form of the same computation."""
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("custom-eq.ckpt")
        octx = OpContext()
        pos = Conditioning(context=p.encode_prompt(["a fox"])[0])
        neg = Conditioning(context=p.encode_prompt([""])[0])
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (ks_out,) = get_op("KSampler").execute(
            octx, p, 31, 4, 5.0, "dpmpp_2m", "karras", pos, neg, lat, 1.0)
        (sampler,) = get_op("KSamplerSelect").execute(octx, "dpmpp_2m")
        (sig,) = get_op("BasicScheduler").execute(octx, p, "karras", 4,
                                                  1.0)
        out, out2 = get_op("SamplerCustom").execute(
            octx, p, True, 31, 5.0, pos, neg, lat, sampler, sig)
        np.testing.assert_allclose(np.asarray(out["samples"]),
                                   np.asarray(ks_out["samples"]),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(out["samples"]),
                                      np.asarray(out2["samples"]))
        registry.clear_pipeline_cache()

    def test_split_sigmas_two_stage_roundtrip(self):
        """hi/lo split driven through two SamplerCustom stages equals the
        single full run (euler: the deterministic two-window identity)."""
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("custom-2stage.ckpt")
        octx = OpContext()
        pos = Conditioning(context=p.encode_prompt(["a bay"])[0])
        neg = Conditioning(context=p.encode_prompt([""])[0])
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (sampler,) = get_op("KSamplerSelect").execute(octx, "euler")
        (sig,) = get_op("BasicScheduler").execute(octx, p, "normal", 6,
                                                  1.0)
        full, _ = get_op("SamplerCustom").execute(
            octx, p, True, 5, 4.0, pos, neg, lat, sampler, sig)
        hi, lo = get_op("SplitSigmas").execute(octx, sig, 3)
        stage1, _ = get_op("SamplerCustom").execute(
            octx, p, True, 5, 4.0, pos, neg, lat, sampler, hi)
        stage2, _ = get_op("SamplerCustom").execute(
            octx, p, False, 5, 4.0, pos, neg, stage1, sampler, lo)
        np.testing.assert_allclose(np.asarray(stage2["samples"]),
                                   np.asarray(full["samples"]),
                                   rtol=1e-4, atol=1e-4)
        registry.clear_pipeline_cache()


class TestCustomSamplingAdvanced:
    """NOISE/GUIDER suite: RandomNoise, DisableNoise, BasicGuider,
    CFGGuider, DualCFGGuider -> SamplerCustomAdvanced."""

    def _setup(self, name):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline(name)
        octx = OpContext()
        pos = Conditioning(context=p.encode_prompt(["a fox"])[0])
        neg = Conditioning(context=p.encode_prompt([""])[0])
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (sampler,) = get_op("KSamplerSelect").execute(octx, "euler")
        (sig,) = get_op("BasicScheduler").execute(octx, p, "normal", 4,
                                                  1.0)
        return octx, get_op, p, pos, neg, lat, sampler, sig

    def test_cfg_guider_matches_sampler_custom(self):
        octx, get_op, p, pos, neg, lat, sampler, sig = \
            self._setup("adv-cfg.ckpt")
        (noise,) = get_op("RandomNoise").execute(octx, 7)
        (guider,) = get_op("CFGGuider").execute(octx, p, pos, neg, 5.0)
        adv, adv2 = get_op("SamplerCustomAdvanced").execute(
            octx, noise, guider, sampler, sig, lat)
        ref, _ = get_op("SamplerCustom").execute(
            octx, p, True, 7, 5.0, pos, neg, lat, sampler, sig)
        np.testing.assert_allclose(np.asarray(adv["samples"]),
                                   np.asarray(ref["samples"]),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(adv["samples"]),
                                      np.asarray(adv2["samples"]))
        registry.clear_pipeline_cache()

    def test_basic_guider_is_cfg_one(self):
        octx, get_op, p, pos, neg, lat, sampler, sig = \
            self._setup("adv-basic.ckpt")
        (noise,) = get_op("RandomNoise").execute(octx, 3)
        (guider,) = get_op("BasicGuider").execute(octx, p, pos)
        adv, _ = get_op("SamplerCustomAdvanced").execute(
            octx, noise, guider, sampler, sig, lat)
        ref, _ = get_op("SamplerCustom").execute(
            octx, p, True, 3, 1.0, pos, neg, lat, sampler, sig)
        np.testing.assert_allclose(np.asarray(adv["samples"]),
                                   np.asarray(ref["samples"]),
                                   rtol=1e-5, atol=1e-5)
        registry.clear_pipeline_cache()

    def test_disable_noise(self):
        octx, get_op, p, pos, neg, lat, sampler, sig = \
            self._setup("adv-nonoise.ckpt")
        lat = {"samples": np.full((1, 8, 8, 4), 0.4, np.float32)}
        (noise,) = get_op("DisableNoise").execute(octx)
        (guider,) = get_op("CFGGuider").execute(octx, p, pos, neg, 4.0)
        adv, _ = get_op("SamplerCustomAdvanced").execute(
            octx, noise, guider, sampler, sig, lat)
        ref, _ = get_op("SamplerCustom").execute(
            octx, p, False, 0, 4.0, pos, neg, lat, sampler, sig)
        np.testing.assert_allclose(np.asarray(adv["samples"]),
                                   np.asarray(ref["samples"]),
                                   rtol=1e-5, atol=1e-5)
        registry.clear_pipeline_cache()

    def test_dual_cfg_collapses_to_cfg_when_cond2_is_negative(self):
        """(neg + cfg2*(neg-neg)) + cfg1*(pos-neg) == plain CFG at cfg1 —
        the dual combine's exact algebraic reduction, any cfg2."""
        octx, get_op, p, pos, neg, lat, sampler, sig = \
            self._setup("adv-dual-eq.ckpt")
        (noise,) = get_op("RandomNoise").execute(octx, 11)
        (dual,) = get_op("DualCFGGuider").execute(octx, p, pos, neg, neg,
                                                  6.0, 3.3)
        adv, _ = get_op("SamplerCustomAdvanced").execute(
            octx, noise, dual, sampler, sig, lat)
        (cfgg,) = get_op("CFGGuider").execute(octx, p, pos, neg, 6.0)
        ref, _ = get_op("SamplerCustomAdvanced").execute(
            octx, noise, cfgg, sampler, sig, lat)
        np.testing.assert_allclose(np.asarray(adv["samples"]),
                                   np.asarray(ref["samples"]),
                                   rtol=1e-4, atol=1e-4)
        registry.clear_pipeline_cache()

    def test_dual_cfg_distinct_middle_finite_and_differs(self):
        from comfyui_distributed_tpu.ops.base import Conditioning
        octx, get_op, p, pos, neg, lat, sampler, sig = \
            self._setup("adv-dual.ckpt")
        mid = Conditioning(context=p.encode_prompt(["oil painting"])[0])
        (noise,) = get_op("RandomNoise").execute(octx, 5)
        (dual,) = get_op("DualCFGGuider").execute(octx, p, pos, mid, neg,
                                                  7.0, 1.5)
        adv, _ = get_op("SamplerCustomAdvanced").execute(
            octx, noise, dual, sampler, sig, lat)
        s = np.asarray(adv["samples"])
        assert np.isfinite(s).all()
        (cfgg,) = get_op("CFGGuider").execute(octx, p, pos, neg, 7.0)
        ref, _ = get_op("SamplerCustomAdvanced").execute(
            octx, noise, cfgg, sampler, sig, lat)
        assert not np.allclose(s, np.asarray(ref["samples"]))
        registry.clear_pipeline_cache()

    def test_dual_cfg_mixed_token_lengths(self):
        """cond1 chained to 154 tokens via ConditioningConcat while
        middle/negative stay 77: the tripled-batch concat must align all
        three to one length (lcm-repeat), not crash at trace time."""
        octx, get_op, p, pos, neg, lat, sampler, sig = \
            self._setup("adv-dual-tok.ckpt")
        from comfyui_distributed_tpu.ops.base import Conditioning
        mid = Conditioning(context=p.encode_prompt(["sketch"])[0])
        (long_pos,) = get_op("ConditioningConcat").execute(octx, pos, pos)
        assert long_pos.context.shape[1] == 2 * pos.context.shape[1]
        (noise,) = get_op("RandomNoise").execute(octx, 13)
        (dual,) = get_op("DualCFGGuider").execute(
            octx, p, long_pos, mid, neg, 6.0, 2.0)
        adv, _ = get_op("SamplerCustomAdvanced").execute(
            octx, noise, dual, sampler, sig, lat)
        assert np.isfinite(np.asarray(adv["samples"])).all()
        registry.clear_pipeline_cache()

    def test_dual_cfg_with_controlnet(self):
        """Control on the positive rides the dual path with a per-block
        [cond, middle, uncond] strength tuple; a fresh virtual net
        (zero-convs) is bit-identical to no control."""
        octx, get_op, p, pos, neg, lat, sampler, sig = \
            self._setup("adv-dual-cn.ckpt")
        from comfyui_distributed_tpu.ops.base import Conditioning
        mid = Conditioning(context=p.encode_prompt(["photo"])[0])
        module, params = registry.load_controlnet("dual_cn.safetensors")
        hint = np.random.default_rng(5).uniform(
            0, 1, (1, 64, 64, 3)).astype(np.float32)
        (noise,) = get_op("RandomNoise").execute(octx, 21)
        (dual,) = get_op("DualCFGGuider").execute(octx, p, pos, mid, neg,
                                                  5.0, 1.5)
        plain, _ = get_op("SamplerCustomAdvanced").execute(
            octx, noise, dual, sampler, sig, lat)
        (posc,) = get_op("ControlNetApply").execute(
            octx, pos, (module, params), hint, 1.0)
        (dualc,) = get_op("DualCFGGuider").execute(octx, p, posc, mid,
                                                   neg, 5.0, 1.5)
        zeroed, _ = get_op("SamplerCustomAdvanced").execute(
            octx, noise, dualc, sampler, sig, lat)
        np.testing.assert_array_equal(np.asarray(plain["samples"]),
                                      np.asarray(zeroed["samples"]))
        import jax as _jax
        params2 = _jax.tree_util.tree_map(lambda a: a + 0.05, params)
        (posc2,) = get_op("ControlNetApply").execute(
            octx, pos, (module, params2), hint, 1.0)
        (dualc2,) = get_op("DualCFGGuider").execute(octx, p, posc2, mid,
                                                    neg, 5.0, 1.5)
        steered, _ = get_op("SamplerCustomAdvanced").execute(
            octx, noise, dualc2, sampler, sig, lat)
        assert not np.allclose(np.asarray(plain["samples"]),
                               np.asarray(steered["samples"]))
        registry.clear_pipeline_cache()

    def test_dual_cfg_rejects_regional_conds(self):
        octx, get_op, p, pos, neg, lat, sampler, sig = \
            self._setup("adv-dual-rej.ckpt")
        from comfyui_distributed_tpu.ops.base import Conditioning
        mid = Conditioning(context=p.encode_prompt(["left half"])[0])
        mask = np.ones((64, 64), np.float32)
        (masked_mid,) = get_op("ConditioningSetMask").execute(
            octx, mid, mask, 0.8, "default")
        (noise,) = get_op("RandomNoise").execute(octx, 2)
        (dual,) = get_op("DualCFGGuider").execute(
            octx, p, pos, masked_mid, neg, 5.0, 1.5)
        with pytest.raises(ValueError, match="multi-entry"):
            get_op("SamplerCustomAdvanced").execute(
                octx, noise, dual, sampler, sig, lat)
        registry.clear_pipeline_cache()

    def test_dual_prep_middle_own_pooled_and_control(self):
        """The middle entry carries its OWN pooled ADM vector (y list is
        [cond, middle, uncond-rides-positive]) and a control attached to
        the middle alone becomes a flat per-block strength tuple."""
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext)
        from comfyui_distributed_tpu.ops.basic import \
            _prepare_sample_inputs

        class _U:
            adm_in_channels = 2816

        class _F:
            unet = _U()

        class _P:
            family = _F()

        pos = Conditioning(context=np.zeros((1, 77, 32), np.float32),
                           pooled=np.full((1, 1280), 0.1, np.float32))
        mid = Conditioning(context=np.zeros((1, 77, 32), np.float32),
                           pooled=np.full((1, 1280), 0.9, np.float32),
                           control=(object(), {"w": 1},
                                    np.zeros((1, 64, 64, 3), np.float32),
                                    0.7))
        neg = Conditioning(context=np.zeros((1, 77, 32), np.float32))
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        prep = _prepare_sample_inputs(OpContext(), _P(), 0, lat, pos,
                                      neg, middle=mid)
        assert isinstance(prep.y, list) and len(prep.y) == 3
        assert not np.allclose(np.asarray(prep.y[1]),
                               np.asarray(prep.y[0]))
        np.testing.assert_array_equal(np.asarray(prep.y[2]),
                                      np.asarray(prep.y[0]))
        assert prep.mid_context.shape == prep.context.shape
        assert prep.control is not None
        assert prep.control[0][3] == (0.0, 0.7, 0.0)  # 1-chain wire

    def test_dual_cfg_honors_rescale_patch(self):
        octx, get_op, p, pos, neg, lat, sampler, sig = \
            self._setup("adv-dual-rs.ckpt")
        from comfyui_distributed_tpu.ops.base import Conditioning
        mid = Conditioning(context=p.encode_prompt(["ink wash"])[0])
        (noise,) = get_op("RandomNoise").execute(octx, 8)
        (dual,) = get_op("DualCFGGuider").execute(octx, p, pos, mid, neg,
                                                  7.0, 3.0)
        base, _ = get_op("SamplerCustomAdvanced").execute(
            octx, noise, dual, sampler, sig, lat)
        (pr,) = get_op("RescaleCFG").execute(octx, p, 0.7)
        (dual_r,) = get_op("DualCFGGuider").execute(octx, pr, pos, mid,
                                                    neg, 7.0, 3.0)
        rs, _ = get_op("SamplerCustomAdvanced").execute(
            octx, noise, dual_r, sampler, sig, lat)
        r = np.asarray(rs["samples"])
        assert np.isfinite(r).all()
        assert not np.allclose(r, np.asarray(base["samples"]))
        registry.clear_pipeline_cache()


class TestSDXLTextEncodeNodes:
    """CLIPTextEncodeSDXL / CLIPTextEncodeSDXLRefiner: per-tower prompts
    + explicit ADM size scalars."""

    def test_texts_alt_feeds_later_towers_only(self):
        """Duplicate the tiny family's single tower into a 2-tower
        pipeline: text_l drives the first half of the context, text_g
        the second."""
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("sdxl-enc.ckpt")
        p.clip_models = [p.clip_models[0], p.clip_models[0]]
        p.clip_params = [p.clip_params[0], p.clip_params[0]]
        same, _ = p.encode_prompt(["a fox"], texts_alt=["a fox"])
        split, _ = p.encode_prompt(["a fox"], texts_alt=["a crow"])
        base, _ = p.encode_prompt(["a fox"])
        np.testing.assert_array_equal(np.asarray(same), np.asarray(base))
        half = same.shape[-1] // 2
        np.testing.assert_array_equal(np.asarray(split[..., :half]),
                                      np.asarray(base[..., :half]))
        assert not np.allclose(np.asarray(split[..., half:]),
                               np.asarray(base[..., half:]))
        registry.clear_pipeline_cache()

    def test_size_cond_rides_adm_vector(self):
        from comfyui_distributed_tpu.ops.base import Conditioning
        from comfyui_distributed_tpu.ops.basic import _sdxl_vector_cond

        class _U:
            adm_in_channels = 2816

        class _F:
            unet = _U()

        class _P:
            family = _F()

        pooled = np.full((1, 1280), 0.2, np.float32)
        derived = _sdxl_vector_cond(
            _P(), Conditioning(context=None, pooled=pooled), 2, 512, 512)
        explicit = _sdxl_vector_cond(
            _P(), Conditioning(context=None, pooled=pooled,
                               size_cond=(512, 512, 0, 0, 512, 512)),
            2, 512, 512)
        np.testing.assert_array_equal(np.asarray(derived),
                                      np.asarray(explicit))
        shifted = _sdxl_vector_cond(
            _P(), Conditioning(context=None, pooled=pooled,
                               size_cond=(1024, 1024, 0, 0, 512, 512)),
            2, 512, 512)
        assert shifted.shape == (2, 2816)
        assert not np.allclose(np.asarray(shifted), np.asarray(derived))
        # refiner 5-scalar layout: pooled 1280 + 5*256 = 2560, padded to
        # the family's adm width
        ref = _sdxl_vector_cond(
            _P(), Conditioning(context=None, pooled=pooled,
                               size_cond=(512, 512, 0, 0, 6.0)),
            1, 512, 512)
        assert ref.shape == (1, 2816)
        assert not np.allclose(np.asarray(ref)[:, :2560],
                               np.asarray(derived)[:1, :2560])

    def test_nodes_build_size_cond(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("sdxl-enc2.ckpt")
        octx = OpContext()
        (c,) = get_op("CLIPTextEncodeSDXL").execute(
            octx, p, 1024, 1024, 0, 0, 1024, 1024, "a fox", "a fox")
        assert c.size_cond == (1024, 1024, 0, 0, 1024, 1024)
        assert c.context.shape[0] == 1
        (r,) = get_op("CLIPTextEncodeSDXLRefiner").execute(
            octx, p, 6.0, 1024, 1024, "a fox")
        assert r.size_cond == (1024, 1024, 0, 0, 6.0)
        registry.clear_pipeline_cache()


class TestTextualInversion:
    """embedding:name prompt refs splice learned vectors into the token
    stream (ComfyUI textual-inversion syntax)."""

    def _write_embedding(self, models_dir, name, arr, key="emb_params"):
        import os

        from safetensors.numpy import save_file
        os.makedirs(os.path.join(models_dir, "embeddings"), exist_ok=True)
        save_file({key: arr}, os.path.join(models_dir, "embeddings",
                                           name + ".safetensors"))

    def test_embedding_changes_encoding(self, tmp_path):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("ti-base.ckpt",
                                   models_dir=str(tmp_path))
        width = int(p.clip_models[0].cfg.width)
        rng = np.random.default_rng(5)
        self._write_embedding(str(tmp_path), "mystyle",
                              rng.standard_normal((2, width))
                              .astype(np.float32))
        octx = OpContext()
        (plain,) = get_op("CLIPTextEncode").execute(octx, p, "a fox")
        (with_emb,) = get_op("CLIPTextEncode").execute(
            octx, p, "a fox embedding:mystyle")
        assert with_emb.context.shape == plain.context.shape
        assert not np.allclose(np.asarray(with_emb.context),
                               np.asarray(plain.context))
        # unknown name: dropped -> identical to the plain prompt
        (dropped,) = get_op("CLIPTextEncode").execute(
            octx, p, "a fox embedding:doesnotexist")
        np.testing.assert_array_equal(np.asarray(dropped.context),
                                      np.asarray(plain.context))
        registry.clear_pipeline_cache()

    def test_spliced_positions_and_weights(self, tmp_path):
        from comfyui_distributed_tpu.models.registry import \
            load_textual_embedding
        from comfyui_distributed_tpu.models.tokenizer import (
            encode_with_embeddings, make_tokenizer)
        width = 16
        rng = np.random.default_rng(3)
        vecs = rng.standard_normal((3, width)).astype(np.float32)
        self._write_embedding(str(tmp_path), "tivec", vecs)
        tok = make_tokenizer()

        def look(nm):
            return load_textual_embedding(nm, str(tmp_path), width)

        ids, w, ov, mask = encode_with_embeddings(
            tok, "a (embedding:tivec:1.5) fox", look, width)
        assert ids.shape == (tok.max_length,)
        assert mask.sum() == 3.0
        pos = np.nonzero(mask)[0]
        np.testing.assert_array_equal(ov[pos], vecs)
        np.testing.assert_array_equal(ids[pos], np.zeros(3, np.int32))
        np.testing.assert_allclose(w[pos], 1.5)
        # width mismatch -> None -> dropped
        assert load_textual_embedding("tivec", str(tmp_path), 32) is None

    def test_per_tower_keys(self, tmp_path):
        import os

        from safetensors.numpy import save_file
        from comfyui_distributed_tpu.models.registry import \
            load_textual_embedding
        os.makedirs(os.path.join(str(tmp_path), "embeddings"),
                    exist_ok=True)
        l = np.ones((1, 8), np.float32)
        g = np.full((1, 12), 2.0, np.float32)
        save_file({"clip_l": l, "clip_g": g},
                  os.path.join(str(tmp_path), "embeddings",
                               "xl.safetensors"))
        np.testing.assert_array_equal(
            load_textual_embedding("xl", str(tmp_path), 8, tower_idx=0), l)
        np.testing.assert_array_equal(
            load_textual_embedding("xl", str(tmp_path), 12, tower_idx=1),
            g)
        # tower 0 must not fall back to the g-tensor
        assert load_textual_embedding("xl", str(tmp_path), 12,
                                      tower_idx=0) is None


class TestModelPatchesRound4:
    """ModelSamplingDiscrete / PerpNeg / HyperTile."""

    def test_model_sampling_discrete(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("msd.ckpt")
        octx = OpContext()
        (pv,) = get_op("ModelSamplingDiscrete").execute(octx, p,
                                                        "v_prediction",
                                                        False)
        assert pv.prediction_type == "v" and pv.unet_params is p.unet_params
        (pz,) = get_op("ModelSamplingDiscrete").execute(octx, p, "eps",
                                                        True)
        assert pz.schedule.sigma_max > p.schedule.sigma_max * 10
        # the reference ecosystem's pinned terminal abar (ADVICE r4):
        # sigma_max = sqrt((1-abar)/abar) at abar=4.8973451890853435e-08
        ref_abar = 4.8973451890853435e-08
        np.testing.assert_allclose(
            float(pz.schedule.sigma_max),
            float(np.sqrt((1.0 - ref_abar) / ref_abar)), rtol=1e-4)
        assert np.isclose(pz.schedule.sigmas[0], p.schedule.sigmas[0],
                          rtol=0.15)       # clean end barely moves
        # patch rides a LoRA derivation
        (pl, _) = get_op("LoraLoader").execute(octx, pv, pv,
                                               "style.safetensors", 0.5,
                                               0.5)
        assert pl.prediction_type == "v"
        with pytest.raises(ValueError):
            get_op("ModelSamplingDiscrete").execute(octx, p, "nope",
                                                    False)
        # sampling: v-interpretation of the same weights differs from eps
        pos = Conditioning(context=p.encode_prompt(["dunes"])[0])
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (a,) = get_op("KSampler").execute(octx, p, 5, 2, 4.0, "euler",
                                          "normal", pos, pos, lat, 1.0)
        (b,) = get_op("KSampler").execute(octx, pv, 5, 2, 4.0, "euler",
                                          "normal", pos, pos, lat, 1.0)
        assert np.isfinite(np.asarray(b["samples"])).all()
        assert not np.allclose(np.asarray(a["samples"]),
                               np.asarray(b["samples"]))
        registry.clear_pipeline_cache()

    def test_perp_neg_reduces_to_cfg_when_empty_is_negative(self):
        """neg == empty -> the perpendicular component vanishes and the
        combine is EXACTLY plain CFG against the empty prompt."""
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("pn-eq.ckpt")
        octx = OpContext()
        pos = Conditioning(context=p.encode_prompt(["a fox"])[0])
        neg = Conditioning(context=p.encode_prompt([""])[0])
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (pp,) = get_op("PerpNeg").execute(octx, p, neg, 1.0)
        (a,) = get_op("KSampler").execute(octx, pp, 5, 2, 6.0, "euler",
                                          "normal", pos, neg, lat, 1.0)
        (b,) = get_op("KSampler").execute(octx, p, 5, 2, 6.0, "euler",
                                          "normal", pos, neg, lat, 1.0)
        # tripled- vs doubled-batch executables fuse differently; the
        # reduction is algebraically exact, numerically ~1e-6 relative
        np.testing.assert_allclose(np.asarray(a["samples"]),
                                   np.asarray(b["samples"]),
                                   rtol=1e-3, atol=1e-4)
        # a DISTINCT empty changes the guidance
        emp = Conditioning(context=p.encode_prompt(["photo"])[0])
        (pd,) = get_op("PerpNeg").execute(octx, p, emp, 1.0)
        (c,) = get_op("KSampler").execute(octx, pd, 5, 2, 6.0, "euler",
                                          "normal", pos, neg, lat, 1.0)
        s = np.asarray(c["samples"])
        assert np.isfinite(s).all()
        assert not np.allclose(s, np.asarray(b["samples"]))
        registry.clear_pipeline_cache()

    def test_perp_neg_guider_matches_patch(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("pn-g.ckpt")
        octx = OpContext()
        pos = Conditioning(context=p.encode_prompt(["a fox"])[0])
        neg = Conditioning(context=p.encode_prompt(["blurry"])[0])
        emp = Conditioning(context=p.encode_prompt([""])[0])
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (sampler,) = get_op("KSamplerSelect").execute(octx, "euler")
        (sig,) = get_op("BasicScheduler").execute(octx, p, "normal", 3,
                                                  1.0)
        (noise,) = get_op("RandomNoise").execute(octx, 9)
        (guider,) = get_op("PerpNegGuider").execute(octx, p, pos, neg,
                                                    emp, 6.0, 1.0)
        a, _ = get_op("SamplerCustomAdvanced").execute(
            octx, noise, guider, sampler, sig, lat)
        (pp,) = get_op("PerpNeg").execute(octx, p, emp, 1.0)
        b, _ = get_op("SamplerCustom").execute(
            octx, pp, True, 9, 6.0, pos, neg, lat, sampler, sig)
        np.testing.assert_allclose(np.asarray(a["samples"]),
                                   np.asarray(b["samples"]),
                                   rtol=1e-5, atol=1e-5)
        registry.clear_pipeline_cache()

    def test_hypertile_module_level(self):
        import jax as _jax

        from comfyui_distributed_tpu.models.layers import (
            SpatialTransformer, _hypertile_divisor)
        assert _hypertile_divisor(32, 4) == 8
        assert _hypertile_divisor(32, 32) == 1
        assert _hypertile_divisor(30, 7) == 3   # 30/3=10 >= 7
        st = SpatialTransformer(num_heads=2, dtype=jnp.float32)
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.standard_normal((1, 8, 8, 32)), jnp.float32)
        ctx = jnp.asarray(rng.standard_normal((1, 7, 64)), jnp.float32)
        params = st.init(_jax.random.PRNGKey(0), x, ctx)
        base = st.apply(params, x, ctx)
        tiled = SpatialTransformer(num_heads=2, dtype=jnp.float32,
                                   hypertile_tile=4)
        out = tiled.apply(params, x, ctx)
        assert out.shape == base.shape
        assert not np.allclose(np.asarray(out), np.asarray(base))
        # a tile >= the whole map is a no-op (nh = nw = 1)
        whole = SpatialTransformer(num_heads=2, dtype=jnp.float32,
                                   hypertile_tile=8)
        np.testing.assert_array_equal(np.asarray(whole.apply(params, x,
                                                             ctx)),
                                      np.asarray(base))

    def test_hypertile_node_runs(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("ht.ckpt")
        octx = OpContext()
        (ph,) = get_op("HyperTile").execute(octx, p, 32, 2, 1, False)
        assert ph.family.unet.hypertile == (32, 1, False)
        assert ph.unet_params is p.unet_params
        pos = Conditioning(context=p.encode_prompt(["a fox"])[0])
        lat = {"samples": np.zeros((1, 16, 16, 4), np.float32)}
        (a,) = get_op("KSampler").execute(octx, ph, 5, 2, 4.0, "euler",
                                          "normal", pos, pos, lat, 1.0)
        s = np.asarray(a["samples"])
        assert np.isfinite(s).all()
        (b,) = get_op("KSampler").execute(octx, p, 5, 2, 4.0, "euler",
                                          "normal", pos, pos, lat, 1.0)
        assert not np.allclose(s, np.asarray(b["samples"]))
        registry.clear_pipeline_cache()


class TestPerpNegIntegration:
    def test_cache_keyed_by_empty_cond_and_rides_chains(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("pn-cache.ckpt")
        octx = OpContext()
        e1 = Conditioning(context=p.encode_prompt(["a"])[0])
        e2 = Conditioning(context=p.encode_prompt(["b"])[0])
        (p1,) = get_op("PerpNeg").execute(octx, p, e1, 1.0)
        (p2,) = get_op("PerpNeg").execute(octx, p, e2, 1.0)
        assert p1 is not p2            # distinct empties: distinct clones
        assert p2.perp_neg_cond is e2
        (p1b,) = get_op("PerpNeg").execute(octx, p, e1, 1.0)
        assert p1b is p1               # same empty: cache hit
        (pl, _) = get_op("LoraLoader").execute(octx, p1, p1,
                                               "s.safetensors", 0.5, 0.5)
        assert getattr(pl, "perp_neg_cond", None) is e1
        assert getattr(pl, "perp_neg_scale", None) == 1.0
        registry.clear_pipeline_cache()

    def test_refine_batch_passes_perp_neg(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        import jax.numpy as jnp
        captured = {}

        class _U:
            adm_in_channels = None

        class _F:
            unet = _U()

        class _Pipe:
            family = _F()
            perp_neg_cond = Conditioning(
                context=np.ones((1, 77, 8), np.float32))
            perp_neg_scale = 0.7

            def vae_encode(self, t):
                return jnp.zeros((t.shape[0], 4, 4, 4))

            def sample(self, lat, c, u, seeds, **kw):
                captured.update(kw)
                return lat

            def vae_decode(self, lat):
                return np.zeros((lat.shape[0], 8, 8, 3), np.float32)

        op = get_op("UltimateSDUpscaleDistributed")
        pos = Conditioning(context=np.zeros((1, 77, 8), np.float32))
        params = {"seed": 1, "steps": 1, "cfg": 4.0,
                  "sampler_name": "euler", "scheduler": "normal",
                  "denoise": 0.5}
        op._refine_batch(OpContext(), _Pipe(),
                         np.zeros((2, 8, 8, 3), np.float32), [0, 1],
                         pos, pos, params)
        assert captured["guidance"] == "perp_neg"
        assert captured["cfg2"] == 0.7
        assert captured["middle_context"].shape == (2, 77, 8)


class TestSelfAttentionGuidance:
    def test_sag_changes_output_and_zero_scale_matches_plain(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("sag.ckpt")
        octx = OpContext()
        pos = Conditioning(context=p.encode_prompt(["a fox"])[0])
        neg = Conditioning(context=p.encode_prompt([""])[0])
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (plain,) = get_op("KSampler").execute(octx, p, 3, 2, 6.0, "euler",
                                              "normal", pos, neg, lat,
                                              1.0)
        (p0,) = get_op("SelfAttentionGuidance").execute(octx, p, 0.0,
                                                        2.0)
        (z,) = get_op("KSampler").execute(octx, p0, 3, 2, 6.0, "euler",
                                          "normal", pos, neg, lat, 1.0)
        # scale 0: the SAG term vanishes; only fusion noise remains
        np.testing.assert_allclose(np.asarray(z["samples"]),
                                   np.asarray(plain["samples"]),
                                   rtol=1e-3, atol=1e-4)
        (ps,) = get_op("SelfAttentionGuidance").execute(octx, p, 0.8,
                                                        2.0)
        assert ps.family.unet.sag_capture is True
        assert ps.sag_params == (0.8, 2.0)
        (s,) = get_op("KSampler").execute(octx, ps, 3, 2, 6.0, "euler",
                                          "normal", pos, neg, lat, 1.0)
        arr = np.asarray(s["samples"])
        assert np.isfinite(arr).all()
        assert not np.allclose(arr, np.asarray(plain["samples"]))
        registry.clear_pipeline_cache()

    def test_sag_falls_back_without_uncond_benefit(self):
        """cfg == 1 (no uncond evaluated): SAG logs and samples without
        guidance instead of crashing."""
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("sag-fb.ckpt")
        octx = OpContext()
        pos = Conditioning(context=p.encode_prompt(["a fox"])[0])
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (ps,) = get_op("SelfAttentionGuidance").execute(octx, p, 0.5,
                                                        2.0)
        (out,) = get_op("KSampler").execute(octx, ps, 3, 2, 1.0, "euler",
                                            "normal", pos, pos, lat, 1.0)
        assert np.isfinite(np.asarray(out["samples"])).all()
        registry.clear_pipeline_cache()

    def test_gaussian_blur_reflect_constant_invariant(self):
        from comfyui_distributed_tpu.models import samplers as smp
        import jax.numpy as jnp
        flat = jnp.full((1, 12, 12, 4), 0.7, jnp.float32)
        out = smp._gaussian_blur_nhwc(flat, 9, 2.0)
        np.testing.assert_allclose(np.asarray(out), 0.7, atol=1e-6)


class TestInpaintModelFamily:
    """9-channel inpaint checkpoints (sd15_inpaint / tiny_inpaint) +
    InpaintModelConditioning."""

    def test_family_detection_and_virtual_init(self, monkeypatch):
        monkeypatch.delenv(registry.FAMILY_ENV, raising=False)
        assert registry.detect_family("sd-v1-5-inpainting.ckpt") \
            == "sd15_inpaint"
        assert registry.detect_family("tiny-inpaint.ckpt") \
            == "tiny_inpaint"
        assert registry.detect_family("dreamlike.safetensors") == "sd15"
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("tiny-inpaint-a.ckpt")
        assert p.family.unet.in_channels == 9
        # conv_in consumes 9 channels
        kern = p.unet_params["conv_in"]["kernel"]
        assert kern.shape[2] == 9
        registry.clear_pipeline_cache()

    def test_inpaint_model_conditioning_e2e(self, monkeypatch):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        monkeypatch.setenv(registry.FAMILY_ENV, "tiny_inpaint")
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("tiny-inpaint-b.ckpt")
        octx = OpContext()
        pos = Conditioning(context=p.encode_prompt(["a fox"])[0])
        neg = Conditioning(context=p.encode_prompt([""])[0])
        rng = np.random.default_rng(3)
        img = rng.uniform(0, 1, (1, 32, 32, 3)).astype(np.float32)
        mask = np.zeros((1, 32, 32), np.float32)
        mask[:, 8:24, 8:24] = 1.0
        pos2, neg2, lat = get_op("InpaintModelConditioning").execute(
            octx, pos, neg, p, img, mask, True)
        # tiny VAE downscales 2x: latent 16x16; concat = mask(1)+lat(4)
        assert pos2.concat_latent.shape == (1, 16, 16, 5)
        assert neg2.concat_latent is pos2.concat_latent
        assert "noise_mask" in lat
        (out,) = get_op("KSampler").execute(octx, p, 5, 2, 4.0, "euler",
                                            "normal", pos2, neg2, lat,
                                            0.6)
        s = np.asarray(out["samples"])
        assert np.isfinite(s).all()
        # the concat channels actually steer: a different mask/masked
        # content changes the result
        mask2 = np.zeros((1, 32, 32), np.float32)
        mask2[:, 0:8, 0:8] = 1.0
        pos3, neg3, lat3 = get_op("InpaintModelConditioning").execute(
            octx, pos, neg, p, img, mask2, True)
        (out2,) = get_op("KSampler").execute(octx, p, 5, 2, 4.0, "euler",
                                             "normal", pos3, neg3, lat3,
                                             0.6)
        assert not np.allclose(s, np.asarray(out2["samples"]))
        # noise_mask widget off: no mask on the latent (pure
        # model-driven inpainting)
        _, _, lat_nm = get_op("InpaintModelConditioning").execute(
            octx, pos, neg, p, img, mask, False)
        assert "noise_mask" not in lat_nm
        registry.clear_pipeline_cache()


class TestDeepShrink:
    def test_unet_shrunk_config_shapes(self):
        import jax as _jax

        from comfyui_distributed_tpu.models import unet as unet_mod
        cfg = unet_mod.TINY_CONFIG
        mod = unet_mod.UNet(cfg)
        x = jnp.zeros((1, 16, 16, 4), jnp.float32)
        ts = jnp.zeros((1,))
        c = jnp.zeros((1, 77, cfg.context_dim), jnp.float32)
        params = registry._virtual_params(mod, 3, x, ts, c)
        plain = mod.apply({"params": params}, x, ts, c)
        import dataclasses as dc
        sh_mod = unet_mod.UNet(dc.replace(cfg, deep_shrink=(1, 2.0)))
        shrunk = sh_mod.apply({"params": params}, x, ts, c)
        assert shrunk.shape == plain.shape
        assert not np.allclose(np.asarray(shrunk), np.asarray(plain))

    def test_node_patch_and_window(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("dshrink.ckpt")
        octx = OpContext()
        pos = Conditioning(context=p.encode_prompt(["a fox"])[0])
        lat = {"samples": np.zeros((1, 16, 16, 4), np.float32)}
        (plain,) = get_op("KSampler").execute(octx, p, 3, 3, 4.0,
                                              "euler", "normal", pos,
                                              pos, lat, 1.0)
        (pd,) = get_op("PatchModelAddDownscale").execute(
            octx, p, 3, 2.0, 0.0, 0.35, True, "bicubic", "bicubic")
        lvl, fac, t_lo, t_hi = pd.deep_shrink_spec
        assert lvl == 1.0 and fac == 2.0 and t_hi > t_lo
        (out,) = get_op("KSampler").execute(octx, pd, 3, 3, 4.0,
                                            "euler", "normal", pos, pos,
                                            lat, 1.0)
        s = np.asarray(out["samples"])
        assert np.isfinite(s).all()
        assert not np.allclose(s, np.asarray(plain["samples"]))
        # window [0, 0): never active -> results match the plain run
        (p0,) = get_op("PatchModelAddDownscale").execute(
            octx, p, 3, 2.0, 0.0, 0.0, True, "bicubic", "bicubic")
        (same,) = get_op("KSampler").execute(octx, p0, 3, 3, 4.0,
                                             "euler", "normal", pos,
                                             pos, lat, 1.0)
        np.testing.assert_allclose(np.asarray(same["samples"]),
                                   np.asarray(plain["samples"]),
                                   rtol=1e-4, atol=1e-5)
        # rides a LoRA derivation
        (pl, _) = get_op("LoraLoader").execute(octx, pd, pd,
                                               "s.safetensors", 0.5, 0.5)
        assert getattr(pl, "deep_shrink_spec", None) is not None
        registry.clear_pipeline_cache()

    def test_block_number_level_mapping(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("dshrink-map.ckpt")   # tiny: nrb=1
        octx = OpContext()
        # tiny num_res_blocks=1: block1 -> level0, block2 (its
        # downsample) -> level1
        (a,) = get_op("PatchModelAddDownscale").execute(
            octx, p, 1, 2.0, 0.0, 0.5, True, "bicubic", "bicubic")
        assert a.deep_shrink_spec[0] == 0.0
        (b,) = get_op("PatchModelAddDownscale").execute(
            octx, p, 2, 2.0, 0.0, 0.5, True, "bicubic", "bicubic")
        assert b.deep_shrink_spec[0] == 1.0
        registry.clear_pipeline_cache()


class TestRound4ReviewFixes:
    def test_inpaint_family_routing(self, monkeypatch):
        monkeypatch.delenv(registry.FAMILY_ENV, raising=False)
        assert registry.detect_family("512-inpainting-ema.ckpt") \
            == "sd21_inpaint"
        assert registry.detect_family("sd2-inpainting.safetensors") \
            == "sd21_inpaint"
        assert registry.detect_family("sd_xl_inpainting_0.1.safetensors") \
            == "sdxl_inpaint"
        assert registry.detect_family("sd-v1-5-inpainting.ckpt") \
            == "sd15_inpaint"
        assert registry.FAMILIES["sd21_inpaint"].unet.context_dim == 1024
        assert registry.FAMILIES["sdxl_inpaint"].unet.in_channels == 9

    def test_image_quantize_dither_has_effect(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        octx = OpContext()
        rng = np.random.default_rng(6)
        grad = np.linspace(0, 1, 64, dtype=np.float32)
        img = np.broadcast_to(grad, (32, 64)).astype(np.float32)
        img = np.stack([img, img, img], axis=-1)[None]
        img = img + rng.uniform(0, 0.02, img.shape).astype(np.float32)
        (nd,) = get_op("ImageQuantize").execute(octx, img, 4, "none")
        (fd,) = get_op("ImageQuantize").execute(octx, img, 4,
                                                "floyd-steinberg")
        assert not np.array_equal(nd, fd)    # dithering actually runs

    def test_sag_falls_back_with_hypertiled_mid(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("sag-ht.ckpt")
        octx = OpContext()
        (ph,) = get_op("HyperTile").execute(octx, p, 32, 2, 3, False)
        (ps,) = get_op("SelfAttentionGuidance").execute(octx, ph, 0.5,
                                                        2.0)
        pos = Conditioning(context=p.encode_prompt(["a fox"])[0])
        neg = Conditioning(context=p.encode_prompt([""])[0])
        lat = {"samples": np.zeros((1, 16, 16, 4), np.float32)}
        (out,) = get_op("KSampler").execute(octx, ps, 3, 2, 5.0, "euler",
                                            "normal", pos, neg, lat, 1.0)
        assert np.isfinite(np.asarray(out["samples"])).all()
        registry.clear_pipeline_cache()


class TestHypernetwork:
    def _make_pt(self, path, dim, seed=0):
        """A real A1111-layout .pt: torch Sequential exports + metadata."""
        import torch
        g = torch.Generator().manual_seed(seed)

        def stream():
            return {
                "linear.0.weight": torch.randn((dim * 2, dim),
                                               generator=g) * 0.2,
                "linear.0.bias": torch.zeros(dim * 2),
                "linear.2.weight": torch.randn((dim, dim * 2),
                                               generator=g) * 0.2,
                "linear.2.bias": torch.zeros(dim),
            }
        torch.save({"layer_structure": [1, 2, 1],
                    "activation_func": "relu",
                    "is_layer_norm": False,
                    "activate_output": False,
                    dim: [stream(), stream()]}, path)

    def test_parse_and_apply_real_pt(self, tmp_path):
        import os

        from comfyui_distributed_tpu.models import hypernetwork as hn_mod
        d = os.path.join(str(tmp_path), "hypernetworks")
        os.makedirs(d)
        self._make_pt(os.path.join(d, "style.pt"), 16, seed=3)
        hn = hn_mod.load_hypernetwork("style", models_dir=str(tmp_path))
        assert 16 in hn
        ctx = jnp.asarray(np.random.default_rng(1).standard_normal(
            (1, 7, 16)), jnp.float32)
        ck, cv = hn_mod.apply_hypernetwork(hn, 1.0, ctx)
        assert ck.shape == ctx.shape and cv.shape == ctx.shape
        assert not np.allclose(np.asarray(ck), np.asarray(ctx))
        assert not np.allclose(np.asarray(ck), np.asarray(cv))
        # strength 0: exact passthrough
        ck0, cv0 = hn_mod.apply_hypernetwork(hn, 0.0, ctx)
        np.testing.assert_array_equal(np.asarray(ck0), np.asarray(ctx))
        # unknown width: passthrough untouched
        other = jnp.zeros((1, 7, 24), jnp.float32)
        ok, ov = hn_mod.apply_hypernetwork(hn, 1.0, other)
        assert ok is other and ov is other
        # torch-reference parity for the k stream: x + relu-MLP(x)
        import torch
        sd = torch.load(os.path.join(d, "style.pt"),
                        weights_only=True)
        k_sd = sd[16][0]
        xt = torch.from_numpy(np.asarray(ctx))
        ref = xt + (torch.relu(xt @ k_sd["linear.0.weight"].T
                               + k_sd["linear.0.bias"])
                    @ k_sd["linear.2.weight"].T + k_sd["linear.2.bias"])
        np.testing.assert_allclose(np.asarray(ck), ref.numpy(),
                                   rtol=1e-5, atol=1e-5)
        hn_mod.clear_hypernetwork_cache()

    def test_loader_node_steers_sampling(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("hn-base.ckpt")
        octx = OpContext()
        pos = Conditioning(context=p.encode_prompt(["a fox"])[0])
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (plain,) = get_op("KSampler").execute(octx, p, 3, 2, 4.0,
                                              "euler", "normal", pos,
                                              pos, lat, 1.0)
        (ph,) = get_op("HypernetworkLoader").execute(octx, p,
                                                     "vstyle.pt", 0.8)
        assert ph is not p and ph.hypernets[0][1] == 0.8
        (out,) = get_op("KSampler").execute(octx, ph, 3, 2, 4.0,
                                            "euler", "normal", pos, pos,
                                            lat, 1.0)
        s = np.asarray(out["samples"])
        assert np.isfinite(s).all()
        assert not np.allclose(s, np.asarray(plain["samples"]))
        # strength 0 is a passthrough (no derivation)
        (p0,) = get_op("HypernetworkLoader").execute(octx, p,
                                                     "vstyle.pt", 0.0)
        assert p0 is p
        # rides a LoRA chain
        (pl, _) = get_op("LoraLoader").execute(octx, ph, ph,
                                               "s.safetensors", 0.5, 0.5)
        assert getattr(pl, "hypernets", None) is not None
        # chained loaders COMPOSE (reference: attn patches stack)
        (p2,) = get_op("HypernetworkLoader").execute(octx, ph,
                                                     "other.pt", 0.3)
        assert len(p2.hypernets) == 2
        assert p2.hypernets[0][1] == 0.8 and p2.hypernets[1][1] == 0.3
        (out2,) = get_op("KSampler").execute(octx, p2, 3, 2, 4.0,
                                             "euler", "normal", pos,
                                             pos, lat, 1.0)
        assert np.isfinite(np.asarray(out2["samples"])).all()
        assert not np.allclose(np.asarray(out2["samples"]), s)
        registry.clear_pipeline_cache()


class TestModelMergingAndSaves:
    def test_model_merge_simple_exact(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        import jax as _jax
        registry.clear_pipeline_cache()
        a = registry.load_pipeline("merge-a.ckpt")
        b = registry.load_pipeline("merge-b.ckpt")
        octx = OpContext()
        (m1,) = get_op("ModelMergeSimple").execute(octx, a, b, 1.0)
        _jax.tree_util.tree_map(
            lambda x, y: np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=1e-6),
            m1.unet_params, a.unet_params)
        (mh,) = get_op("ModelMergeSimple").execute(octx, a, b, 0.25)
        la = _jax.tree_util.tree_leaves(a.unet_params)[0]
        lb = _jax.tree_util.tree_leaves(b.unet_params)[0]
        lm = _jax.tree_util.tree_leaves(mh.unet_params)[0]
        np.testing.assert_allclose(
            np.asarray(lm),
            np.asarray(la) * 0.25 + np.asarray(lb) * 0.75, rtol=1e-5)
        # CLIP/VAE stay model1's (ComfyUI merges the UNet only here)
        assert mh.clip_params is a.clip_params
        registry.clear_pipeline_cache()

    def test_model_merge_blocks_sections(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        registry.clear_pipeline_cache()
        a = registry.load_pipeline("mergeb-a.ckpt")
        b = registry.load_pipeline("mergeb-b.ckpt")
        octx = OpContext()
        (m,) = get_op("ModelMergeBlocks").execute(octx, a, b, 1.0, 0.0,
                                                  1.0)
        # middle ratio 0 -> mid blocks are exactly model2's
        np.testing.assert_allclose(
            np.asarray(m.unet_params["mid_res_0"]["in_conv"]["kernel"]),
            np.asarray(b.unet_params["mid_res_0"]["in_conv"]["kernel"]),
            rtol=1e-6)
        # encoder ratio 1 -> down blocks are exactly model1's
        np.testing.assert_allclose(
            np.asarray(m.unet_params["conv_in"]["kernel"]),
            np.asarray(a.unet_params["conv_in"]["kernel"]), rtol=1e-6)
        registry.clear_pipeline_cache()

    def test_clip_merge_and_lora_model_only(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        import jax as _jax
        registry.clear_pipeline_cache()
        a = registry.load_pipeline("cm-a.ckpt")
        b = registry.load_pipeline("cm-b.ckpt")
        octx = OpContext()
        (c,) = get_op("CLIPMergeSimple").execute(octx, a, b, 0.5)
        la = _jax.tree_util.tree_leaves(a.clip_params[0])[0]
        lb = _jax.tree_util.tree_leaves(b.clip_params[0])[0]
        lc = _jax.tree_util.tree_leaves(c.clip_params[0])[0]
        np.testing.assert_allclose(
            np.asarray(lc), (np.asarray(la) + np.asarray(lb)) / 2,
            rtol=1e-5)
        (lm,) = get_op("LoraLoaderModelOnly").execute(
            octx, a, "style.safetensors", 0.7)
        assert lm is not a and lm.clip_params is a.clip_params
        assert lm.unet_params is not a.unet_params
        registry.clear_pipeline_cache()

    def test_vae_and_clip_save_round_trip(self, tmp_path, monkeypatch):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        import jax as _jax
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("saver.ckpt")
        octx = OpContext()
        octx.output_dir = str(tmp_path)
        get_op("VAESave").execute(octx, p, "vae/exported")
        import os
        vp = os.path.join(str(tmp_path), "vae", "exported.safetensors")
        assert os.path.exists(vp)
        # bare-key standalone file loads back through VAELoader
        reloaded = registry.load_vae(
            os.path.relpath(vp, str(tmp_path)), models_dir=str(tmp_path),
            family_name="tiny")
        _jax.tree_util.tree_map(
            lambda x, y: np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6),
            reloaded.vae_params, p.vae_params)
        get_op("CLIPSave").execute(octx, p, "clip/exported")
        assert os.path.exists(os.path.join(str(tmp_path), "clip",
                                           "exported.safetensors"))
        registry.clear_pipeline_cache()


class TestMergeBlocksSectionAnchoring:
    def test_encoder_inner_out_norm_uses_input_ratio(self):
        """ResBlocks contain an inner 'out_norm'; a substring match
        would misroute encoder norms into the 'out' section."""
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        registry.clear_pipeline_cache()
        a = registry.load_pipeline("anchor-a.ckpt")
        b = registry.load_pipeline("anchor-b.ckpt")
        octx = OpContext()
        (m,) = get_op("ModelMergeBlocks").execute(octx, a, b, 1.0, 1.0,
                                                  0.0)
        # encoder ResBlock's INNER out_norm follows the input ratio (1.0
        # -> model1), not the out ratio
        np.testing.assert_allclose(
            np.asarray(m.unet_params["down_0_res_0"]["out_norm"]
                       ["GroupNorm_0"]["scale"])
            if "GroupNorm_0" in m.unet_params["down_0_res_0"]["out_norm"]
            else np.asarray(m.unet_params["down_0_res_0"]["out_norm"]
                            [next(iter(m.unet_params["down_0_res_0"]
                                       ["out_norm"]))]["scale"]),
            np.asarray(a.unet_params["down_0_res_0"]["out_norm"]
                       ["GroupNorm_0"]["scale"])
            if "GroupNorm_0" in a.unet_params["down_0_res_0"]["out_norm"]
            else np.asarray(a.unet_params["down_0_res_0"]["out_norm"]
                            [next(iter(a.unet_params["down_0_res_0"]
                                       ["out_norm"]))]["scale"]),
            rtol=1e-6)
        # the top-level out_norm follows the OUT ratio (0.0 -> model2)
        top = m.unet_params["out_norm"]
        key = next(iter(top))
        np.testing.assert_allclose(
            np.asarray(top[key]["scale"]),
            np.asarray(b.unet_params["out_norm"][key]["scale"]),
            rtol=1e-6)
        # cache probe: re-execution returns the same object
        (m2,) = get_op("ModelMergeBlocks").execute(octx, a, b, 1.0, 1.0,
                                                   0.0)
        assert m2 is m
        registry.clear_pipeline_cache()


class TestUnCLIP:
    def test_vision_tower_encode_shapes(self):
        registry.clear_pipeline_cache()
        tower = registry.load_clip_vision("tiny-vision")
        rng = np.random.default_rng(2)
        img = rng.uniform(0, 1, (2, 48, 96, 3)).astype(np.float32)
        out = tower.encode(img, crop="center")
        assert out.image_embeds.shape == (2, 32)
        n_tok = (64 // 16) ** 2 + 1
        assert out.last_hidden.shape == (2, n_tok, 64)
        assert np.isfinite(np.asarray(out.image_embeds)).all()
        # center crop differs from squash on a non-square source
        out2 = tower.encode(img, crop="none")
        assert not np.allclose(np.asarray(out.image_embeds),
                               np.asarray(out2.image_embeds))
        registry.clear_pipeline_cache()

    def test_vision_checkpoint_round_trip(self, tmp_path):
        """A real HF-layout vision safetensors loads through the
        converter and matches the exporting params."""
        import os

        import jax as _jax
        from comfyui_distributed_tpu.models import clip_vision as cv
        from comfyui_distributed_tpu.models.checkpoints import \
            save_state_dict
        registry.clear_pipeline_cache()
        tower = registry.load_clip_vision("tiny-vision-rt")
        p = tower.params
        sd = {}
        sd["vision_model.embeddings.class_embedding"] = \
            np.asarray(p["class_embedding"], np.float32)
        sd["vision_model.embeddings.position_embedding.weight"] = \
            np.asarray(p["position_embedding"], np.float32)
        k = np.asarray(p["patch_embed"]["kernel"], np.float32)
        sd["vision_model.embeddings.patch_embedding.weight"] = \
            k.transpose(3, 2, 0, 1)
        for tk, fk in (("pre_layrnorm", "pre_ln"),
                       ("post_layernorm", "post_ln")):
            sd[f"vision_model.{tk}.weight"] = \
                np.asarray(p[fk]["scale"], np.float32)
            sd[f"vision_model.{tk}.bias"] = \
                np.asarray(p[fk]["bias"], np.float32)
        for i in range(tower.cfg.layers):
            lp = p[f"layers_{i}"]
            t = f"vision_model.encoder.layers.{i}"
            for tn, fn in (("layer_norm1", "ln1"), ("layer_norm2",
                                                    "ln2")):
                sd[f"{t}.{tn}.weight"] = np.asarray(lp[fn]["scale"])
                sd[f"{t}.{tn}.bias"] = np.asarray(lp[fn]["bias"])
            for tn, fn in (("self_attn.q_proj", "q"),
                           ("self_attn.k_proj", "k"),
                           ("self_attn.v_proj", "v"),
                           ("self_attn.out_proj", "proj"),
                           ("mlp.fc1", "fc1"), ("mlp.fc2", "fc2")):
                sd[f"{t}.{tn}.weight"] = \
                    np.asarray(lp[fn]["kernel"]).T
                sd[f"{t}.{tn}.bias"] = np.asarray(lp[fn]["bias"])
        sd["visual_projection.weight"] = \
            np.asarray(p["visual_projection"]["kernel"]).T
        d = os.path.join(str(tmp_path), "clip_vision")
        os.makedirs(d)
        # save_state_dict, NOT raw safetensors save_file: transposed
        # views silently round-trip WRONG through save_file (it ignores
        # strides) — the production saver makes arrays contiguous
        save_state_dict(sd, os.path.join(d, "tiny_vit.safetensors"))
        loaded = registry.load_clip_vision("tiny_vit.safetensors",
                                           models_dir=str(tmp_path),
                                           config_name="tiny")
        _jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6),
            loaded.params, tower.params)
        registry.clear_pipeline_cache()

    def test_unclip_conditioning_and_sampling(self, monkeypatch):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        monkeypatch.delenv(registry.FAMILY_ENV, raising=False)
        assert registry.detect_family("sd21-unclip-h.ckpt") \
            == "sd21_unclip"
        registry.clear_pipeline_cache()
        octx = OpContext()
        model, clip, vae, vision = get_op("unCLIPCheckpointLoader") \
            .execute(octx, "tiny-unclip-a.ckpt")
        assert model.family.adm_kind == "unclip"
        rng = np.random.default_rng(5)
        img = rng.uniform(0, 1, (1, 64, 64, 3)).astype(np.float32)
        (vout,) = get_op("CLIPVisionEncode").execute(octx, vision, img,
                                                     "center")
        pos = Conditioning(context=model.encode_prompt(["a fox"])[0])
        neg = Conditioning(context=model.encode_prompt([""])[0])
        (posu,) = get_op("unCLIPConditioning").execute(octx, pos, vout,
                                                       1.0, 0.1)
        assert len(posu.unclip) == 1
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (out,) = get_op("KSampler").execute(octx, model, 3, 2, 5.0,
                                            "euler", "normal", posu, neg,
                                            lat, 1.0)
        s = np.asarray(out["samples"])
        assert np.isfinite(s).all()
        # the image conditioning steers: dropping it changes the result
        (plain,) = get_op("KSampler").execute(octx, model, 3, 2, 5.0,
                                              "euler", "normal", pos,
                                              neg, lat, 1.0)
        assert not np.allclose(s, np.asarray(plain["samples"]))
        # higher noise augmentation changes the ADM
        (posn,) = get_op("unCLIPConditioning").execute(octx, pos, vout,
                                                       1.0, 0.9)
        (outn,) = get_op("KSampler").execute(octx, model, 3, 2, 5.0,
                                             "euler", "normal", posn,
                                             neg, lat, 1.0)
        assert not np.allclose(s, np.asarray(outn["samples"]))
        registry.clear_pipeline_cache()


class TestUnCLIPReviewFixes:
    def test_uncond_adm_is_zero_and_clamping(self):
        from comfyui_distributed_tpu.ops.base import Conditioning
        from comfyui_distributed_tpu.ops.basic import _unclip_vector_cond
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("tiny-unclip-fix.ckpt",
                                   family_name="tiny_unclip")
        # no entries -> zeros (the reference's zero-fill for uncond)
        z = _unclip_vector_cond(
            p, Conditioning(context=None), 2)
        np.testing.assert_array_equal(np.asarray(z),
                                      np.zeros((2, 64), np.float32))
        emb = np.ones((1, 32), np.float32)
        # negative augmentation clamps to level 0, >1 clamps to max
        lo = _unclip_vector_cond(
            p, Conditioning(context=None, unclip=((emb, 1.0, -0.5),)), 1)
        lo0 = _unclip_vector_cond(
            p, Conditioning(context=None, unclip=((emb, 1.0, 0.0),)), 1)
        np.testing.assert_array_equal(np.asarray(lo), np.asarray(lo0))
        hi = _unclip_vector_cond(
            p, Conditioning(context=None, unclip=((emb, 1.0, 2.0),)), 1)
        assert np.isfinite(np.asarray(hi)).all()
        # batched embeds: row 0 wins, with identical result to passing
        # row 0 directly
        b2 = np.stack([np.ones(32, np.float32),
                       np.full(32, 9.0, np.float32)])
        vb = _unclip_vector_cond(
            p, Conditioning(context=None, unclip=((b2, 1.0, 0.1),)), 1)
        v0 = _unclip_vector_cond(
            p, Conditioning(context=None, unclip=((b2[:1], 1.0, 0.1),)),
            1)
        np.testing.assert_array_equal(np.asarray(vb), np.asarray(v0))
        registry.clear_pipeline_cache()


class TestUnCLIPUncondZeroFill:
    def test_uncond_block_gets_zero_adm(self, monkeypatch):
        """The CFG uncond row must ride the negative's ZERO-filled ADM,
        not a replicated positive image embedding — otherwise
        cfg*(cond-uncond) cancels the image guidance."""
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext)
        from comfyui_distributed_tpu.ops.basic import \
            _prepare_sample_inputs
        monkeypatch.setenv(registry.FAMILY_ENV, "tiny_unclip")
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("zero-unc.ckpt")
        emb = np.ones((1, 32), np.float32)
        pos = Conditioning(context=p.encode_prompt(["a fox"])[0],
                           unclip=((emb, 1.0, 0.0),))
        neg = Conditioning(context=p.encode_prompt([""])[0])
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        prep = _prepare_sample_inputs(OpContext(), p, 0, lat, pos, neg)
        assert isinstance(prep.y, list) and len(prep.y) == 2
        assert not np.allclose(np.asarray(prep.y[0]), 0.0)
        np.testing.assert_array_equal(np.asarray(prep.y[1]),
                                      np.zeros_like(
                                          np.asarray(prep.y[1])))
        registry.clear_pipeline_cache()


class TestTokenMerging:
    def test_merge_unmerge_contract(self):
        """Kept tokens round-trip EXACTLY; merged tokens adopt their
        destination's row; r=0 is the identity."""
        from comfyui_distributed_tpu.models import tome
        rng = np.random.default_rng(3)
        h = w = 4
        x = jnp.asarray(rng.standard_normal((2, h * w, 8)), jnp.float32)
        m0, u0, r0 = tome.build_merge(x, h, w, 0.0)
        assert r0 == 0 and m0(x) is x and u0(x) is x
        merge, unmerge, r = tome.build_merge(x, h, w, 0.25)
        assert r == 4
        y = merge(x)
        assert y.shape == (2, h * w - r, 8)
        out = unmerge(y)
        assert out.shape == x.shape
        dst_idx, src_idx = tome.dst_grid_indices(h, w)
        # dst rows in the unmerge output must equal the pooled dst rows
        np.testing.assert_allclose(np.asarray(out[:, dst_idx]),
                                   np.asarray(y[:, -dst_idx.shape[0]:]),
                                   rtol=1e-6)
        # EXACT oracle: replicate the matching in numpy and assert the
        # full unmerge(merge(x)) output positionally
        xs = np.asarray(x)
        for b in range(2):
            mm = xs[b] / np.maximum(
                np.linalg.norm(xs[b], axis=-1, keepdims=True), 1e-6)
            scores = mm[src_idx] @ mm[dst_idx].T
            node_max = scores.max(-1)
            node_tgt = scores.argmax(-1)
            order = np.argsort(-node_max, kind="stable")
            merged_sel, kept_sel = order[:r], order[r:]
            pooled = xs[b][dst_idx].copy()
            cnt = np.ones(len(dst_idx), np.float32)
            for srow in merged_sel:
                pooled[node_tgt[srow]] += xs[b][src_idx[srow]]
                cnt[node_tgt[srow]] += 1.0
            pooled /= cnt[:, None]
            expect = np.empty_like(xs[b])
            expect[dst_idx] = pooled
            expect[src_idx[kept_sel]] = xs[b][src_idx[kept_sel]]
            expect[src_idx[merged_sel]] = pooled[node_tgt[merged_sel]]
            np.testing.assert_allclose(np.asarray(out[b]), expect,
                                       rtol=1e-5, atol=1e-6)

    def test_merge_pools_identical_tokens_losslessly(self):
        """If every token in a cell is identical, merging then
        unmerging an identity transform reconstructs the input
        EXACTLY (mean of identical rows = the row)."""
        from comfyui_distributed_tpu.models import tome
        h = w = 4
        base = np.random.default_rng(5).standard_normal((1, 4, 8))
        cells = np.repeat(np.repeat(
            base.reshape(1, 2, 2, 8), 2, axis=1), 2, axis=2) \
            .reshape(1, h * w, 8).astype(np.float32)
        x = jnp.asarray(cells)
        merge, unmerge, r = tome.build_merge(x, h, w, 0.5)
        assert r == 8
        np.testing.assert_allclose(np.asarray(unmerge(merge(x))),
                                   np.asarray(x), rtol=1e-5, atol=1e-5)

    def test_node_patches_and_steers(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("tome.ckpt")
        octx = OpContext()
        (pt,) = get_op("TomePatchModel").execute(octx, p, 0.3)
        assert pt.family.unet.tome_ratio == 0.3
        assert pt.unet_params is p.unet_params
        (p0,) = get_op("TomePatchModel").execute(octx, p, 0.0)
        assert p0 is p
        pos = Conditioning(context=p.encode_prompt(["a fox"])[0])
        lat = {"samples": np.zeros((1, 16, 16, 4), np.float32)}
        (a,) = get_op("KSampler").execute(octx, pt, 3, 2, 4.0, "euler",
                                          "normal", pos, pos, lat, 1.0)
        s = np.asarray(a["samples"])
        assert np.isfinite(s).all()
        (b,) = get_op("KSampler").execute(octx, p, 3, 2, 4.0, "euler",
                                          "normal", pos, pos, lat, 1.0)
        assert not np.allclose(s, np.asarray(b["samples"]))
        registry.clear_pipeline_cache()


class TestGligen:
    def test_position_net_and_fuser_shapes(self):
        import jax as _jax

        from comfyui_distributed_tpu.models import gligen as gg
        from comfyui_distributed_tpu.models.layers import \
            GatedSelfAttention
        registry.clear_pipeline_cache()
        gm = gg.load_gligen("tiny-gligen.pth", text_dim=64)
        embs = np.ones((1, 3, 64), np.float32)
        boxes = np.asarray([[[0, 0, .5, .5], [.5, 0, 1, .5],
                             [0, .5, 1, 1]]], np.float32)
        toks = gm.grounding_tokens(embs, boxes, np.ones((1, 3)))
        assert toks.shape == (1, 3, 64)
        nulls = gm.grounding_tokens(np.zeros_like(embs),
                                    np.zeros_like(boxes),
                                    np.zeros((1, 3)))
        assert not np.allclose(np.asarray(toks), np.asarray(nulls))
        # zero-init gates: a FRESH fuser is an exact no-op
        fus = GatedSelfAttention(num_heads=2, dtype=jnp.float32)
        x = jnp.asarray(np.random.default_rng(1).standard_normal(
            (1, 16, 32)), jnp.float32)
        params = fus.init(_jax.random.PRNGKey(0), x, toks)
        np.testing.assert_array_equal(np.asarray(fus.apply(params, x,
                                                           toks)),
                                      np.asarray(x))
        registry.clear_pipeline_cache()

    def test_textbox_apply_reaches_combined_siblings(self):
        """ADVICE r4: the reference applies the grounding spec to EVERY
        entry of the conditioning list — siblings bundled earlier by
        ConditioningCombine must carry it too, or their stacked blocks
        sample with null grounding tokens."""
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("gligen-base.ckpt")
        octx = OpContext()
        (gm,) = get_op("GLIGENLoader").execute(octx, "tiny-gligen.pth")
        a = Conditioning(context=p.encode_prompt(["a meadow"])[0])
        b = Conditioning(context=p.encode_prompt(["a lake"])[0])
        (a1,) = get_op("GLIGENTextBoxApply").execute(
            octx, a, p, gm, "a red fox", 32, 32, 0, 0)
        (b1,) = get_op("GLIGENTextBoxApply").execute(
            octx, b, p, gm, "a blue bird", 32, 32, 32, 32)
        (combined,) = get_op("ConditioningCombine").execute(octx, a1, b1)
        assert combined.siblings
        (grounded,) = get_op("GLIGENTextBoxApply").execute(
            octx, combined, p, gm, "a green tree", 16, 16, 16, 0)
        # head: its own prior box + the new one
        assert len(grounded.gligen[1]) == 2
        # sibling: ITS prior box (the bird) survives + the new one
        sib = grounded.siblings[0]
        assert len(sib.gligen[1]) == 2
        assert sib.gligen is not grounded.gligen
        np.testing.assert_array_equal(sib.gligen[1][0][0],
                                      b1.gligen[1][0][0])
        # distinct per-block specs sample end-to-end (stacked token
        # sets padded to a common object count)
        neg = Conditioning(context=p.encode_prompt([""])[0])
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (out,) = get_op("KSampler").execute(octx, p, 3, 2, 5.0, "euler",
                                            "normal", grounded, neg,
                                            lat, 1.0)
        assert np.isfinite(np.asarray(out["samples"])).all()

    def test_textbox_apply_and_sampling(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("gligen-base.ckpt")
        octx = OpContext()
        (gm,) = get_op("GLIGENLoader").execute(octx, "tiny-gligen.pth")
        pos = Conditioning(context=p.encode_prompt(["a meadow"])[0])
        neg = Conditioning(context=p.encode_prompt([""])[0])
        (posg,) = get_op("GLIGENTextBoxApply").execute(
            octx, pos, p, gm, "a red fox", 32, 32, 0, 0)
        (posg2,) = get_op("GLIGENTextBoxApply").execute(
            octx, posg, p, gm, "a blue bird", 32, 32, 32, 32)
        assert len(posg2.gligen[1]) == 2
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (out,) = get_op("KSampler").execute(octx, p, 3, 2, 5.0, "euler",
                                            "normal", posg2, neg, lat,
                                            1.0)
        s = np.asarray(out["samples"])
        assert np.isfinite(s).all()
        (plain,) = get_op("KSampler").execute(octx, p, 3, 2, 5.0,
                                              "euler", "normal", pos,
                                              neg, lat, 1.0)
        # virtual fusers zero-init their gates: grounded == plain
        # EXACTLY (the graft preserves the base weights bit-exact)
        np.testing.assert_allclose(s, np.asarray(plain["samples"]),
                                   rtol=2e-3, atol=2e-3)
        # boost the gates -> grounding steers
        from comfyui_distributed_tpu.ops.basic import gligen_attach
        pg = gligen_attach(p, gm)
        import jax as _jax

        def boost(path, a):
            kp = _jax.tree_util.keystr(path)
            if "alpha_attn" in kp or "alpha_dense" in kp:
                return jnp.full_like(a, 0.5)
            return a
        pg.unet_params = _jax.tree_util.tree_map_with_path(
            boost, pg.unet_params)
        pg._jit_cache.clear()
        (steered,) = get_op("KSampler").execute(octx, pg, 3, 2, 5.0,
                                                "euler", "normal",
                                                posg2, neg, lat, 1.0)
        assert np.isfinite(np.asarray(steered["samples"])).all()
        assert not np.allclose(np.asarray(steered["samples"]), s,
                               atol=1e-3)
        registry.clear_pipeline_cache()


class TestGligenCarryFlags:
    def test_flags_follow_the_carrying_entry(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        from comfyui_distributed_tpu.ops.basic import \
            _prepare_sample_inputs
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("gligen-flags.ckpt")
        octx = OpContext()
        (gm,) = get_op("GLIGENLoader").execute(octx, "tiny-gg2.pth")
        pos = Conditioning(context=p.encode_prompt(["a"])[0])
        neg = Conditioning(context=p.encode_prompt([""])[0])
        (negg,) = get_op("GLIGENTextBoxApply").execute(
            octx, neg, p, gm, "x", 16, 16, 0, 0)
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        # gligen on the NEGATIVE only: spec indices (pos=-1, neg=0)
        prep = _prepare_sample_inputs(octx, p, 0, lat, pos, negg)
        assert prep.gligen_objs is not None
        assert prep.gligen_objs[2] == (-1, 0)
        # and on the positive: (0, -1)
        (posg,) = get_op("GLIGENTextBoxApply").execute(
            octx, pos, p, gm, "x", 16, 16, 0, 0)
        prep2 = _prepare_sample_inputs(octx, p, 0, lat, posg, neg)
        assert prep2.gligen_objs[2] == (0, -1)
        # distinct specs on BOTH sides: each block keeps its own set
        prep3 = _prepare_sample_inputs(octx, p, 0, lat, posg, negg)
        assert prep3.gligen_objs[2] == (0, 1)
        assert prep3.gligen_objs[0].shape[0] == 2   # stacked [S, ...]
        registry.clear_pipeline_cache()


class TestComponentLoadersRound5:
    """CLIPLoader / DualCLIPLoader / UNETLoader: standalone towers
    assemble into usable wires (reference-ecosystem split-checkpoint
    workflows)."""

    def test_clip_save_load_round_trip(self, tmp_path):
        """CLIPSave's in-checkpoint-prefix export reloads through
        load_clip into a tower that encodes IDENTICALLY."""
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        registry.clear_pipeline_cache()
        pipe = registry.load_pipeline("cliprt.ckpt")
        octx = OpContext(output_dir=str(tmp_path))
        get_op("CLIPSave").execute(octx, pipe, "tower")
        loaded = registry.load_clip(["tower.safetensors"],
                                    models_dir=str(tmp_path),
                                    family_name="tiny")
        a, _ = pipe.encode_prompt(["a red fox"])
        b, _ = loaded.encode_prompt(["a red fox"])
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    def test_clip_loader_op_virtual_and_type_validation(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        (clip,) = get_op("CLIPLoader").execute(OpContext(), "solo.bin",
                                               "stable_diffusion")
        ctx_arr, _ = clip.encode_prompt(["x"])
        assert ctx_arr.shape[0] == 1
        with pytest.raises(ValueError):
            get_op("CLIPLoader").execute(OpContext(), "x.bin", "nope")
        with pytest.raises(ValueError):   # sdxl needs the dual loader
            get_op("CLIPLoader").execute(OpContext(), "x.bin", "sdxl")

    def test_dual_clip_loader_sdxl_towers(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        (clip,) = get_op("DualCLIPLoader").execute(
            OpContext(), "clip_l.safetensors", "clip_g.safetensors",
            "sdxl")
        assert len(clip.clip_params) == 2
        ctx_arr, pooled = clip.encode_prompt(["x"])
        # SDXL concat: CLIP-L width + bigG width
        assert ctx_arr.shape[-1] == sum(c.width
                                        for c in clip.family.clips)

    def test_unet_loader_samples_end_to_end(self):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        (model,) = get_op("UNETLoader").execute(OpContext(),
                                                "tiny-solo-unet.sft")
        assert model.family.name == "tiny"
        pos = Conditioning(context=model.encode_prompt(["x"])[0])
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (out,) = get_op("KSampler").execute(OpContext(), model, 3, 2,
                                            3.0, "euler", "normal", pos,
                                            pos, lat, 1.0)
        assert np.isfinite(np.asarray(out["samples"])).all()


class TestModelMergeArithmetic:
    """ModelMergeAdd / ModelMergeSubtract — the add-difference pair."""

    def test_subtract_then_add_round_trips(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        registry.clear_pipeline_cache()
        a = registry.load_pipeline("ma.ckpt")
        b = registry.load_pipeline("mb.ckpt")
        octx = OpContext()
        (delta,) = get_op("ModelMergeSubtract").execute(octx, a, b, 1.0)
        (back,) = get_op("ModelMergeAdd").execute(octx, delta, b)
        import jax
        for la, lb in zip(jax.tree_util.tree_leaves(a.unet_params),
                          jax.tree_util.tree_leaves(back.unet_params)):
            np.testing.assert_allclose(np.asarray(la, np.float32),
                                       np.asarray(lb, np.float32),
                                       rtol=1e-3, atol=1e-3)

    def test_family_mismatch_raises(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        a = registry.load_pipeline("ma.ckpt")
        c = registry.load_pipeline("inp.ckpt",
                                   family_name="tiny_inpaint")
        with pytest.raises(ValueError):
            get_op("ModelMergeAdd").execute(OpContext(), a, c)


class TestImageBlendOp:
    def _imgs(self):
        a = np.full((1, 4, 4, 3), 0.5, np.float32)
        b = np.full((1, 4, 4, 3), 0.25, np.float32)
        return a, b

    def test_modes(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        a, b = self._imgs()
        op = get_op("ImageBlend")
        octx = OpContext()
        (normal,) = op.execute(octx, a, b, 1.0, "normal")
        np.testing.assert_allclose(normal, 0.25)
        (mult,) = op.execute(octx, a, b, 1.0, "multiply")
        np.testing.assert_allclose(mult, 0.125)
        (scr,) = op.execute(octx, a, b, 1.0, "screen")
        np.testing.assert_allclose(scr, 1 - 0.5 * 0.75, rtol=1e-6)
        (diff,) = op.execute(octx, a, b, 1.0, "difference")
        np.testing.assert_allclose(diff, 0.25)
        (ovl,) = op.execute(octx, a, b, 1.0, "overlay")
        np.testing.assert_allclose(ovl, 0.25, rtol=1e-6)  # a<=0.5: 2ab
        (half,) = op.execute(octx, a, b, 0.5, "normal")
        np.testing.assert_allclose(half, 0.375)
        (soft,) = op.execute(octx, a, b, 1.0, "soft_light")
        assert np.all((soft >= 0) & (soft <= 1))
        with pytest.raises(ValueError):
            op.execute(octx, a, b, 1.0, "dodge")

    def test_mismatched_sizes_resize(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        a = np.zeros((1, 8, 8, 3), np.float32)
        b = np.ones((1, 4, 4, 3), np.float32)
        (out,) = get_op("ImageBlend").execute(OpContext(), a, b, 1.0,
                                              "normal")
        assert out.shape == a.shape
        np.testing.assert_allclose(out, 1.0)


class TestInstructPixToPix:
    def test_conditioning_and_sampling(self, monkeypatch):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        registry.clear_pipeline_cache()
        monkeypatch.delenv(registry.FAMILY_ENV, raising=False)
        assert registry.detect_family("tiny-ip2p.ckpt") == "tiny_ip2p"
        assert registry.detect_family(
            "instruct-pix2pix-00-22000.safetensors") == "sd15_ip2p"
        pipe = registry.load_pipeline("tiny-ip2p.ckpt")
        assert pipe.family.unet.in_channels == 8
        octx = OpContext()
        img = np.random.default_rng(0).random((1, 16, 16, 3)
                                              ).astype(np.float32)
        pos = Conditioning(context=pipe.encode_prompt(["make it snowy"])[0])
        neg = Conditioning(context=pipe.encode_prompt([""])[0])
        (p2, n2, lat) = get_op("InstructPixToPixConditioning").execute(
            octx, pos, neg, pipe, img)
        assert p2.concat_latent is not None
        assert n2.concat_latent is not None
        np.testing.assert_array_equal(np.asarray(lat["samples"]), 0.0)
        assert lat["samples"].shape[-1] == 4
        (out,) = get_op("KSampler").execute(octx, pipe, 3, 2, 3.0,
                                            "euler", "normal", p2, n2,
                                            lat, 1.0)
        assert np.isfinite(np.asarray(out["samples"])).all()
        registry.clear_pipeline_cache()


class TestRound5SaveMergeTail:
    def test_clip_merge_subtract_then_add_round_trips(self):
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        registry.clear_pipeline_cache()
        a = registry.load_pipeline("cma.ckpt")
        b = registry.load_pipeline("cmb.ckpt")
        octx = OpContext()
        (delta,) = get_op("CLIPMergeSubtract").execute(octx, a, b, 1.0)
        (back,) = get_op("CLIPMergeAdd").execute(octx, delta, b)
        import jax
        for ta, tb in zip(a.clip_params, back.clip_params):
            for la, lb in zip(jax.tree_util.tree_leaves(ta),
                              jax.tree_util.tree_leaves(tb)):
                np.testing.assert_allclose(np.asarray(la, np.float32),
                                           np.asarray(lb, np.float32),
                                           rtol=1e-3, atol=1e-3)

    def test_model_save_unet_loader_round_trip(self, tmp_path,
                                               monkeypatch):
        """ModelSave's model.diffusion_model export reloads through
        UNETLoader into a pipeline whose UNet forward matches."""
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        registry.clear_pipeline_cache()
        pipe = registry.load_pipeline("msave.ckpt")
        octx = OpContext(output_dir=str(tmp_path),
                         models_dir=str(tmp_path))
        get_op("ModelSave").execute(octx, pipe, "unet_rt")
        monkeypatch.delenv(registry.FAMILY_ENV, raising=False)
        # geometry validation: the tiny-geometry file against the
        # name-detected sd15 config must FAIL LOUDLY, not mis-load
        with pytest.raises(KeyError):
            get_op("UNETLoader").execute(octx, "unet_rt.safetensors")
        registry.clear_pipeline_cache()
        loaded = registry.load_unet("unet_rt.safetensors",
                                    models_dir=str(tmp_path),
                                    family_name="tiny")
        import jax
        x = jnp.zeros((1, 8, 8, 4))
        ts = jnp.zeros((1,))
        c = jnp.zeros((1, 77, pipe.family.unet.context_dim))
        a = pipe.unet.apply({"params": pipe.unet_params}, x, ts, c)
        b = loaded.unet.apply({"params": loaded.unet_params}, x, ts, c)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)
        registry.clear_pipeline_cache()


class TestSDXLRefinerFamily:
    def test_detection_geometry_and_prefix(self, monkeypatch):
        monkeypatch.delenv(registry.FAMILY_ENV, raising=False)
        assert registry.detect_family("sd_xl_refiner_1.0.safetensors") \
            == "sdxl_refiner"
        assert registry.detect_family("sd_xl_base_1.0.safetensors") \
            == "sdxl"
        fam = registry.FAMILIES["sdxl_refiner"]
        assert fam.unet.model_channels == 384
        assert fam.unet.transformer_depth == (0, 4, 4, 0)
        assert fam.unet.transformer_depth_middle == 4
        assert fam.unet.context_dim == 1280
        assert fam.unet.adm_in_channels == 2560
        assert len(fam.clips) == 1
        assert fam.clips[0].layout == "openclip"
        from comfyui_distributed_tpu.models.checkpoints import \
            _clip_prefixes
        assert _clip_prefixes(fam) == ["conditioner.embedders.0.model."]

    def test_refiner_shaped_unet_forward_and_key_walk(self):
        """A scaled-down refiner geometry (edge levels without attention
        + an explicit middle depth) must forward AND round-trip through
        the converter's key walk (missing/extra keys fail loudly)."""
        import dataclasses as dc

        import jax
        from comfyui_distributed_tpu.models.checkpoints import (
            _ExportMapper, _LoadMapper, _run_unet)
        from comfyui_distributed_tpu.models.unet import (UNet, UNetConfig,
                                                         mid_depth)
        cfg = UNetConfig(model_channels=16, channel_mult=(1, 2, 4, 4),
                         num_res_blocks=1,
                         transformer_depth=(0, 1, 1, 0),
                         transformer_depth_middle=2,
                         context_dim=32, num_head_channels=8,
                         adm_in_channels=48,
                         use_linear_in_transformer=True,
                         dtype=jnp.float32)
        assert mid_depth(cfg) == 2
        model = UNet(cfg)
        x = jnp.zeros((1, 16, 16, 4))
        ts = jnp.zeros((1,))
        c = jnp.zeros((1, 7, 32))
        y = jnp.zeros((1, 48))
        params = model.init(jax.random.PRNGKey(0), x, ts, c, y=y)["params"]
        out = model.apply({"params": params}, x, ts, c, y=y)
        assert out.shape == x.shape
        sd = _run_unet(_ExportMapper(params, ""), cfg)
        # the middle transformer carries BOTH depth blocks in the export
        assert any("middle_block.1.transformer_blocks.1." in k
                   for k in sd)
        back = _run_unet(_LoadMapper(sd, ""), cfg)
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_refiner_ascore_reaches_full_width_adm(self):
        """The 5th scalar (aesthetic_score) lands in the 2560-wide
        refiner ADM vector: different scores give different vectors."""
        from comfyui_distributed_tpu.ops.base import Conditioning
        from comfyui_distributed_tpu.ops.basic import _sdxl_vector_cond

        class _U:
            adm_in_channels = 2560

        class _F:
            unet = _U()

        class _P:
            family = _F()

        pooled = np.full((1, 1280), 0.2, np.float32)
        vecs = {}
        for score in (2.0, 9.0):
            vecs[score] = np.asarray(_sdxl_vector_cond(
                _P(), Conditioning(context=None, pooled=pooled,
                                   size_cond=(64, 64, 0, 0, score)),
                1, 64, 64))
        assert vecs[2.0].shape == (1, 2560)
        assert not np.allclose(vecs[2.0], vecs[9.0])

    def test_refiner_size_cond_steers_sampling(self):
        """CLIPTextEncodeSDXLRefiner's scalar conditioning reaches the
        UNet end-to-end: different size scalars give different samples
        (tiny_sdxl stand-in — its 128-wide ADM carries the pooled + the
        first scalar's embedding)."""
        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("ref-asc.ckpt",
                                   family_name="tiny_sdxl")
        octx = OpContext()
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        outs = {}
        for height in (32, 640):
            (cond,) = get_op("CLIPTextEncodeSDXLRefiner").execute(
                octx, p, 6.0, 64, height, "crisp photo")
            assert cond.size_cond == (height, 64, 0, 0, 6.0)
            (out,) = get_op("KSampler").execute(
                octx, p, 3, 2, 3.0, "euler", "normal", cond, cond, lat,
                1.0)
            outs[height] = np.asarray(out["samples"])
        assert np.isfinite(outs[32]).all()
        assert not np.allclose(outs[32], outs[640])
        registry.clear_pipeline_cache()
