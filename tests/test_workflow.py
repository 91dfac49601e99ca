"""Workflow engine: parsing the reference JSONs, execution, SPMD fan-out."""

import copy
import json
import os

import jax
import numpy as np
import pytest

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.ops.base import OpContext
from comfyui_distributed_tpu.parallel import mesh as mesh_mod
from comfyui_distributed_tpu.workflow import WorkflowExecutor, parse_workflow

_TESTS = os.path.dirname(os.path.abspath(__file__))
_WORKFLOWS = os.path.join(os.path.dirname(_TESTS), "workflows")
TXT2IMG = os.path.join(_WORKFLOWS, "distributed-txt2img.json")
UPSCALE = os.path.join(_WORKFLOWS, "distributed-upscale.json")
# the txt2img graph as the ComfyUI front end saves it (UI format); no
# workflow the repo ships is written in that format
TXT2IMG_UI = os.path.join(_TESTS, "fixtures", "distributed-txt2img.ui.json")


def _only(g, class_type):
    """The id of the graph's one node of this type."""
    (nid,) = g.find_by_type(class_type)
    return nid


@pytest.fixture(autouse=True)
def tiny_family(monkeypatch):
    monkeypatch.setenv(registry.FAMILY_ENV, "tiny")
    yield


@pytest.fixture
def ctx():
    return OpContext(runtime=mesh_mod.MeshRuntime(mesh=mesh_mod.build_mesh()))


class TestParse:
    """The saved (UI) format: ``nodes`` / ``links``, positional
    ``widgets_values``, bypassed and muted nodes, round trip to API
    format."""

    def test_txt2img_parses(self):
        g = parse_workflow(TXT2IMG_UI)
        assert len(g.nodes) == 9
        ks = g.nodes[_only(g, "KSampler")]
        # widget mapping: [seed, control, steps, cfg, sampler, scheduler, den]
        assert ks.inputs["steps"] == 20
        assert ks.inputs["cfg"] == 7.0
        assert ks.inputs["sampler_name"] == "euler"
        assert ks.inputs["scheduler"] == "karras"
        assert ks.inputs["denoise"] == 1
        # seed widget overridden by the link from DistributedSeed
        assert ks.inputs["seed"] == [_only(g, "DistributedSeed"), 0]
        assert g.nodes[_only(g, "EmptyLatentImage")].inputs["width"] == 1024
        # the same nodes, inputs and edges as the API file it was written
        # from, before the bypass / mute cases below edit it
        assert g.to_api_format() == parse_workflow(TXT2IMG).to_api_format()

    def test_upscale_parses(self):
        g = parse_workflow(UPSCALE)
        assert len(g.nodes) == 9
        up = g.nodes[_only(g, "UltimateSDUpscaleDistributed")]
        assert up.inputs["tile_width"] == 512
        assert up.inputs["padding"] == 32
        assert up.inputs["mask_blur"] == 8
        assert up.inputs["force_uniform_tiles"] is True
        assert abs(up.inputs["denoise"] - 0.35) < 1e-6
        assert up.inputs["upscaled_image"] == [_only(g, "ImageScale"), 0]

    def test_topo_order(self):
        g = parse_workflow(TXT2IMG_UI)
        order = g.topo_order()
        at = {g.nodes[n].class_type: order.index(n) for n in order}
        assert at["CheckpointLoaderSimple"] < at["KSampler"]
        assert at["KSampler"] < at["VAEDecode"]
        assert at["DistributedCollector"] < at["PreviewImage"]

    def test_cycle_detection(self):
        g = parse_workflow(json.dumps({
            "a": {"class_type": "VAEDecode",
                  "inputs": {"samples": ["b", 0], "vae": ["b", 1]}},
            "b": {"class_type": "VAEDecode",
                  "inputs": {"samples": ["a", 0], "vae": ["a", 1]}},
        }))
        with pytest.raises(ValueError, match="cycle"):
            g.topo_order()

    def test_bypassed_node_passes_through(self):
        """Mode-4 (bypass) nodes are removed with links rewired through
        type-matching inputs — ComfyUI bypass semantics."""
        whole = parse_workflow(TXT2IMG_UI)
        doc = json.load(open(TXT2IMG_UI))
        for n in doc["nodes"]:
            if n["type"] == "DistributedCollector":
                n["mode"] = 4
        g = parse_workflow(doc)
        assert _only(whole, "DistributedCollector") not in g.nodes
        # PreviewImage now feeds directly from VAEDecode
        assert g.nodes[_only(g, "PreviewImage")].inputs["images"] == \
            [_only(g, "VAEDecode"), 0]

    def test_muted_node_drops_link(self):
        whole = parse_workflow(TXT2IMG_UI)
        doc = json.load(open(TXT2IMG_UI))
        for n in doc["nodes"]:
            if n["type"] == "DistributedSeed":
                n["mode"] = 2
        g = parse_workflow(doc)
        assert _only(whole, "DistributedSeed") not in g.nodes
        # KSampler keeps its widget seed; the dead link is dropped
        assert isinstance(g.nodes[_only(g, "KSampler")].inputs["seed"], int)

    def test_api_format_round_trip(self):
        g = parse_workflow(TXT2IMG_UI)
        api = g.to_api_format()
        g2 = parse_workflow(json.dumps(api))
        assert set(g2.nodes) == set(g.nodes)
        assert g2.nodes[_only(g2, "KSampler")].inputs["steps"] == 20


def _scaled_txt2img(width=64, height=64, steps=2, batch=1):
    """The txt2img fixture with sizes/steps scaled for CPU tests."""
    g = parse_workflow(TXT2IMG)
    g.nodes[_only(g, "EmptyLatentImage")].inputs.update(
        width=width, height=height, batch_size=batch)
    g.nodes[_only(g, "KSampler")].inputs.update(steps=steps)
    return g


class TestTxt2ImgE2E:
    def test_fanout_produces_replica_batch(self, ctx):
        res = WorkflowExecutor(ctx).execute(_scaled_txt2img())
        # 8 mesh slots x batch 1, collected master-first
        assert len(res.images) == 8
        imgs = np.stack(res.images)
        assert imgs.shape == (8, 16, 16, 3)  # tiny VAE upscales latent x2
        # distributed seed => every replica's image differs
        for i in range(1, 8):
            assert not np.allclose(imgs[0], imgs[i]), f"replica {i} == master"

    def test_determinism(self, ctx):
        r1 = WorkflowExecutor(ctx).execute(_scaled_txt2img())
        ctx2 = OpContext(runtime=ctx.runtime)
        r2 = WorkflowExecutor(ctx2).execute(_scaled_txt2img())
        assert np.allclose(np.stack(r1.images), np.stack(r2.images))

    def test_plain_seed_replicates_identically(self, ctx):
        """Without DistributedSeed all participants produce the same images
        (reference parity: seed fan-out is what makes replicas differ)."""
        g = _scaled_txt2img()
        g.nodes[_only(g, "KSampler")].inputs["seed"] = 1234  # break link
        res = WorkflowExecutor(ctx).execute(g)
        imgs = np.stack(res.images)
        assert imgs.shape[0] == 8
        for i in range(1, 8):
            assert np.allclose(imgs[0], imgs[i], atol=1e-5)

    def test_worker_mode_no_fanout(self):
        """Worker processes run the graph without batch expansion."""
        ctx = OpContext(runtime=mesh_mod.MeshRuntime(mesh=mesh_mod.build_mesh()),
                        is_worker=True, worker_id="worker_2")
        res = WorkflowExecutor(ctx).execute(_scaled_txt2img())
        assert len(res.images) == 1

    def test_timings_recorded(self, ctx):
        res = WorkflowExecutor(ctx).execute(_scaled_txt2img())
        assert set(res.timings) == set(parse_workflow(TXT2IMG).nodes)
        assert res.total_s > 0


IMG2IMG = "/root/repo/workflows/distributed-img2img.json"


def _scaled_img2img(size=32, steps=2):
    """The img2img variation-sweep fixture scaled for CPU tests."""
    g = parse_workflow(IMG2IMG)
    g.nodes["1"].inputs["image"] = "__missing__.png"    # synthetic test card
    g.nodes["2"].inputs.update(width=size, height=size)
    g.nodes["3"].inputs.update(steps=steps)
    return g


class TestImg2ImgE2E:
    """BASELINE config 4: seed-offset fan-out over one VAE-encoded source
    (every participant denoises the same latent with its own seed)."""

    def test_variation_sweep_fans_out(self, ctx):
        res = WorkflowExecutor(ctx).execute(_scaled_img2img())
        assert len(res.images) == 8
        imgs = np.stack(res.images)
        assert imgs.shape == (8, 32, 32, 3)
        # same source latent + distributed seed => variations, not copies
        for i in range(1, 8):
            assert not np.allclose(imgs[0], imgs[i]), \
                f"variation {i} identical to master"

    def test_plain_seed_gives_identical_variations(self, ctx):
        g = _scaled_img2img()
        g.nodes["3"].inputs["seed"] = 77  # break link, plain int
        res = WorkflowExecutor(ctx).execute(g)
        imgs = np.stack(res.images)
        assert imgs.shape[0] == 8
        for i in range(1, 8):
            assert np.allclose(imgs[0], imgs[i], atol=1e-5)

    def test_worker_mode_single_variation(self):
        ctx = OpContext(runtime=mesh_mod.MeshRuntime(mesh=mesh_mod.build_mesh()),
                        is_worker=True, worker_id="worker_1")
        res = WorkflowExecutor(ctx).execute(_scaled_img2img())
        assert len(res.images) == 1

    def test_side_branch_not_fanned_out(self, ctx):
        """A branch with no distributed node runs once even when the graph
        has a distributed component elsewhere (reference parity: workers
        are pruned to the connected component, gpupanel.js:1045-1071).
        The side branch needs its OWN loader — sharing node 4 would merge
        the components via the bidirectional walk, as in the reference."""
        g = _scaled_img2img()
        g2 = parse_workflow(json.dumps({
            "20": {"class_type": "CheckpointLoaderSimple",
                   "inputs": {"ckpt_name": "side.ckpt"}},
            "21": {"class_type": "EmptyLatentImage",
                   "inputs": {"width": 32, "height": 32, "batch_size": 1}},
            "22": {"class_type": "CLIPTextEncode",
                   "inputs": {"text": "side", "clip": ["20", 1]}},
            "23": {"class_type": "KSampler",
                   "inputs": {"seed": 5, "steps": 1, "cfg": 1.0,
                              "sampler_name": "euler", "scheduler": "normal",
                              "denoise": 1.0, "model": ["20", 0],
                              "positive": ["22", 0], "negative": ["22", 0],
                              "latent_image": ["21", 0]}},
            "24": {"class_type": "VAEDecode",
                   "inputs": {"samples": ["23", 0], "vae": ["20", 2]}},
            "25": {"class_type": "PreviewImage",
                   "inputs": {"images": ["24", 0]}}}))
        g.nodes.update(g2.nodes)
        res = WorkflowExecutor(ctx).execute(g)
        # 8 fanned variations + exactly 1 side-branch image
        assert len(res.images) == 9

    def test_hires_fix_chain_not_reexpanded(self, ctx):
        """A mid-graph VAEEncode (hires-fix: sample -> decode -> upscale ->
        re-encode -> refine) must NOT tile an already-fanned batch again:
        8 variations stay 8, not 64."""
        g = parse_workflow(json.dumps({
            "4": {"class_type": "CheckpointLoaderSimple",
                  "inputs": {"ckpt_name": "hires.ckpt"}},
            "5": {"class_type": "EmptyLatentImage",
                  "inputs": {"width": 16, "height": 16, "batch_size": 1}},
            "6": {"class_type": "CLIPTextEncode",
                  "inputs": {"text": "x", "clip": ["4", 1]}},
            "13": {"class_type": "DistributedSeed", "inputs": {"seed": 9}},
            "3": {"class_type": "KSampler",
                  "inputs": {"seed": ["13", 0], "steps": 1, "cfg": 1.0,
                             "sampler_name": "euler", "scheduler": "normal",
                             "denoise": 1.0, "model": ["4", 0],
                             "positive": ["6", 0], "negative": ["6", 0],
                             "latent_image": ["5", 0]}},
            "8": {"class_type": "VAEDecode",
                  "inputs": {"samples": ["3", 0], "vae": ["4", 2]}},
            "16": {"class_type": "UpscaleModelLoader",
                   "inputs": {"model_name": "2x_hires.pth"}},
            "17": {"class_type": "ImageUpscaleWithModel",
                   "inputs": {"upscale_model": ["16", 0],
                              "image": ["8", 0]}},
            "10": {"class_type": "ImageScale",
                   "inputs": {"image": ["17", 0],
                              "upscale_method": "lanczos",
                              "width": 32, "height": 32,
                              "crop": "disabled"}},
            "11": {"class_type": "VAEEncode",
                   "inputs": {"pixels": ["10", 0], "vae": ["4", 2]}},
            "12": {"class_type": "KSampler",
                   "inputs": {"seed": ["13", 0], "steps": 1, "cfg": 1.0,
                              "sampler_name": "euler", "scheduler": "normal",
                              "denoise": 0.5, "model": ["4", 0],
                              "positive": ["6", 0], "negative": ["6", 0],
                              "latent_image": ["11", 0]}},
            "15": {"class_type": "VAEDecode",
                   "inputs": {"samples": ["12", 0], "vae": ["4", 2]}},
            "14": {"class_type": "DistributedCollector",
                   "inputs": {"images": ["15", 0]}},
            "9": {"class_type": "PreviewImage",
                  "inputs": {"images": ["14", 0]}}}))
        res = WorkflowExecutor(ctx).execute(g)
        imgs = np.stack(res.images)
        assert imgs.shape == (8, 32, 32, 3), imgs.shape
        # refined variations still differ per replica
        assert not np.allclose(imgs[0], imgs[1])

    def test_denoise_below_one_preserves_source_structure(self, ctx):
        """img2img at low denoise stays closer to the source than a fresh
        txt2img sample from the same seed would — the encoded latent must
        actually be the starting point (add_noise on top of source)."""
        g = _scaled_img2img()
        g.nodes["3"].inputs["denoise"] = 0.1
        res_low = WorkflowExecutor(ctx).execute(g)
        g2 = _scaled_img2img()
        g2.nodes["3"].inputs["denoise"] = 1.0
        res_full = WorkflowExecutor(ctx).execute(g2)
        # the source card is a smooth gradient; at denoise 0.1 the output
        # must correlate with it far more than the fully-resampled one
        from comfyui_distributed_tpu.ops.base import get_op
        card = get_op("LoadImage").execute(OpContext(), "__missing__.png")[0]
        card = get_op("ImageScale").execute(
            OpContext(), card, "lanczos", 32, 32)[0][0]

        def err(r):
            return float(np.mean(np.abs(np.stack(r.images) - card[None])))

        assert err(res_low) < err(res_full)


HIRES = "/root/repo/workflows/distributed-hires-fix.json"


class TestHiresFixE2E:
    """The staged hires-fix fixture: LoraLoader -> CLIPSetLastLayer ->
    KSamplerAdvanced (leftover noise) -> LatentUpscale -> KSamplerAdvanced
    finish, fanned over the mesh."""

    def test_hires_fix_fans_out(self, ctx):
        g = parse_workflow(HIRES)
        # scale for CPU: tiny latents, 1+1 steps (LatentUpscale divides
        # pixel widgets by 8, ComfyUI convention)
        g.nodes["5"].inputs.update(width=32, height=32)
        g.nodes["3"].inputs.update(steps=2, end_at_step=1)
        g.nodes["10"].inputs.update(width=64, height=64)
        g.nodes["11"].inputs.update(steps=2, start_at_step=1)
        res = WorkflowExecutor(ctx).execute(g)
        assert len(res.images) == 8
        imgs = np.stack(res.images)
        # tiny VAE: 8x8 latent (64//8) -> 16px image at downscale 2
        assert imgs.shape == (8, 16, 16, 3)
        for i in range(1, 8):
            assert not np.allclose(imgs[0], imgs[i]), \
                f"variation {i} identical to master"

    def test_latent_upscale_preserves_fanout_meta(self, ctx):
        from comfyui_distributed_tpu.ops.base import get_op
        lat = {"samples": np.zeros((8, 8, 8, 4), np.float32),
               "local_batch": 1, "fanout": 8}
        (out,) = get_op("LatentUpscale").execute(
            ctx, lat, "nearest-exact", 128, 128)
        assert out["samples"].shape == (8, 16, 16, 4)
        assert out["fanout"] == 8 and out["local_batch"] == 1
        (out2,) = get_op("LatentUpscaleBy").execute(ctx, lat, "bilinear",
                                                    1.5)
        assert out2["samples"].shape == (8, 12, 12, 4)
        assert out2["fanout"] == 8

    def test_latent_upscale_rectangular_and_zero_dims(self, ctx):
        """Non-square targets (argument-order tripwire), width/height=0
        aspect-derivation, 0/0 passthrough, and center crop — ComfyUI's
        LatentUpscale conventions."""
        from comfyui_distributed_tpu.ops.base import get_op
        op = get_op("LatentUpscale")
        lat = {"samples": np.zeros((1, 8, 16, 4), np.float32)}  # H=8, W=16
        (r,) = op.execute(ctx, lat, "bilinear", 256, 64)   # W=32, H=8
        assert r["samples"].shape == (1, 8, 32, 4)
        (r,) = op.execute(ctx, lat, "bilinear", 0, 128)    # H=16, W by AR
        assert r["samples"].shape == (1, 16, 32, 4)
        (r,) = op.execute(ctx, lat, "bilinear", 128, 0)    # W=16, H by AR
        assert r["samples"].shape == (1, 8, 16, 4)
        (r,) = op.execute(ctx, lat, "bilinear", 0, 0)      # passthrough
        assert r["samples"].shape == (1, 8, 16, 4)
        # center crop: 2:1 latent -> square target without distortion
        (r,) = op.execute(ctx, lat, "bilinear", 128, 128, "center")
        assert r["samples"].shape == (1, 16, 16, 4)

    def test_latent_upscale_by_rectangular(self, ctx):
        from comfyui_distributed_tpu.ops.base import get_op
        lat = {"samples": np.zeros((1, 8, 16, 4), np.float32)}
        (r,) = get_op("LatentUpscaleBy").execute(ctx, lat, "bilinear", 2.0)
        assert r["samples"].shape == (1, 16, 32, 4)
        img = np.zeros((1, 8, 16, 3), np.float32)
        (ri,) = get_op("ImageScaleBy").execute(ctx, img, "bilinear", 2.0)
        assert ri.shape == (1, 16, 32, 3)

    def test_image_scale_by_preserves_fanout_meta(self, ctx):
        from comfyui_distributed_tpu.ops.base import get_op
        from comfyui_distributed_tpu.ops.basic import ImageBatch
        img = ImageBatch(np.zeros((8, 16, 16, 3), np.float32),
                         local_batch=1, fanout=8)
        (out,) = get_op("ImageScaleBy").execute(ctx, img, "bilinear", 2.0)
        assert out.shape == (8, 32, 32, 3)
        assert out.fanout == 8


INPAINT = "/root/repo/workflows/distributed-inpaint.json"
OUTPAINT = "/root/repo/workflows/distributed-outpaint.json"


class TestInpaintE2E:
    def test_inpaint_fixture_fans_out_masked_variations(self, ctx,
                                                        tmp_path):
        """The inpaint fixture over the mesh: every participant resamples
        the masked region with its own seed.  (The unmasked LATENT is
        anchored exactly — covered by test_models.TestInpainting; decoded
        pixels are NOT asserted stable because the VAE decoder's global
        mid-block attention mixes every latent into every pixel.)"""
        from PIL import Image
        # source card with an alpha channel: alpha=0 right half -> mask=1
        rgba = np.zeros((32, 32, 4), np.uint8)
        rgba[..., :3] = 128
        rgba[..., 3] = 255
        rgba[:, 16:, 3] = 0                    # LoadImage: mask = 1-alpha
        (tmp_path / "in").mkdir()
        Image.fromarray(rgba).save(tmp_path / "in" / "card.png")
        ctx.input_dir = str(tmp_path / "in")

        g = parse_workflow(INPAINT)
        g.nodes["1"].inputs["image"] = "card.png"
        g.nodes["2"].inputs.update(width=32, height=32)
        g.nodes["5"].inputs.update(grow_mask_by=0)
        g.nodes["3"].inputs.update(steps=2)
        res = WorkflowExecutor(ctx).execute(g)
        assert len(res.images) == 8
        imgs = np.stack(res.images)
        # masked halves differ across replicas (seed fan-out).  NOTE: the
        # unmasked LATENT region is anchored exactly (unit-tested in
        # test_models.TestInpainting); pixel-exact stability does not
        # survive VAE decode because the decoder's mid-block attention is
        # global — every output pixel attends to every latent (true of
        # the torch stack as well)
        for i in range(1, 8):
            assert not np.allclose(imgs[0][:, 16:], imgs[i][:, 16:]), \
                f"variation {i} masked region identical to master"
        assert np.isfinite(imgs).all()


    def test_outpaint_fixture_extends_and_fans_out(self, ctx, tmp_path):
        """The outpaint fixture: pad-right canvas extension, feathered
        mask into VAEEncodeForInpaint, seed fan-out of the new area."""
        from PIL import Image
        rgb = np.full((32, 32, 3), 64, np.uint8)
        (tmp_path / "in").mkdir()
        Image.fromarray(rgb).save(tmp_path / "in" / "src.png")
        ctx.input_dir = str(tmp_path / "in")

        g = parse_workflow(OUTPAINT)
        g.nodes["1"].inputs["image"] = "src.png"
        g.nodes["2"].inputs.update(width=32, height=32)
        g.nodes["10"].inputs.update(right=16, feathering=4)
        g.nodes["5"].inputs.update(grow_mask_by=0)
        g.nodes["3"].inputs.update(steps=2)
        res = WorkflowExecutor(ctx).execute(g)
        assert len(res.images) == 8
        imgs = np.stack(res.images)
        assert imgs.shape[1:] == (32, 48, 3)   # canvas extended right
        assert np.isfinite(imgs).all()
        # the outpainted right side varies across replicas (seed fan-out)
        for i in range(1, 8):
            assert not np.allclose(imgs[0][:, 32:], imgs[i][:, 32:]), i

    def test_batch_gt1_mask_fans_out(self, ctx):
        """ADVICE r3 (medium): a batch>1 noise_mask must fan out with the
        latents — pre-fix only B=1 worked (by broadcasting) and B>1
        crashed with a shape error inside the jitted sampler."""
        from comfyui_distributed_tpu.ops.base import Conditioning, get_op
        pipe = registry.load_pipeline("maskfan.ckpt")
        ctx_arr, _ = pipe.encode_prompt(["x"])
        pos = Conditioning(context=ctx_arr, pooled=None)
        fanout = ctx.fanout = len(jax.devices())
        assert fanout > 1
        b = 2
        lat = np.tile(np.zeros((b, 8, 8, 4), np.float32),
                      (fanout, 1, 1, 1))
        mask = np.zeros((b, 64, 64), np.float32)
        mask[:, :, 32:] = 1.0                    # resample the right half
        latent = {"samples": lat, "local_batch": b, "fanout": fanout,
                  "noise_mask": mask}
        (out,) = get_op("KSampler").execute(ctx, pipe, 7, 2, 1.5, "euler",
                                            "normal", pos, pos, latent, 1.0)
        s = np.asarray(out["samples"])
        assert s.shape[0] == b * fanout
        assert np.isfinite(s).all()
        # unmasked (left) half anchored exactly to the zero source...
        np.testing.assert_array_equal(s[:, :, :4, :],
                                      np.zeros_like(s[:, :, :4, :]))
        # ...masked half resampled
        assert not np.allclose(s[:, :, 4:, :], 0.0)


def _scaled_upscale(tile=32, padding=8, blur=2, steps=1):
    g = parse_workflow(UPSCALE)
    # synthetic test card
    g.nodes[_only(g, "LoadImage")].inputs["image"] = "__missing__.png"
    g.nodes[_only(g, "ImageScale")].inputs.update(width=64, height=64)
    g.nodes[_only(g, "UltimateSDUpscaleDistributed")].inputs.update(
        steps=steps, tile_width=tile, tile_height=tile, padding=padding,
        mask_blur=blur)
    return g


class TestUpscaleE2E:
    def test_distributed_tiled_upscale(self, ctx):
        res = WorkflowExecutor(ctx).execute(_scaled_upscale())
        assert len(res.images) == 1
        out = res.images[0]
        assert out.shape == (64, 64, 3)
        assert np.isfinite(out).all()

    def test_spmd_matches_single_device_oracle(self, ctx):
        """Golden test (SURVEY.md §4): the distributed path must match the
        single-device path — same per-tile seeds, same blend order."""
        res_d = WorkflowExecutor(ctx).execute(_scaled_upscale())
        ctx_s = OpContext(runtime=ctx.runtime)
        ctx_s.runtime.enabled = False  # num_participants -> 1
        try:
            res_s = WorkflowExecutor(ctx_s).execute(_scaled_upscale())
        finally:
            ctx.runtime.enabled = True
        np.testing.assert_allclose(res_d.images[0], res_s.images[0],
                                   atol=2e-3)


class TestRegionalTiledUpscale:
    """VERDICT r4 #4: regional conditioning entries refine with their
    masks cropped through the tile windows (instead of the loud
    primary-prompt fallback)."""

    def _regional_conds(self, pipe):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      OpContext, get_op)
        octx = OpContext()
        a = Conditioning(context=pipe.encode_prompt(["blue sky"])[0])
        b = Conditioning(context=pipe.encode_prompt(["green forest"])[0])
        left = np.zeros((64, 64), np.float32)
        left[:, :32] = 1.0
        (am,) = get_op("ConditioningSetMask").execute(octx, a, left, 1.0)
        (bm,) = get_op("ConditioningSetMask").execute(octx, b,
                                                      1.0 - left, 1.0)
        (combined,) = get_op("ConditioningCombine").execute(octx, am, bm)
        neg = Conditioning(context=pipe.encode_prompt([""])[0])
        return combined, neg

    def _upscale(self, ctx, pipe, positive, negative):
        from comfyui_distributed_tpu.ops.base import get_op
        rng = np.random.default_rng(3)
        img = rng.random((1, 64, 64, 3)).astype(np.float32)
        (out,) = get_op("UltimateSDUpscaleDistributed").execute(
            ctx, img, pipe, positive, negative, pipe, 5, 1, 4.0,
            "euler", "normal", 0.4, 32, 32, 8, 2, True)
        return np.asarray(out)

    def test_regional_spmd_matches_single_device_oracle(self, ctx):
        pipe = registry.load_pipeline("regup.ckpt")
        pos, neg = self._regional_conds(pipe)
        out_d = self._upscale(ctx, pipe, pos, neg)
        ctx_s = OpContext(runtime=ctx.runtime)
        ctx_s.runtime.enabled = False
        try:
            out_s = self._upscale(ctx_s, pipe, pos, neg)
        finally:
            ctx.runtime.enabled = True
        assert np.isfinite(out_d).all()
        np.testing.assert_allclose(out_d, out_s, atol=2e-3)

    def test_regional_masks_engage(self, ctx):
        """The cropped masks must actually reach the sampler: the
        regional result differs from refining with the primary prompt
        alone (the old fallback behavior)."""
        from comfyui_distributed_tpu.ops.base import Conditioning
        pipe = registry.load_pipeline("regup.ckpt")
        pos, neg = self._regional_conds(pipe)
        out_r = self._upscale(ctx, pipe, pos, neg)
        primary = Conditioning(context=pipe.encode_prompt(["blue sky"])[0])
        out_p = self._upscale(ctx, pipe, primary, neg)
        assert not np.allclose(out_r, out_p, atol=1e-4)


class TestRepoFixtures:
    """The repo's own workflow fixtures (same node-type surface as the
    reference's two workflows) parse and execute end-to-end on the virtual
    mesh with tiny virtual checkpoints."""

    def _ctx(self, tmp_path, monkeypatch):
        import os
        monkeypatch.setenv("DTPU_DEFAULT_FAMILY", "tiny")
        from comfyui_distributed_tpu.models import registry
        registry.clear_pipeline_cache()
        from comfyui_distributed_tpu.ops.base import OpContext
        from comfyui_distributed_tpu.parallel.mesh import MeshRuntime, build_mesh
        rt = MeshRuntime(mesh=build_mesh({"data": 2, "tensor": 1, "seq": 1},
                                         devices=jax.devices()[:2]))
        os.makedirs(tmp_path / "input", exist_ok=True)
        return OpContext(runtime=rt, input_dir=str(tmp_path / "input"),
                         output_dir=str(tmp_path / "out"))

    def test_txt2img_fixture(self, tmp_path, monkeypatch):
        from comfyui_distributed_tpu.workflow import WorkflowExecutor, parse_workflow
        g = parse_workflow("/root/repo/workflows/distributed-txt2img.json")
        g.nodes["5"].inputs.update(width=64, height=64, batch_size=1)
        g.nodes["3"].inputs.update(steps=2)
        res = WorkflowExecutor(self._ctx(tmp_path, monkeypatch)).execute(g)
        assert len(res.images) == 2  # fan-out x2 over the data axis
        # EmptyLatentImage uses the ComfyUI /8 contract; the tiny family's
        # VAE only upsamples x2, so 64px request -> 8px latent -> 16px image
        assert res.images[0].shape == (16, 16, 3)

    def test_upscale_fixture(self, tmp_path, monkeypatch):
        import numpy as np
        from PIL import Image
        ctx = self._ctx(tmp_path, monkeypatch)
        Image.fromarray(
            (np.random.default_rng(0).random((64, 64, 3)) * 255
             ).astype("uint8")).save(f"{ctx.input_dir}/input.png")
        from comfyui_distributed_tpu.workflow import WorkflowExecutor, parse_workflow
        g = parse_workflow("/root/repo/workflows/distributed-upscale.json")
        g.nodes["16"].inputs.update(width=128, height=128)
        g.nodes["2"].inputs.update(steps=1, tile_width=64, tile_height=64,
                                   padding=8, mask_blur=2)
        res = WorkflowExecutor(ctx).execute(g)
        assert len(res.images) == 1
        assert res.images[0].shape == (128, 128, 3)


class TestRegionalE2E:
    def test_regional_fixture_fans_out(self, ctx):
        """The regional fixture: two prompts on canvas halves, combined,
        seed-fanned; replicas differ, output finite."""
        g = parse_workflow("/root/repo/workflows/distributed-regional.json")
        g.nodes["2"].inputs.update(width=32, height=32)
        g.nodes["3"].inputs.update(steps=2)
        res = WorkflowExecutor(ctx).execute(g)
        assert len(res.images) == 8
        imgs = np.stack(res.images)
        assert np.isfinite(imgs).all()
        for i in range(1, 8):
            assert not np.allclose(imgs[0], imgs[i]), i


class TestCustomSamplerWidgetBinding:
    def test_sampler_custom_ui_widgets_skip_control_slot(self):
        """ComfyUI UI exports serialize seed widgets with a trailing
        control_after_generate; SamplerCustom/RandomNoise must declare
        the CONTROL slot so cfg doesn't receive 'randomize'."""
        from comfyui_distributed_tpu.workflow.graph import \
            _widgets_to_inputs
        got = _widgets_to_inputs("SamplerCustom",
                                 [True, 5, "randomize", 4.5])
        assert got["add_noise"] is True
        assert got["noise_seed"] == 5
        assert got["cfg"] == 4.5
        assert "control_after_generate" not in got
        got = _widgets_to_inputs("RandomNoise", [7, "fixed"])
        assert got["noise_seed"] == 7


class TestMaskCompositeNodes:
    """SolidMask / InvertMask / GrowMask / MaskComposite / Image* /
    Latent* composite family (ComfyUI mask toolchain)."""

    def _op(self, name):
        from comfyui_distributed_tpu.ops.base import get_op
        return get_op(name)

    def _ctx(self):
        from comfyui_distributed_tpu.ops.base import OpContext
        return OpContext()

    def test_solid_invert_grow(self):
        octx = self._ctx()
        (m,) = self._op("SolidMask").execute(octx, 0.25, 8, 6)
        assert m.shape == (1, 6, 8) and np.all(m == 0.25)
        (inv,) = self._op("InvertMask").execute(octx, m)
        assert np.allclose(inv, 0.75)
        point = np.zeros((1, 7, 7), np.float32)
        point[0, 3, 3] = 1.0
        (grown,) = self._op("GrowMask").execute(octx, point, 1, True)
        assert grown[0, 3, 3] == 1 and grown[0, 2, 3] == 1
        assert grown[0, 2, 2] == 0          # tapered: no corners
        (grown2,) = self._op("GrowMask").execute(octx, point, 1, False)
        assert grown2[0, 2, 2] == 1         # full 3x3
        (shrunk,) = self._op("GrowMask").execute(octx, grown, -1, True)
        np.testing.assert_array_equal(shrunk, point)

    def test_mask_composite_ops(self):
        octx = self._ctx()
        d = np.ones((1, 4, 4), np.float32)
        s = np.full((1, 2, 2), 1.0, np.float32)
        (sub,) = self._op("MaskComposite").execute(octx, d, s, 1, 1,
                                                   "subtract")
        assert sub[0, 1, 1] == 0.0 and sub[0, 0, 0] == 1.0
        (xor,) = self._op("MaskComposite").execute(octx, d, s, 0, 0,
                                                   "xor")
        assert xor[0, 0, 0] == 0.0 and xor[0, 3, 3] == 1.0
        with pytest.raises(ValueError):
            self._op("MaskComposite").execute(octx, d, s, 0, 0, "nope")

    def test_empty_image_and_crop_and_batch(self):
        octx = self._ctx()
        (img,) = self._op("EmptyImage").execute(octx, 8, 4, 2, 0xFF0000)
        assert img.shape == (2, 4, 8, 3)
        assert np.allclose(img[..., 0], 1.0) and np.allclose(img[..., 1:],
                                                             0.0)
        (crop,) = self._op("ImageCrop").execute(octx, img, 4, 2, 2, 1)
        assert crop.shape == (2, 2, 4, 3)
        (inv,) = self._op("ImageInvert").execute(octx, img)
        assert np.allclose(inv[..., 0], 0.0)
        small = np.zeros((1, 2, 4, 3), np.float32)
        (batch,) = self._op("ImageBatch").execute(octx, img, small)
        assert batch.shape == (3, 4, 8, 3)

    def test_image_composite_masked(self):
        octx = self._ctx()
        dest = np.zeros((1, 4, 4, 3), np.float32)
        src = np.ones((1, 2, 2, 3), np.float32)
        (out,) = self._op("ImageCompositeMasked").execute(
            octx, dest, src, 1, 1, False, None)
        assert out[0, 1, 1, 0] == 1.0 and out[0, 0, 0, 0] == 0.0
        mask = np.zeros((1, 2, 2), np.float32)
        mask[0, 0, 0] = 1.0
        (mout,) = self._op("ImageCompositeMasked").execute(
            octx, dest, src, 1, 1, False, mask)
        assert mout[0, 1, 1, 0] == 1.0 and mout[0, 2, 2, 0] == 0.0
        # negative offset crops the source, no wraparound
        (neg,) = self._op("ImageCompositeMasked").execute(
            octx, dest, src, -1, -1, False, None)
        assert neg[0, 0, 0, 0] == 1.0 and neg[0, 1, 1, 0] == 0.0
        assert neg[0, 3, 3, 0] == 0.0

    def test_latent_composites_preserve_meta(self):
        octx = self._ctx()
        to = {"samples": np.zeros((2, 8, 8, 4), np.float32),
              "fanout": 2, "local_batch": 1}
        frm = {"samples": np.ones((1, 4, 4, 4), np.float32)}
        (out,) = self._op("LatentComposite").execute(octx, to, frm,
                                                     16, 16, 0)
        assert out["fanout"] == 2 and out["local_batch"] == 1
        s = out["samples"]
        assert s[0, 2, 2, 0] == 1.0 and s[0, 1, 1, 0] == 0.0
        assert s[1, 2, 2, 0] == 1.0          # short batch cycles
        (fe,) = self._op("LatentComposite").execute(octx, to, frm,
                                                    16, 16, 16)
        sf = fe["samples"]
        assert 0.0 < sf[0, 2, 2, 0] < 1.0    # feather edge ramp
        # border-flush paste: no ramp on the flush (top/left) edges,
        # ramp only toward interior dest content (ComfyUI edge rule)
        (flush,) = self._op("LatentComposite").execute(octx, to, frm,
                                                       0, 0, 16)
        sfl = flush["samples"]
        assert sfl[0, 0, 0, 0] == 1.0        # flush corner stays solid
        assert 0.0 < sfl[0, 3, 3, 0] < 1.0   # interior edge ramps
        # corner toward interior: rates multiply, not min
        assert np.isclose(sfl[0, 3, 3, 0], 0.25)
        mask = np.ones((1, 4, 4), np.float32)
        mask[0, :, :2] = 0.0
        (lm,) = self._op("LatentCompositeMasked").execute(
            octx, to, frm, 16, 16, False, mask)
        sm = lm["samples"]
        assert sm[0, 2, 2, 0] == 0.0 and sm[0, 2, 5, 0] == 1.0


class TestLatentImageUtilityNodes:
    """Round-4 utility batch: latent transforms, image filters,
    conditioning utils."""

    def _op(self, name):
        from comfyui_distributed_tpu.ops.base import get_op
        return get_op(name)

    def _ctx(self):
        from comfyui_distributed_tpu.ops.base import OpContext
        return OpContext()

    def test_latent_flip_rotate_crop(self):
        octx = self._ctx()
        lat = {"samples": np.arange(2 * 4 * 6 * 1, dtype=np.float32)
               .reshape(2, 4, 6, 1), "fanout": 2, "local_batch": 1}
        (fx,) = self._op("LatentFlip").execute(octx, lat,
                                               "x-axis: vertically")
        np.testing.assert_array_equal(fx["samples"][:, ::-1],
                                      lat["samples"])
        assert fx["fanout"] == 2
        (fy,) = self._op("LatentFlip").execute(octx, lat,
                                               "y-axis: horizontally")
        np.testing.assert_array_equal(fy["samples"][:, :, ::-1],
                                      lat["samples"])
        (r90,) = self._op("LatentRotate").execute(octx, lat, "90 degrees")
        assert r90["samples"].shape == (2, 6, 4, 1)
        (r360s,) = self._op("LatentRotate").execute(
            octx, r90, "270 degrees")
        np.testing.assert_array_equal(r360s["samples"], lat["samples"])
        (cr,) = self._op("LatentCrop").execute(octx, lat, 16, 16, 8, 8)
        assert cr["samples"].shape == (2, 2, 2, 1)
        np.testing.assert_array_equal(cr["samples"],
                                      lat["samples"][:, 1:3, 1:3])

    def test_latent_blend_and_batch(self):
        octx = self._ctx()
        a = {"samples": np.ones((2, 4, 4, 4), np.float32)}
        b = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        (bl,) = self._op("LatentBlend").execute(octx, a, b, 0.25)
        assert bl["samples"].shape == (2, 4, 4, 4)
        np.testing.assert_allclose(bl["samples"], 0.25)
        (bt,) = self._op("LatentBatch").execute(octx, a, b)
        assert bt["samples"].shape == (3, 4, 4, 4)

    def test_conditioning_zero_out_and_strength(self):
        from comfyui_distributed_tpu.ops.base import Conditioning
        octx = self._ctx()
        c = Conditioning(context=np.ones((1, 77, 16), np.float32),
                         pooled=np.ones((1, 32), np.float32))
        (z,) = self._op("ConditioningZeroOut").execute(octx, c)
        assert np.all(np.asarray(z.context) == 0)
        assert np.all(np.asarray(z.pooled) == 0)
        (s,) = self._op("ConditioningSetAreaStrength").execute(octx, c,
                                                               0.4)
        assert s.area_strength == 0.4

    def test_image_blur_sharpen_quantize_scale(self):
        octx = self._ctx()
        rng = np.random.default_rng(2)
        img = rng.uniform(0, 1, (1, 16, 16, 3)).astype(np.float32)
        (bl,) = self._op("ImageBlur").execute(octx, img, 2, 1.5)
        assert bl.shape == img.shape
        assert bl.std() < img.std()          # blur reduces variance
        flat = np.full((1, 8, 8, 3), 0.5, np.float32)
        (blf,) = self._op("ImageBlur").execute(octx, flat, 3, 2.0)
        np.testing.assert_allclose(blf, 0.5, atol=1e-6)  # edge replicate
        (sh,) = self._op("ImageSharpen").execute(octx, img, 2, 1.5, 1.0)
        assert sh.shape == img.shape
        assert sh.std() > bl.std()
        (q,) = self._op("ImageQuantize").execute(octx, img, 4, "none")
        assert q.shape == img.shape
        assert len(np.unique(q.reshape(-1, 3), axis=0)) <= 4
        (sc,) = self._op("ImageScaleToTotalPixels").execute(
            octx, img, "bilinear", 0.001)
        assert abs(sc.shape[1] * sc.shape[2] - 0.001 * 1024 * 1024) \
            < 0.25 * 0.001 * 1024 * 1024


class TestRound4Fixtures:
    """The round-4 feature fixtures execute end-to-end on the virtual
    mesh with tiny virtual checkpoints (same scaling recipe as
    TestRepoFixtures)."""

    def _ctx(self, tmp_path, monkeypatch, family="tiny"):
        import os
        monkeypatch.setenv("DTPU_DEFAULT_FAMILY", family)
        registry.clear_pipeline_cache()
        from comfyui_distributed_tpu.parallel.mesh import (MeshRuntime,
                                                           build_mesh)
        rt = MeshRuntime(mesh=build_mesh(
            {"data": 2, "tensor": 1, "seq": 1},
            devices=jax.devices()[:2]))
        os.makedirs(tmp_path / "input", exist_ok=True)
        return OpContext(runtime=rt, input_dir=str(tmp_path / "input"),
                         output_dir=str(tmp_path / "out"))

    def test_sdxl_dualprompt_fixture(self, tmp_path, monkeypatch):
        from comfyui_distributed_tpu.workflow import (WorkflowExecutor,
                                                      parse_workflow)
        g = parse_workflow("/root/repo/workflows/distributed-sdxl.json")
        g.nodes["2"].inputs.update(width=64, height=64, batch_size=1)
        g.nodes["6"].inputs.update(steps=2)
        # tiny_sdxl: an ADM-bearing family so the dual-prompt size
        # conds actually reach the UNet (plain 'tiny' would skip the
        # whole y path)
        ctx = self._ctx(tmp_path, monkeypatch, family="tiny_sdxl")
        res = WorkflowExecutor(ctx).execute(g)
        assert len(res.images) == 2
        imgs = np.stack(res.images)
        assert np.isfinite(imgs).all()
        assert not np.allclose(imgs[0], imgs[1])
        # the explicit size conds steer: a different declared size
        # changes the prepared ADM vector (deterministic regression net
        # for size_cond handling — image-level inequality at 2 steps
        # proved order-flaky across the full suite)
        from comfyui_distributed_tpu.ops.base import get_op
        from comfyui_distributed_tpu.ops.basic import \
            _prepare_sample_inputs
        p = registry.load_pipeline("sd_xl_base_1.0.safetensors")
        octx2 = OpContext()
        (c1,) = get_op("CLIPTextEncodeSDXL").execute(
            octx2, p, 1024, 1024, 0, 0, 1024, 1024, "a", "b")
        (c2,) = get_op("CLIPTextEncodeSDXL").execute(
            octx2, p, 256, 256, 0, 0, 256, 256, "a", "b")
        lat = {"samples": np.zeros((1, 8, 8, 4), np.float32)}
        # through the SAMPLER prep path (not the helper directly): the
        # size_cond must reach the prepared ADM the KSampler consumes
        y1 = np.asarray(_prepare_sample_inputs(octx2, p, 0, lat, c1,
                                               c1).y)
        y2 = np.asarray(_prepare_sample_inputs(octx2, p, 0, lat, c2,
                                               c2).y)
        assert y1.shape == (1, 128)
        assert not np.allclose(y1, y2)

    def test_inpaint_model_fixture(self, tmp_path, monkeypatch):
        from comfyui_distributed_tpu.workflow import (WorkflowExecutor,
                                                      parse_workflow)
        g = parse_workflow(
            "/root/repo/workflows/distributed-inpaint-model.json")
        g.nodes["8"].inputs.update(steps=2)
        # the synthetic 512px test card would be a 256x256-token latent
        # for the tiny family: rescale the pixel path to 64px
        from comfyui_distributed_tpu.workflow.graph import Node
        g.nodes["2s"] = Node(id="2s", class_type="ImageScale",
                             inputs={"image": ["2", 0],
                                     "upscale_method": "bilinear",
                                     "width": 64, "height": 64,
                                     "crop": "disabled"})
        g.nodes["6"].inputs["pixels"] = ["2s", 0]
        ctx = self._ctx(tmp_path, monkeypatch, family="tiny_inpaint")
        res = WorkflowExecutor(ctx).execute(g)
        assert len(res.images) == 2
        assert np.isfinite(np.stack(res.images)).all()

    def test_unclip_fixture(self, tmp_path, monkeypatch):
        from comfyui_distributed_tpu.workflow import (WorkflowExecutor,
                                                      parse_workflow)
        g = parse_workflow(
            "/root/repo/workflows/distributed-unclip.json")
        g.nodes["7"].inputs.update(width=64, height=64, batch_size=1)
        g.nodes["9"].inputs.update(steps=2)
        monkeypatch.setenv("DTPU_DEFAULT_FAMILY", "tiny_unclip")
        ctx = self._ctx(tmp_path, monkeypatch, family="tiny_unclip")
        res = WorkflowExecutor(ctx).execute(g)
        assert len(res.images) == 2
        imgs = np.stack(res.images)
        assert np.isfinite(imgs).all()
        assert not np.allclose(imgs[0], imgs[1])


class TestCannyBatchMorphoNodes:
    def _op(self, name):
        from comfyui_distributed_tpu.ops.base import get_op
        return get_op(name)

    def _ctx(self):
        return OpContext()

    def test_canny_finds_a_box_edge(self):
        octx = self._ctx()
        img = np.zeros((1, 32, 32, 3), np.float32)
        img[:, 8:24, 8:24] = 1.0
        (edges,) = self._op("Canny").execute(octx, img, 0.1, 0.3)
        assert edges.shape == (1, 32, 32, 3)
        assert set(np.unique(edges)) <= {0.0, 1.0}
        # edges ring the box, interior and background stay empty
        assert edges[0, 8, 16, 0] == 1.0 or edges[0, 7, 16, 0] == 1.0
        assert edges[0, 16, 16, 0] == 0.0
        assert edges[0, 2, 2, 0] == 0.0
        # a flat image has no edges
        (none,) = self._op("Canny").execute(
            octx, np.full((1, 16, 16, 3), 0.5, np.float32), 0.1, 0.3)
        assert none.sum() == 0.0

    def test_image_from_batch_and_rebatch(self):
        octx = self._ctx()
        img = np.arange(3 * 4 * 4 * 3, dtype=np.float32) \
            .reshape(3, 4, 4, 3)
        (one,) = self._op("ImageFromBatch").execute(octx, img, 1, 1)
        np.testing.assert_array_equal(one, img[1:2])
        (two,) = self._op("ImageFromBatch").execute(octx, img, 1, 2)
        assert two.shape[0] == 2
        (rb,) = self._op("RebatchImages").execute(octx, img, 2)
        np.testing.assert_array_equal(rb, img)
        lat = {"samples": np.ones((2, 4, 4, 4), np.float32),
               "fanout": 2}
        (rl,) = self._op("RebatchLatents").execute(octx, lat, 1)
        assert rl["fanout"] == 2

    def test_morphology_ops(self):
        octx = self._ctx()
        img = np.zeros((1, 9, 9, 3), np.float32)
        img[:, 4, 4] = 1.0
        (d,) = self._op("Morphology").execute(octx, img, "dilate", 3)
        assert d[0, 3, 3, 0] == 1.0 and d[0, 1, 1, 0] == 0.0
        (e,) = self._op("Morphology").execute(octx, d, "erode", 3)
        np.testing.assert_array_equal(e, img)
        (g,) = self._op("Morphology").execute(octx, img, "gradient", 3)
        # gradient of a point: dilation minus erosion is 1 across the
        # whole dilated neighborhood (erosion of a point is empty)
        assert g[0, 4, 4, 0] == 1.0 and g[0, 3, 4, 0] == 1.0
        assert g[0, 1, 1, 0] == 0.0
        with pytest.raises(ValueError):
            self._op("Morphology").execute(octx, img, "nope", 3)


class TestMaskToolchainCompletion:
    def _op(self, name):
        from comfyui_distributed_tpu.ops.base import get_op
        return get_op(name)

    def test_mask_image_conversions(self):
        octx = OpContext()
        m = np.zeros((1, 4, 4), np.float32)
        m[0, 1, 2] = 0.8
        (img,) = self._op("MaskToImage").execute(octx, m)
        assert img.shape == (1, 4, 4, 3)
        np.testing.assert_array_equal(img[..., 0], m)
        (back,) = self._op("ImageToMask").execute(octx, img, "red")
        np.testing.assert_array_equal(back, m)
        rgb = np.zeros((1, 2, 2, 3), np.float32)
        rgb[0, 0, 1] = [1.0, 0.0, 0.0]
        (cm,) = self._op("ImageColorToMask").execute(octx, rgb,
                                                     0xFF0000)
        assert cm[0, 0, 1] == 1.0 and cm.sum() == 1.0

    def test_crop_feather_threshold(self):
        octx = OpContext()
        m = np.ones((1, 8, 8), np.float32)
        (cr,) = self._op("CropMask").execute(octx, m, 2, 2, 4, 4)
        assert cr.shape == (1, 4, 4)
        (fe,) = self._op("FeatherMask").execute(octx, m, 2, 2, 0, 0)
        # reference rate (t+1)/margin: edge 1/2, inner row reaches 1.0
        assert fe[0, 0, 4] == 0.5 and fe[0, 1, 4] == 1.0
        assert fe[0, 4, 7] == 1.0                 # right untouched
        assert fe[0, 0, 0] == fe[0, 0, 4] * fe[0, 4, 0]  # corners mult
        # margin 1 is a no-op (the reference's semantics)
        (noop,) = self._op("FeatherMask").execute(octx, m, 1, 1, 1, 1)
        np.testing.assert_array_equal(noop, m)
        soft = np.linspace(0, 1, 16, dtype=np.float32).reshape(1, 4, 4)
        (th,) = self._op("ThresholdMask").execute(octx, soft, 0.5)
        assert set(np.unique(th)) <= {0.0, 1.0}
        assert th.sum() == (soft > 0.5).sum()

    def test_style_model_apply(self):
        octx = OpContext()
        from comfyui_distributed_tpu.ops.base import Conditioning
        registry.clear_pipeline_cache()
        (sm,) = self._op("StyleModelLoader").execute(octx,
                                                     "tiny-style.pth")
        vision = registry.load_clip_vision("tiny-style-vision")
        rng = np.random.default_rng(3)
        img = rng.uniform(0, 1, (1, 64, 64, 3)).astype(np.float32)
        (vout,) = self._op("CLIPVisionEncode").execute(octx, vision,
                                                       img, "center")
        c = Conditioning(context=np.zeros((1, 7, 64), np.float32))
        (out,) = self._op("StyleModelApply").execute(octx, c, sm, vout)
        assert out.context.shape == (1, 7 + sm.cfg.num_tokens, 64)
        assert np.isfinite(np.asarray(out.context)).all()
        # style tokens depend on the image
        img2 = rng.uniform(0, 1, (1, 64, 64, 3)).astype(np.float32)
        (vout2,) = self._op("CLIPVisionEncode").execute(octx, vision,
                                                        img2, "center")
        (out2,) = self._op("StyleModelApply").execute(octx, c, sm,
                                                      vout2)
        assert not np.allclose(np.asarray(out.context[:, 7:]),
                               np.asarray(out2.context[:, 7:]))
        registry.clear_pipeline_cache()


class TestCompositingAndSeedBehavior:
    def _op(self, name):
        from comfyui_distributed_tpu.ops.base import get_op
        return get_op(name)

    def test_porter_duff_modes(self):
        octx = OpContext()
        cs = np.full((1, 2, 2, 3), 0.8, np.float32)
        cd = np.full((1, 2, 2, 3), 0.2, np.float32)
        a1 = np.ones((1, 2, 2), np.float32)
        a0 = np.zeros((1, 2, 2), np.float32)
        op = self._op("PorterDuffImageComposite")
        # SRC_OVER with opaque source = source
        c, a = op.execute(octx, cs, a1, cd, a1, "SRC_OVER")
        np.testing.assert_allclose(c, 0.8, atol=1e-6)
        np.testing.assert_allclose(a, 1.0)
        # SRC_OVER with transparent source: the reference feeds
        # STRAIGHT values into the premultiplied formula (its known
        # quirk) -> cs + cd, clipped
        c, a = op.execute(octx, cs, a0, cd, a1, "SRC_OVER")
        np.testing.assert_allclose(c, 1.0, atol=1e-5)
        np.testing.assert_allclose(a, 1.0)
        # DST_IN with opaque source keeps the destination exactly
        c, a = op.execute(octx, cs, a1, cd, a1, "DST_IN")
        np.testing.assert_allclose(c, 0.2, atol=1e-5)
        # SCREEN formula
        c, _ = op.execute(octx, cs, a1, cd, a1, "SCREEN")
        np.testing.assert_allclose(c, 0.8 + 0.2 - 0.16, atol=1e-5)
        # DST ignores the source entirely
        c, a = op.execute(octx, cs, a1, cd, a1, "DST")
        np.testing.assert_allclose(c, 0.2, atol=1e-6)
        # MULTIPLY / ADD / DARKEN / LIGHTEN formulas
        c, _ = op.execute(octx, cs, a1, cd, a1, "MULTIPLY")
        np.testing.assert_allclose(c, 0.16, atol=1e-5)
        c, _ = op.execute(octx, cs, a1, cd, a1, "ADD")
        np.testing.assert_allclose(c, 1.0)
        c, _ = op.execute(octx, cs, a1, cd, a1, "DARKEN")
        np.testing.assert_allclose(c, 0.2, atol=1e-5)
        c, _ = op.execute(octx, cs, a1, cd, a1, "LIGHTEN")
        np.testing.assert_allclose(c, 0.8, atol=1e-5)
        # CLEAR zeroes everything
        c, a = op.execute(octx, cs, a1, cd, a1, "CLEAR")
        assert c.sum() == 0.0 and a.sum() == 0.0
        with pytest.raises(ValueError):
            op.execute(octx, cs, a1, cd, a1, "NOPE")

    def test_alpha_split_join_round_trip(self):
        octx = OpContext()
        rng = np.random.default_rng(4)
        rgba = rng.uniform(0, 1, (1, 4, 4, 4)).astype(np.float32)
        rgb, mask = self._op("SplitImageWithAlpha").execute(octx, rgba)
        np.testing.assert_array_equal(rgb, rgba[..., :3])
        np.testing.assert_allclose(mask, 1.0 - rgba[..., 3])
        (joined,) = self._op("JoinImageWithAlpha").execute(octx, rgb,
                                                           mask)
        np.testing.assert_allclose(joined, rgba, atol=1e-6)

    def test_seed_behavior_fixed_gives_identical_batch(self, ctx):
        from comfyui_distributed_tpu.ops.base import (Conditioning,
                                                      get_op)
        registry.clear_pipeline_cache()
        p = registry.load_pipeline("seedfix.ckpt")
        octx = OpContext()
        pos = Conditioning(context=p.encode_prompt(["a fox"])[0])
        lat = {"samples": np.zeros((3, 8, 8, 4), np.float32)}
        (fixed,) = get_op("LatentBatchSeedBehavior").execute(
            octx, lat, "fixed")
        (out,) = get_op("KSampler").execute(octx, p, 5, 2, 4.0, "euler",
                                            "normal", pos, pos, fixed,
                                            1.0)
        s = np.asarray(out["samples"])
        np.testing.assert_allclose(s[0], s[1], atol=1e-5)
        np.testing.assert_allclose(s[0], s[2], atol=1e-5)
        (rand,) = get_op("LatentBatchSeedBehavior").execute(
            octx, lat, "random")
        (out2,) = get_op("KSampler").execute(octx, p, 5, 2, 4.0,
                                             "euler", "normal", pos,
                                             pos, rand, 1.0)
        s2 = np.asarray(out2["samples"])
        assert not np.allclose(s2[0], s2[1])
        registry.clear_pipeline_cache()


class TestLatentAndAnimatedIO:
    def test_save_load_latent_round_trip(self, tmp_path):
        from comfyui_distributed_tpu.ops.base import get_op
        octx = OpContext()
        octx.output_dir = str(tmp_path)
        octx.input_dir = str(tmp_path)
        rng = np.random.default_rng(3)
        lat = {"samples": rng.standard_normal((2, 8, 8, 4))
               .astype(np.float32)}
        get_op("SaveLatent").execute(octx, lat, "latents/rt")
        import os
        p = os.path.join(str(tmp_path), "latents", "rt_00000.latent")
        assert os.path.exists(p)
        # never-overwrite: a second save gets the next counter
        get_op("SaveLatent").execute(octx, lat, "latents/rt")
        assert os.path.exists(os.path.join(str(tmp_path), "latents",
                                           "rt_00001.latent"))
        # NCHW on disk (reference format)
        from safetensors import safe_open
        with safe_open(p, framework="numpy") as f:
            assert f.get_tensor("latent_tensor").shape == (2, 4, 8, 8)
        (loaded,) = get_op("LoadLatent").execute(
            octx, "latents/rt_00000.latent")
        np.testing.assert_allclose(loaded["samples"], lat["samples"],
                                   rtol=1e-6)
        # the reference's pre-versioning files (no marker) load with
        # the 1/0.18215 legacy multiplier
        from comfyui_distributed_tpu.models.checkpoints import \
            save_state_dict
        legacy = os.path.join(str(tmp_path), "latents", "old.latent")
        save_state_dict(
            {"latent_tensor":
             np.ascontiguousarray(lat["samples"].transpose(0, 3, 1, 2))},
            legacy)
        (old,) = get_op("LoadLatent").execute(octx, "latents/old.latent")
        np.testing.assert_allclose(old["samples"],
                                   lat["samples"] / 0.18215, rtol=1e-5)

    def test_animated_savers(self, tmp_path):
        from PIL import Image

        from comfyui_distributed_tpu.ops.base import get_op
        octx = OpContext()
        octx.output_dir = str(tmp_path)
        frames = np.stack([np.full((16, 16, 3), v, np.float32)
                           for v in (0.1, 0.5, 0.9)])
        get_op("SaveAnimatedWEBP").execute(octx, frames, "anim/w", 8.0,
                                           True, 80, "slowest")
        get_op("SaveAnimatedPNG").execute(octx, frames, "anim/p", 8.0, 4)
        import os
        wp = os.path.join(str(tmp_path), "anim", "w_00000.webp")
        pp = os.path.join(str(tmp_path), "anim", "p_00000.png")
        assert os.path.exists(wp) and os.path.exists(pp)
        im = Image.open(wp)
        assert getattr(im, "n_frames", 1) == 3
        im2 = Image.open(pp)
        assert getattr(im2, "n_frames", 1) == 3


class TestPngWorkflowMetadata:
    """VERDICT r4 #5: saved PNGs embed the executing prompt and the
    client's extra_pnginfo (reference ships extra_pnginfo.workflow with
    every dispatch, gpupanel.js:1344-1358) and round-trip into the same
    graph."""

    def test_save_image_embeds_and_round_trips(self, ctx, tmp_path):
        import os

        from PIL import Image
        g = parse_workflow("/root/repo/workflows/distributed-txt2img.json")
        g.nodes["5"].inputs.update(width=64, height=64, batch_size=1)
        g.nodes["3"].inputs.update(steps=1)
        g.nodes["9"].class_type = "SaveImage"
        g.nodes["9"].inputs["filename_prefix"] = "meta_rt"
        ui_doc = json.load(
            open("/root/repo/workflows/distributed-txt2img.json"))
        ctx.output_dir = str(tmp_path / "out")
        res = WorkflowExecutor(ctx).execute(
            g, extra_pnginfo={"workflow": ui_doc})
        assert res.images
        outs = sorted(os.listdir(ctx.output_dir))
        assert outs, "SaveImage wrote nothing"
        im = Image.open(os.path.join(ctx.output_dir, outs[0]))
        assert "prompt" in im.info and "workflow" in im.info
        # the prompt chunk reloads into the SAME executable graph
        g2 = parse_workflow(json.loads(im.info["prompt"]))
        assert g2.to_api_format() == g.to_api_format()
        # the workflow chunk reloads into the same node set
        g3 = parse_workflow(json.loads(im.info["workflow"]))
        assert set(g3.nodes) == set(g.nodes)

    def test_no_metadata_when_none_given(self, tmp_path):
        """A bare op-level SaveImage (no executor run) writes clean PNGs."""
        import os

        from PIL import Image

        from comfyui_distributed_tpu.ops.base import OpContext, get_op
        octx = OpContext(output_dir=str(tmp_path / "out"))
        img = np.zeros((1, 8, 8, 3), np.float32)
        get_op("SaveImage").execute(octx, img, "plain")
        outs = sorted(os.listdir(octx.output_dir))
        im = Image.open(os.path.join(octx.output_dir, outs[0]))
        assert "prompt" not in im.info and "workflow" not in im.info


class TestIp2pFixture:
    """distributed-ip2p.json: the InstructPix2Pix edit sweep over the
    split-component loaders (UNETLoader + CLIPLoader + VAELoader), fanned
    out by DistributedSeed on the SPMD mesh."""

    def test_ip2p_fixture_fans_out(self, tmp_path, monkeypatch):
        import os

        from PIL import Image
        monkeypatch.delenv(registry.FAMILY_ENV, raising=False)
        registry.clear_pipeline_cache()
        rt = mesh_mod.MeshRuntime(mesh=mesh_mod.build_mesh(
            {"data": 2, "tensor": 1, "seq": 1},
            devices=jax.devices()[:2]))
        os.makedirs(tmp_path / "input", exist_ok=True)
        Image.fromarray((np.random.default_rng(1).random((32, 32, 3))
                         * 255).astype("uint8")).save(
            tmp_path / "input" / "input.png")
        ctx = OpContext(runtime=rt, input_dir=str(tmp_path / "input"),
                        output_dir=str(tmp_path / "out"))
        g = parse_workflow("/root/repo/workflows/distributed-ip2p.json")
        # tiny geometry for CPU: 8-channel tiny ip2p UNet via name
        # detection, tiny CLIP via the type map, tiny VAE via name
        g.nodes["2"].inputs["unet_name"] = "tiny-ip2p-unet.sft"
        g.nodes["3"].inputs.update(clip_name="tiny-clip.sft",
                                   type="tiny")
        g.nodes["4"].inputs["vae_name"] = "tiny-vae.sft"
        g.nodes["9"].inputs.update(steps=2)
        res = WorkflowExecutor(ctx).execute(g)
        assert len(res.images) == 2          # fan-out x2
        imgs = np.stack(res.images)
        assert np.isfinite(imgs).all()
        assert not np.allclose(imgs[0], imgs[1])   # distinct seeds
        registry.clear_pipeline_cache()


class TestSdxlRefinerFixture:
    """distributed-sdxl-refiner.json: the canonical two-stage SDXL flow
    — base denoises [0, end) with leftover noise, the refiner finishes —
    fanned out by DistributedSeed through BOTH stages."""

    def test_two_stage_handoff_fans_out(self, tmp_path, monkeypatch):
        from comfyui_distributed_tpu.workflow import (WorkflowExecutor,
                                                      parse_workflow)
        monkeypatch.setenv("DTPU_DEFAULT_FAMILY", "tiny_sdxl")
        registry.clear_pipeline_cache()
        rt = mesh_mod.MeshRuntime(mesh=mesh_mod.build_mesh(
            {"data": 2, "tensor": 1, "seq": 1},
            devices=jax.devices()[:2]))
        ctx = OpContext(runtime=rt, output_dir=str(tmp_path / "out"))
        g = parse_workflow(
            "/root/repo/workflows/distributed-sdxl-refiner.json")
        g.nodes["3"].inputs.update(width=64, height=64, batch_size=1)
        g.nodes["8"].inputs.update(steps=4, end_at_step=3)
        g.nodes["9"].inputs.update(steps=4, start_at_step=3)
        res = WorkflowExecutor(ctx).execute(g)
        assert len(res.images) == 2          # fan-out through BOTH stages
        imgs = np.stack(res.images)
        assert np.isfinite(imgs).all()
        assert not np.allclose(imgs[0], imgs[1])   # distinct seeds
        # the refiner stage actually changes the latent: base-only
        # (full denoise, no second stage) differs from the handoff
        g2 = parse_workflow(
            "/root/repo/workflows/distributed-sdxl-refiner.json")
        g2.nodes["3"].inputs.update(width=64, height=64, batch_size=1)
        g2.nodes["8"].inputs.update(steps=4, end_at_step=10000)
        g2.nodes["8"].inputs["return_with_leftover_noise"] = "disable"
        g2.nodes["10"].inputs["samples"] = ["8", 0]
        del g2.nodes["9"]          # orphaned refiner stage: don't pay for it
        res2 = WorkflowExecutor(
            OpContext(runtime=rt, output_dir=str(tmp_path / "o2"))
        ).execute(g2)
        assert not np.allclose(np.stack(res2.images), imgs)
        registry.clear_pipeline_cache()
