"""UltimateSDUpscaleDistributed: scatter/gather tiled SD refinement.

Reference: ``distributed_upscale.py:38-704``.  Same node schema (widget order
``[seed, control, steps, cfg, sampler_name, scheduler, denoise, tile_width,
tile_height, padding, mask_blur, force_uniform_tiles]``) and the same
capability set, executed three ways:

- **SPMD (mesh) mode** — the TPU-native path: the tile batch is padded to a
  multiple of the mesh's data-axis size and sharded across it; every device
  refines its tile shard *as one batched VAE+sampler call* (large MXU
  matmuls instead of the reference's per-tile Python loop), then tiles are
  gathered and feather-blended in deterministic index order.  Tile
  assignment needs no communication — the same property the reference
  exploits when master and workers recompute the partition independently
  (``distributed_upscale.py:143-147``).
- **Worker (HTTP) mode** — refines its contiguous range
  (``partition_tiles`` parity) and POSTs tiles to the master with retry
  and exponential backoff (``send_tile_to_master :606-665``).
- **Master (HTTP) mode** — refines its range, drains the tile queue with
  timeouts, blends whatever arrived (partial-results-on-timeout semantics,
  ``distributed_upscale.py:448-452``).

Per-tile seed is ``seed + tile_idx`` (``:380``), so results are independent
of which participant processed a tile — the distributed and single-device
paths are bit-identical oracles of each other.
"""

from __future__ import annotations

import asyncio
import json
from typing import Any, Dict, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from comfyui_distributed_tpu.ops import tiling
from comfyui_distributed_tpu.ops.base import (
    CONTROL,
    Conditioning,
    Op,
    OpContext,
    as_device_array,
    as_image_array,
    fetch_image_array,
    register_op,
)
from comfyui_distributed_tpu.parallel import collectives as coll
from comfyui_distributed_tpu.utils import constants as C
from comfyui_distributed_tpu.utils.image import encode_png, resize_image
from comfyui_distributed_tpu.utils.logging import Timer, debug_log, log
from comfyui_distributed_tpu.utils.net import post_form_with_retry, run_async_in_loop


def _tile_cache_eligible(pipe, positive: Conditioning,
                         negative: Conditioning) -> bool:
    """Changed-tile skipping is armed only for the plain refine case:
    canvas-global single-entry conditioning and an unpatched model.
    Regional masks resolve per tile POSITION (content identity is not
    enough), and model patches change the refine function in ways the
    key does not capture — those runs skip the tier, never mis-hit."""
    for c in (positive, negative):
        if getattr(c, "siblings", ()) \
                or getattr(c, "area_mask", None) is not None \
                or getattr(c, "timestep_range", None) is not None \
                or getattr(c, "control", None) is not None \
                or getattr(c, "concat_latent", None) is not None \
                or getattr(c, "unclip", None) is not None \
                or getattr(c, "gligen", None) is not None:
            return False
    if getattr(pipe, "perp_neg_cond", None) is not None:
        return False
    for attr in ("sag_params", "hypernets", "deep_shrink_spec",
                 "cfg_rescale"):
        if getattr(pipe, attr, None):
            return False
    return True


@register_op
class UltimateSDUpscaleDistributed(Op):
    TYPE = "UltimateSDUpscaleDistributed"
    WIDGETS = ["seed", CONTROL, "steps", "cfg", "sampler_name", "scheduler",
               "denoise", "tile_width", "tile_height", "padding", "mask_blur",
               "force_uniform_tiles"]
    DEFAULTS = {"steps": 20, "cfg": 8.0, "denoise": 0.5, "tile_width": 512,
                "tile_height": 512, "padding": 32, "mask_blur": 8,
                "force_uniform_tiles": True}
    # tile_indices defaults empty, in which case workers recompute their
    # partition from (enabled_worker_ids, worker_id) — assignment needs
    # no communication (reference keeps the input "Unused - kept for
    # compatibility", distributed_upscale.py:77).  The cluster control
    # plane (runtime/cluster.py) ACTIVATES it: a redispatched recovery
    # graph names the exact lost units, overriding the partition math.
    # dispatch_attempt distinguishes reissues in the idempotency key.
    HIDDEN = ["multi_job_id", "is_worker", "master_url",
              "enabled_worker_ids", "worker_id", "tile_indices",
              "dispatch_attempt"]

    def execute(self, ctx: OpContext, upscaled_image, model,
                positive: Conditioning, negative: Conditioning, vae,
                seed, steps, cfg, sampler_name, scheduler, denoise,
                tile_width, tile_height, padding, mask_blur,
                force_uniform_tiles=True, multi_job_id="", is_worker=None,
                master_url="", enabled_worker_ids="[]", worker_id="",
                tile_indices="", dispatch_attempt=0):
        ctx.check_interrupt()
        image = as_image_array(upscaled_image)
        tile_w = tiling.round_to_multiple(int(tile_width))
        tile_h = tiling.round_to_multiple(int(tile_height))
        seed = int(seed)
        params = dict(seed=seed, steps=int(steps), cfg=float(cfg),
                      sampler_name=str(sampler_name),
                      scheduler=str(scheduler), denoise=float(denoise),
                      tile_w=tile_w, tile_h=tile_h, padding=int(padding),
                      mask_blur=int(mask_blur))
        is_worker = ctx.is_worker if is_worker is None else is_worker

        if multi_job_id and is_worker:
            return self._run_worker(ctx, image, model, positive, negative,
                                    params, multi_job_id,
                                    master_url or ctx.master_url,
                                    worker_id or ctx.worker_id,
                                    enabled_worker_ids,
                                    tile_indices=tile_indices,
                                    dispatch_attempt=int(dispatch_attempt
                                                         or 0))
        if multi_job_id:
            return self._run_master_http(ctx, image, model, positive,
                                         negative, params, multi_job_id,
                                         enabled_worker_ids)
        return self._run_spmd(ctx, image, model, positive, negative, params)

    # --- shared refinement core --------------------------------------------

    def _canvas_area_mask(self, entry, img_w: int, img_h: int):
        """An entry's area spec -> a full-canvas image-resolution weight
        mask [1, H, W, 1], or None.  Rect specs resolve against the
        CURRENT canvas (the upscaled image) — "px" via ComfyUI's //8
        latent-unit convention on this canvas's latent, "pct" as
        fractions; array masks resize like the sample-time path."""
        from comfyui_distributed_tpu.ops.basic import _materialize_area_mask
        if getattr(entry, "area_mask", None) is None:
            return None
        cm = _materialize_area_mask(entry, max(img_h // 8, 1),
                                    max(img_w // 8, 1), 1)
        cm = np.asarray(cm, np.float32)
        if cm.shape[0] != 1:
            log("tiled upscale: regional mask has a batch dimension; the "
                "tile refine uses row 0 for every tile")
            cm = cm[:1]
        return np.clip(resize_image(cm, img_w, img_h, "bilinear"), 0.0, 1.0)

    def _regional_entries(self, pipe, src_entries, n: int,
                          positions: Sequence[Tuple[int, int]],
                          p: Dict[str, Any], img_size: Tuple[int, int],
                          lat_hw: Tuple[int, int], t_align: int,
                          positive: Conditioning, tiles_hw: Tuple[int, int],
                          mesh=None):
        """[Conditioning, ...] (one CFG side) -> registry.sample entry
        list with each entry's canvas mask CROPPED through the tile
        windows: materialize at canvas resolution, extract the same
        padded windows the pixels went through (tiling.extract_tiles, so
        edge clamping and resize agree exactly), then downsample to the
        tile latent (VERDICT r4 #4; reference passes canvas-global conds
        into every tile, distributed_upscale.py:516-541 — cropping is
        strictly more correct).  Returns (entries, y_list)."""
        from comfyui_distributed_tpu.ops.basic import (
            _image_mask_to_latent, _sdxl_vector_cond, adm_cond_source,
            align_cond_tokens, entry_sigma_range)
        img_w, img_h = img_size
        lh, lw = lat_hw
        th, tw = tiles_hw
        adm = pipe.family.unet.adm_in_channels is not None
        entries, ys = [], []
        for e in src_entries:
            ce = jnp.repeat(align_cond_tokens(e.context, t_align), n,
                            axis=0)
            am = None
            cm = self._canvas_area_mask(e, img_w, img_h)
            if cm is not None:
                wins = tiling.extract_tiles(cm, positions, tw, th,
                                            p["padding"],
                                            resize_method="bilinear")
                am = jnp.asarray(_image_mask_to_latent(
                    wins[..., 0], lh, lw, n))
            srange = entry_sigma_range(pipe.schedule, e)
            if mesh is not None:
                # shard_batch reshards device arrays in place — no host
                # round trip on the way to the mesh
                ce = coll.shard_batch(ce, mesh)
                if am is not None and am.shape[0] == n:
                    am = coll.shard_batch(am, mesh)
            entries.append((ce, am,
                            float(getattr(e, "area_strength", 1.0)),
                            srange))
            if adm:
                ye = _sdxl_vector_cond(
                    pipe, adm_cond_source(pipe.family, e, positive),
                    n, th, tw)
                if mesh is not None:
                    ye = coll.shard_batch(ye, mesh)
                ys.append(ye)
        return entries, ys

    def _refine_batch(self, ctx: OpContext, pipe, tiles: np.ndarray,
                      tile_indices: Sequence[int], positive: Conditioning,
                      negative: Conditioning, p: Dict[str, Any],
                      positions: Sequence[Tuple[int, int]] = None,
                      img_size: Tuple[int, int] = None,
                      shard: bool = False,
                      return_device: bool = False) -> np.ndarray:
        """VAE-encode -> sample(denoise) -> decode a [N, th, tw, C] tile
        batch.  Per-tile seed = seed + tile_idx with a fixed fold index so
        results are layout-independent.  Regional conditionings (siblings
        / area masks) refine with their masks cropped per tile window
        (``_regional_entries``).

        ``return_device``: hand back the decoded batch still ON DEVICE —
        the worker send path fetches tile-by-tile so tile k+1's d2h can
        overlap tile k's HTTP upload (double-buffering) instead of one
        big synchronous fetch before the first byte moves."""
        from comfyui_distributed_tpu.ops.basic import _sdxl_vector_cond
        n = tiles.shape[0]
        seeds = np.asarray([p["seed"] + int(t) for t in tile_indices],
                           np.uint64)
        idx = np.zeros((n,), np.uint32)  # each tile is its own batch-of-1
        regional = any(getattr(c, "siblings", ())
                       or getattr(c, "area_mask", None) is not None
                       or getattr(c, "timestep_range", None) is not None
                       for c in (positive, negative))
        if regional and (getattr(pipe, "perp_neg_cond", None) is not None
                         or positions is None or img_size is None):
            # 3-row guidance can't compose with multi-entry conds in one
            # stacked call (registry contract), and a caller that didn't
            # thread tile positions can't crop masks — degrade LOUDLY to
            # the primary prompt, never silently mis-apply canvas-global
            # masks to tile-local coordinates
            log("tiled upscale: regional conditioning cannot be mapped "
                "into this tile refine "
                + ("(PerpNeg-patched model)" if positions is not None
                   else "(no tile positions)")
                + "; using the primary prompt only")
            regional = False
        mesh = ctx.runtime.mesh if (shard and ctx.runtime is not None) \
            else None
        if regional:
            from comfyui_distributed_tpu.ops.basic import cond_token_align
            pos_entries = [positive] + list(getattr(positive, "siblings",
                                                    ()) or ())
            neg_entries = [negative] + list(getattr(negative, "siblings",
                                                    ()) or ())
            t_align = cond_token_align(pos_entries + neg_entries)
            ds = pipe.family.vae.downscale
            lat_hw = (tiles.shape[1] // ds, tiles.shape[2] // ds)
            tiles_hw = (tiles.shape[1], tiles.shape[2])
            ctx_arr, y_conds = self._regional_entries(
                pipe, pos_entries, n, positions, p, img_size, lat_hw,
                t_align, positive, tiles_hw, mesh)
            unc_arr, y_unconds = self._regional_entries(
                pipe, neg_entries, n, positions, p, img_size, lat_hw,
                t_align, positive, tiles_hw, mesh)
            y = (y_conds + y_unconds) if y_conds or y_unconds else None
            tiles_dev = as_device_array(tiles)
            if mesh is not None:
                tiles_dev = coll.shard_batch(tiles_dev, mesh)
            lat = pipe.vae_encode(tiles_dev)
            # encode -> sample -> decode never leaves the device; the
            # tile-latent buffer is fresh (vae_encode output, consumed
            # only here) so the denoise loop donates it.  ONE counted
            # fetch hands the refined tiles to the host-side blend.
            out_lat = pipe.sample(
                lat, ctx_arr, unc_arr, seeds,
                steps=p["steps"], cfg=p["cfg"],
                sampler_name=p["sampler_name"], scheduler=p["scheduler"],
                denoise=p["denoise"], add_noise=True, sample_idx=idx, y=y,
                donate_latents=True)
            decoded = jnp.clip(pipe.vae_decode(out_lat), 0.0, 1.0)
            return decoded if return_device else as_image_array(decoded)
        ctx_arr = jnp.repeat(positive.context, n, axis=0)
        unc_arr = jnp.repeat(negative.context, n, axis=0)
        y = None
        if pipe.family.unet.adm_in_channels is not None:
            y = _sdxl_vector_cond(pipe, positive, n,
                                  tiles.shape[1], tiles.shape[2])
        # a PerpNeg-patched pipeline's empty conditioning steers the tile
        # refine too (the patch rides derive_pipeline; dropping it here
        # would silently degrade to plain CFG)
        mid_arr = None
        guidance, cfg2 = "dual", 1.0
        pn = getattr(pipe, "perp_neg_cond", None)
        if pn is not None:
            c = jnp.asarray(pn.context)
            tm = int(positive.context.shape[1])
            if int(c.shape[1]) != tm:   # align to the prompt's tokens
                t = int(c.shape[1])
                if tm % t == 0:
                    c = jnp.tile(c, (1, tm // t, 1))
                elif t > tm:
                    c = c[:, :tm]
                else:
                    c = jnp.pad(c, ((0, 0), (0, tm - t), (0, 0)))
            mid_arr = jnp.repeat(c, n, axis=0)
            guidance = "perp_neg"
            cfg2 = float(getattr(pipe, "perp_neg_scale", 1.0))
        tiles_dev = as_device_array(tiles)
        if shard and ctx.runtime is not None:
            mesh = ctx.runtime.mesh
            tiles_dev = coll.shard_batch(tiles_dev, mesh)
            ctx_arr = coll.shard_batch(ctx_arr, mesh)
            unc_arr = coll.shard_batch(unc_arr, mesh)
            if y is not None:
                y = coll.shard_batch(y, mesh)
            if mid_arr is not None:
                mid_arr = coll.shard_batch(mid_arr, mesh)
        lat = pipe.vae_encode(tiles_dev)
        out_lat = pipe.sample(
            lat, ctx_arr, unc_arr, seeds,
            steps=p["steps"], cfg=p["cfg"], sampler_name=p["sampler_name"],
            scheduler=p["scheduler"], denoise=p["denoise"],
            add_noise=True, sample_idx=idx, y=y,
            middle_context=mid_arr, cfg2=cfg2, guidance=guidance,
            donate_latents=True)
        # clamp at the decode boundary (ComfyUI VAEDecode parity): the
        # worker->master PNG wire clips to [0,1], so unclamped local tiles
        # would blend differently from the same tile shipped over HTTP.
        # Clip ON device, then ONE counted fetch for the host-side blend
        # (or none — the worker send path streams tile-by-tile).
        decoded = jnp.clip(pipe.vae_decode(out_lat), 0.0, 1.0)
        return decoded if return_device else as_image_array(decoded)

    def _window_to_extracted(self, tile: np.ndarray, pos: Tuple[int, int],
                             p: Dict[str, Any], img_size: Tuple[int, int]
                             ) -> Tuple[np.ndarray, Tuple[int, int, int, int]]:
        """Padded-window tile (possibly downsampled to tile size) -> the
        clamped extraction region at natural size.

        This is THE canonical window->blend-form transform (inverse of
        ``_worker_tile_to_window``): both the local blend and the HTTP wire
        must use it so worker tiles land bit-identically to local ones
        (reference resizes to extracted size, distributed_upscale.py:
        480-514, 606-635)."""
        w, h = img_size
        x, y = pos
        tw, th, pad = p["tile_w"], p["tile_h"], p["padding"]
        x1, y1, x2, y2 = tiling.extraction_region(x, y, tw, th, pad, w, h)
        if pad > 0:
            full_w, full_h = tw + 2 * pad, th + 2 * pad
            if (tile.shape[1], tile.shape[0]) != (full_w, full_h):
                tile = resize_image(tile[None], full_w, full_h)[0]
            ox, oy = x1 - (x - pad), y1 - (y - pad)
            tile = tile[oy:oy + (y2 - y1), ox:ox + (x2 - x1), :]
        return tile, (x1, y1, x2, y2)

    def _blend_all(self, image: np.ndarray,
                   refined: Dict[int, np.ndarray],
                   all_tiles: List[Tuple[int, int]],
                   p: Dict[str, Any]) -> np.ndarray:
        """Deterministic index-order feathered blend of refined tiles into a
        copy of the base image (timed-out/missing tiles keep base pixels —
        the reference's partial-result semantics)."""
        h, w = image.shape[1:3]
        tw, th = p["tile_w"], p["tile_h"]
        canvas = image[0].copy()
        for tile_idx in sorted(refined):
            x, y = all_tiles[tile_idx]
            tile, (x1, y1, x2, y2) = self._window_to_extracted(
                refined[tile_idx], all_tiles[tile_idx], p, (w, h))
            canvas = tiling.blend_tile(
                canvas, tile, x1, y1, (x, y), tw, th,
                (x2 - x1, y2 - y1), p["mask_blur"])
        return np.clip(canvas, 0.0, 1.0)[None]

    # --- changed-tile skipping (ISSUE 13 tier c) ----------------------------

    def _tile_cache_probe(self, pipe, positive, negative, p,
                          tiles: np.ndarray, indices: Sequence[int],
                          refined: Dict[int, np.ndarray]):
        """Per-tile content-addressed lookup (runtime/reuse.py): key =
        model identity + conditioning fingerprint + refine params +
        tile index (its seed is ``seed + idx``) + the extracted
        window's bytes.  Hits land in ``refined`` (the stored refined
        window, bit-identical to what the producing run blended) and
        bump the ``tiles_skipped`` counter + span attr; returns
        ``{tile_idx: key}`` for storing misses, or None when the tier
        is off or this refine is ineligible."""
        import jax

        from comfyui_distributed_tpu.runtime import reuse as reuse_mod
        from comfyui_distributed_tpu.utils import trace as trace_mod
        if not reuse_mod.reuse_enabled() \
                or not _tile_cache_eligible(pipe, positive, negative):
            return None
        if jax.process_count() > 1:
            # multihost SPMD: every process must execute the SAME
            # program, but the caches are per-process — divergent dirty
            # sets would enter the sharded refine with different batch
            # shapes and hang the collectives
            return None
        plane = reuse_mod.get_reuse()
        salt = plane.model_salt(pipe)
        if salt is None:
            return None
        key_list = reuse_mod.tile_keys(
            salt,
            reuse_mod.conditioning_fingerprint(positive, negative),
            p, tiles, [int(i) for i in indices])
        keys = dict(zip((int(i) for i in indices), key_list))
        hits = 0
        for i in keys:
            win = plane.tiles.get(keys[i])
            if win is not None:
                refined[i] = win
                hits += 1
        if hits:
            trace_mod.GLOBAL_COUNTERS.bump("tiles_skipped", hits)
            sp = trace_mod.current_span()
            if sp is not None:
                sp.attrs["tiles_skipped"] = \
                    int(sp.attrs.get("tiles_skipped", 0)) + hits
        return keys

    @staticmethod
    def _tile_cache_store(keys, refined: Dict[int, np.ndarray],
                          only=None) -> None:
        if keys is None:
            return
        from comfyui_distributed_tpu.runtime import reuse as reuse_mod
        plane = reuse_mod.get_reuse()
        for i, win in refined.items():
            if only is not None and i not in only:
                continue
            key = keys.get(int(i))
            if key is not None:
                plane.tiles.put(key, win, reuse_mod.tile_nbytes(win))

    # --- SPMD path ----------------------------------------------------------

    def _run_spmd(self, ctx: OpContext, image: np.ndarray, pipe,
                  positive, negative, p) -> Tuple:
        h, w = image.shape[1:3]
        all_tiles = tiling.calculate_tiles(w, h, p["tile_w"], p["tile_h"])
        total = len(all_tiles)
        d = max(ctx.fanout, 1)
        with Timer("tile_extract"):
            tiles = tiling.extract_tiles(image, all_tiles, p["tile_w"],
                                         p["tile_h"], p["padding"])
        # changed-tile skipping: unchanged windows replay their stored
        # refined tiles; only the dirty set reaches the mesh
        refined: Dict[int, np.ndarray] = {}
        keys = self._tile_cache_probe(pipe, positive, negative, p,
                                      tiles, range(total), refined)
        dirty = [i for i in range(total) if i not in refined]
        if refined:
            log(f"tiled upscale: {len(refined)}/{total} tiles unchanged "
                f"(cache hits); refining {len(dirty)}")
        if dirty:
            padded_n = coll.pad_to_multiple(len(dirty), d) if d > 1 \
                else len(dirty)
            indices = list(dirty) + [dirty[0]] * (padded_n - len(dirty))
            positions = [all_tiles[i] for i in indices]
            log(f"tiled upscale: {len(dirty)} tiles ({w}x{h}, "
                f"{p['tile_w']}x{p['tile_h']}+{p['padding']}) over {d} "
                f"mesh slot(s)"
                + (f", padded to {padded_n}" if padded_n != len(dirty)
                   else ""))
            rows = tiles[indices]
            with Timer("tile_refine"):
                out_rows = self._refine_batch(ctx, pipe, rows, indices,
                                              positive, negative, p,
                                              positions=positions,
                                              img_size=(w, h),
                                              shard=(d > 1))
            fresh = {i: out_rows[k] for k, i in enumerate(indices)
                     if k < len(dirty)}
            self._tile_cache_store(keys, fresh)
            refined.update(fresh)
        with Timer("tile_blend"):
            out = self._blend_all(image, refined, all_tiles, p)
        return (out,)

    # --- worker HTTP path ---------------------------------------------------

    def _run_worker(self, ctx: OpContext, image, pipe, positive, negative,
                    p, multi_job_id, master_url, worker_id,
                    enabled_worker_ids, tile_indices="",
                    dispatch_attempt=0) -> Tuple:
        h, w = image.shape[1:3]
        all_tiles = tiling.calculate_tiles(w, h, p["tile_w"], p["tile_h"])
        explicit: List[int] = []
        if tile_indices:
            # unit-addressed dispatch (cluster recovery/hedge path): the
            # master named the exact units; skip the partition math so a
            # worker outside the original enabled list can pick them up
            try:
                explicit = [int(i) for i in json.loads(tile_indices)]
            except (ValueError, TypeError):
                log(f"tiled upscale worker: bad tile_indices "
                    f"{tile_indices!r}; falling back to partition")
        if explicit:
            mine = [i for i in explicit if 0 <= i < len(all_tiles)]
            debug_log(f"worker {worker_id}: explicit units {mine} "
                      f"(attempt {dispatch_attempt})")
        else:
            workers = [str(x) for x in json.loads(
                enabled_worker_ids or "[]")]
            try:
                w_index = workers.index(str(worker_id))
            except ValueError:
                log(f"tiled upscale worker: {worker_id!r} not in enabled "
                    f"list {workers}; nothing to do")
                return (image,)
            parts = tiling.partition_tiles(len(all_tiles), len(workers))
            mine = parts[1 + w_index]
        if not mine:
            return (image,)
        debug_log(f"worker {worker_id}: tiles {mine[0]}..{mine[-1]}")
        tiles = tiling.extract_tiles(image, [all_tiles[i] for i in mine],
                                     p["tile_w"], p["tile_h"], p["padding"])
        # keep the refined batch ON DEVICE: the send loop fetches one
        # tile at a time, overlapping tile k+1's d2h+encode with tile
        # k's HTTP upload (double-buffering)
        refined = self._refine_batch(ctx, pipe, tiles, mine,
                                     positive, negative, p,
                                     positions=[all_tiles[i] for i in mine],
                                     img_size=(w, h), return_device=True)
        self._send_tiles(ctx, refined, mine, all_tiles, p, multi_job_id,
                         master_url, worker_id, (w, h),
                         attempt=dispatch_attempt)
        return (image,)

    def _send_tiles(self, ctx: OpContext, refined, indices: Sequence[int],
                    all_tiles, p, multi_job_id, master_url, worker_id,
                    img_size, attempt=0) -> None:
        """Double-buffered tile upload: while tile k's POST is in flight,
        tile k+1's d2h fetch + window transform + encode run on an
        executor thread, so the NIC and the device/encoder are busy at
        the same time.  Payload format negotiated per master (raw tensor
        when advertised, PNG fallback)."""
        from comfyui_distributed_tpu.utils import trace as trace_mod
        from comfyui_distributed_tpu.utils.image import encode_tensor
        from comfyui_distributed_tpu.utils.net import (
            negotiate_wire_format, wire_codec)
        w, h = img_size
        # re-enter the executing thread's span context inside the
        # server-loop coroutine (same cross-thread handoff as the image
        # send path) so d2h/encode/upload stage spans join the job trace
        captured_span = trace_mod.capture_span_context()

        async def send_all():
            with trace_mod.use_span(captured_span):
                await send_body()

        async def send_body():
            # fault injection (bench/tests only): simulate a worker that
            # stalls (straggler) or dies after k tiles (partial failure)
            inject = ctx.fault_inject or {}
            stall_s = float(inject.get("stall_s", 0) or 0)
            drop_after = inject.get("drop_tiles_after")
            if stall_s > 0:
                log(f"FAULT INJECTION: worker {worker_id} stalling "
                    f"{stall_s}s before sending")
                await asyncio.sleep(stall_s)
            fmt = await negotiate_wire_format(master_url)
            codec = wire_codec(master_url)
            loop = asyncio.get_running_loop()
            trace_id = (captured_span.trace_id
                        if captured_span is not None else None)

            def prep(k):
                # run_in_executor does NOT propagate contextvars: re-enter
                # the job's span context on the pool thread so the
                # d2h/encode spans stay in the trace
                with trace_mod.use_span(captured_span):
                    return prep_body(k)

            def prep_body(k):
                tile_idx = indices[k]
                # d2h ONE tile (counted; refined may be a device batch)
                row = fetch_image_array(refined[k:k + 1])[0]
                # the wire carries the clamped extraction region at
                # natural size — the exact form the master's blend
                # consumes; sending the raw window would make the master
                # resize-distort it at image edges
                tile, (x1, y1, x2, y2) = self._window_to_extracted(
                    row, all_tiles[tile_idx], p, (w, h))
                with trace_mod.stage("encode"):
                    if fmt == C.TENSOR_WIRE_CONTENT_TYPE:
                        payload, ctype, ext = (encode_tensor(tile[None],
                                                             codec),
                                               fmt, "dtt")
                    else:
                        payload, ctype, ext = (encode_png(tile[None]),
                                               "image/png", "png")
                return payload, ctype, ext, (x1, y1, x2, y2)

            nxt = loop.run_in_executor(None, prep, 0)
            for k, tile_idx in enumerate(indices):
                if drop_after is not None and k >= int(drop_after):
                    log(f"FAULT INJECTION: worker {worker_id} dying "
                        f"after {k} of {len(indices)} tiles")
                    await nxt  # retire the prefetch before vanishing
                    return
                payload, ctype, ext, (x1, y1, x2, y2) = await nxt
                if k + 1 < len(indices):   # prefetch the next tile's
                    nxt = loop.run_in_executor(None, prep, k + 1)

                def make_form(k=k, tile_idx=tile_idx, x1=x1, y1=y1,
                              x2=x2, y2=y2, payload=payload, ctype=ctype,
                              ext=ext):
                    import aiohttp
                    form = aiohttp.FormData()
                    form.add_field("multi_job_id", multi_job_id)
                    form.add_field("worker_id", str(worker_id))
                    form.add_field("tile_idx", str(tile_idx))
                    form.add_field("x", str(x1))
                    form.add_field("y", str(y1))
                    form.add_field("extracted_width", str(x2 - x1))
                    form.add_field("extracted_height", str(y2 - y1))
                    form.add_field("padding", str(p["padding"]))
                    # stable across post_form_with_retry's resends of
                    # THIS send, distinct across dispatch attempts —
                    # the JobStore dedupes replays on it
                    form.add_field("idem_key",
                                   f"{worker_id}:{tile_idx}:{attempt}")
                    form.add_field("is_last", "true" if k == len(indices) - 1
                                   else "false")
                    if k == len(indices) - 1 and trace_id:
                        # final tile carries this process's spans for the
                        # job — the master merges them into its tree
                        form.add_field("spans", json.dumps(
                            trace_mod.GLOBAL_TRACES.export(trace_id)))
                    form.add_field("tile", payload,
                                   filename=f"tile_{tile_idx}.{ext}",
                                   content_type=ctype)
                    return form

                # exponential backoff incl. 404 (queue-not-ready race) —
                # reference distributed_upscale.py:618-665
                with trace_mod.stage("upload"):
                    await post_form_with_retry(
                        f"{master_url}/distributed/tile_complete", make_form,
                        timeout=C.TILE_TRANSFER_TIMEOUT, what="tile_complete",
                        headers=trace_mod.traceparent_headers())

        if ctx.server_loop is not None:
            run_async_in_loop(send_all(), ctx.server_loop,
                              timeout=C.TILE_SEND_TIMEOUT * len(indices))
        else:
            asyncio.run(send_all())
        log(f"worker {worker_id}: sent {len(indices)} tiles for "
            f"{multi_job_id}")

    # --- master HTTP path ---------------------------------------------------

    def _run_master_http(self, ctx: OpContext, image, pipe, positive,
                         negative, p, multi_job_id,
                         enabled_worker_ids) -> Tuple:
        from comfyui_distributed_tpu.runtime import cluster as cluster_mod
        from comfyui_distributed_tpu.utils import trace as trace_mod
        h, w = image.shape[1:3]
        all_tiles = tiling.calculate_tiles(w, h, p["tile_w"], p["tile_h"])
        workers = [str(x) for x in json.loads(enabled_worker_ids or "[]")]
        if not workers:
            return self._run_spmd(ctx, image, pipe, positive, negative, p)
        parts = tiling.partition_tiles(len(all_tiles), len(workers))
        mine = parts[0]
        active_workers = sum(1 for part in parts[1:] if part)

        # changed-tile skipping (ISSUE 13 tier c): hash every extracted
        # window BEFORE the ledger plans the job — cached units check in
        # immediately (owner "cache", exactly-once like any other
        # completion), so the pending set the drain waits on is ONLY the
        # dirty tiles, and duplicate sends from workers that still
        # refined their full partition lose the first-wins race
        from comfyui_distributed_tpu.runtime import reuse as reuse_mod
        cached: Dict[int, np.ndarray] = {}
        tile_keys = None
        windows_all = None
        if reuse_mod.reuse_enabled() \
                and _tile_cache_eligible(pipe, positive, negative):
            with Timer("tile_extract"):
                windows_all = tiling.extract_tiles(
                    image, all_tiles, p["tile_w"], p["tile_h"],
                    p["padding"])
            tile_keys = self._tile_cache_probe(
                pipe, positive, negative, p, windows_all,
                range(len(all_tiles)), cached)
        if cached:
            log(f"tiled upscale master: {len(cached)}/{len(all_tiles)} "
                f"tiles unchanged (cache hits)")

        # work ledger (cluster control plane): record which participant
        # owns which tile indices BEFORE any work happens — completions
        # check in through it (exactly-once at the blend) and whatever is
        # still pending at the end is recoverable instead of dropped
        ledger = ctx.ledger
        if ledger is not None:
            owners: Dict[int, str] = {int(i): "master" for i in mine}
            for wi, part in enumerate(parts[1:]):
                for i in part:
                    owners[int(i)] = workers[wi]
            ledger.create_job(multi_job_id, owners, kind="tile")
            for i, win in cached.items():
                ledger.check_in(multi_job_id, i, "cache",
                                payload=([win], {"form": "window"}))

        def refine_units(units: Sequence[int]) -> Dict[int, np.ndarray]:
            """Master-local refine of arbitrary units (the recovery and
            hedge path).  Per-tile seed = seed + tile_idx, so the result
            is bit-identical to what the lost/straggling owner would
            have produced."""
            units = [int(u) for u in units]
            if windows_all is not None:
                # the cache probe already extracted every window —
                # reuse its rows instead of re-slicing the image
                t = windows_all[units]
            else:
                t = tiling.extract_tiles(
                    image, [all_tiles[i] for i in units],
                    p["tile_w"], p["tile_h"], p["padding"])
            out = self._refine_batch(
                ctx, pipe, t, units, positive, negative, p,
                positions=[all_tiles[i] for i in units], img_size=(w, h))
            out = {i: out[k] for k, i in enumerate(units)}
            self._tile_cache_store(tile_keys, out)
            return out

        # pre-create the tile queue BEFORE refining our own range: workers
        # may finish first, and put_tile requires an existing queue (the
        # reference pre-inits in IS_CHANGED for the same race,
        # distributed_upscale.py:85-105)
        if active_workers and ctx.job_store is not None \
                and ctx.server_loop is not None:
            run_async_in_loop(ctx.job_store.get_tile_queue(multi_job_id),
                              ctx.server_loop, timeout=C.QUEUE_INIT_TIMEOUT)

        try:
            refined: Dict[int, np.ndarray] = dict(cached)
            if ledger is None:
                # no ledger to shrink the pending set through: the
                # cached units simply leave the master's own range
                mine = [i for i in mine if int(i) not in cached]
            if ledger is not None:
                # crash recovery (durability plane): units completed
                # before the old master died blend straight from their
                # spilled payloads — never re-refined — and the master's
                # own range shrinks to what is actually still pending
                for u, (tensors, meta) in ledger.load_payloads(
                        multi_job_id).items():
                    i = int(u)
                    if meta.get("form") == "tile":
                        refined[i] = self._worker_tile_to_window(
                            {**meta, "tensor": tensors[0]},
                            all_tiles[i], p, (w, h))
                    else:
                        refined[i] = np.asarray(tensors[0])
                pending_mine = {int(x) for x in ledger.pending(
                    multi_job_id, owner="master")}
                mine = [i for i in mine if int(i) in pending_mine]
            if mine:
                out = refine_units(mine)
                for i, window in out.items():
                    if ledger is None \
                            or ledger.check_in(
                                multi_job_id, i, "master",
                                payload=([window], {"form": "window"})):
                        refined[i] = window

            if active_workers and ctx.job_store is not None:
                collected = self._collect_tiles(
                    ctx, multi_job_id, active_workers,
                    refine_window=refine_units)
                for tile_idx, item in collected.items():
                    if int(tile_idx) in cached:
                        # ledger-less dedupe: a worker's send for a tile
                        # the cache already settled must not displace
                        # the stored window (with a ledger the
                        # first-wins check-in already dropped it)
                        continue
                    if "window_tensor" in item:
                        # master-local recovery/hedge result: already at
                        # window size
                        refined[int(tile_idx)] = item["window_tensor"]
                    else:
                        # worker tiles arrive at extracted size; store at
                        # window size
                        refined[int(tile_idx)] = self._worker_tile_to_window(
                            item, all_tiles[int(tile_idx)], p, (w, h))
                        self._tile_cache_store(
                            tile_keys, {int(tile_idx):
                                        refined[int(tile_idx)]})

            # post-drain recovery: units still pending (collection
            # deadline fired, or an in-drain recovery failed) are
            # REFINED HERE by the master instead of silently keeping
            # base pixels — unless the policy opts back into the seed's
            # partial-result behavior
            if ledger is not None:
                pending = ledger.pending(multi_job_id)
                if pending:
                    policy = cluster_mod.fault_policy()
                    if policy == "fail":
                        raise cluster_mod.ClusterFaultError(
                            f"job {multi_job_id}: units {pending} "
                            f"unfinished at collection end "
                            f"({C.FAULT_POLICY_ENV}=fail)")
                    if policy == "reassign":
                        moved = ledger.reassign(multi_job_id, pending,
                                                "master")
                        if moved:
                            log(f"tiled upscale master: reassigning "
                                f"units {moved} to master "
                                f"(job {multi_job_id})")
                            with trace_mod.span("reassign",
                                                job=multi_job_id,
                                                units=len(moved),
                                                to="master"):
                                out = refine_units(moved)
                            for i, window in out.items():
                                if ledger.check_in(
                                        multi_job_id, i, "master",
                                        payload=([window],
                                                 {"form": "window"})):
                                    refined[i] = window
                    else:
                        log(f"tiled upscale master: units {pending} "
                            f"lost; blending partial "
                            f"({C.FAULT_POLICY_ENV}=partial)")
            return (self._blend_all(image, refined, all_tiles, p),)
        finally:
            if ledger is not None:
                summary = ledger.finish_job(multi_job_id)
                if summary and (summary["reassigned_units"]
                                or summary["hedged_units"]):
                    log(f"job {multi_job_id}: {summary['done_units']}/"
                        f"{summary['total_units']} units, "
                        f"{summary['reassigned_units']} reassigned, "
                        f"{summary['hedged_units']} hedged")

    def _worker_tile_to_window(self, item, pos, p, img_size) -> np.ndarray:
        """Re-inflate an extracted-size worker tile to the uniform padded
        window (edge-replicated) so _blend_all can treat all tiles alike."""
        w, h = img_size
        x, y = pos
        tw, th, pad = p["tile_w"], p["tile_h"], p["padding"]
        x1, y1, x2, y2 = tiling.extraction_region(x, y, tw, th, pad, w, h)
        tile = np.asarray(item["tensor"], np.float32)
        if tile.ndim == 4:
            tile = tile[0]
        want_w, want_h = x2 - x1, y2 - y1
        if (tile.shape[1], tile.shape[0]) != (want_w, want_h):
            tile = resize_image(tile[None], want_w, want_h)[0]
        ox, oy = x1 - (x - pad), y1 - (y - pad)
        full_h, full_w = th + 2 * pad, tw + 2 * pad
        return np.pad(tile, ((oy, full_h - oy - want_h),
                             (ox, full_w - ox - want_w), (0, 0)),
                      mode="edge")

    def _collect_tiles(self, ctx: OpContext, multi_job_id: str,
                       num_workers: int,
                       refine_window=None) -> Dict[int, Any]:
        """Drain the tile queue.  With the cluster control plane wired
        (``ctx.ledger``), the drain is ledger-driven: it exits when every
        unit has checked in, consults the worker registry each poll so a
        lease expiry triggers recovery IMMEDIATELY (redispatch to a
        healthy HTTP worker when the orchestrator registered one, else
        master-local refine via ``refine_window``), and hedges overdue
        stragglers once the job passes the progress gate — first
        completion wins through the ledger's exactly-once check-in.
        Without a ledger the drain is the pre-cluster done-count loop."""
        from comfyui_distributed_tpu.runtime import cluster as cluster_mod
        from comfyui_distributed_tpu.utils import trace as trace_mod
        ledger = ctx.ledger if (ctx.ledger is not None
                                and ctx.ledger.has_job(multi_job_id)) \
            else None
        registry = ctx.cluster
        policy = cluster_mod.fault_policy()
        hedge_on = cluster_mod.hedge_armed() and ledger is not None \
            and refine_window is not None
        # re-enter the exec thread's span context inside the server-loop
        # coroutine (contextvars don't follow run_coroutine_threadsafe)
        captured_span = trace_mod.capture_span_context()

        async def drain():
            q = await ctx.job_store.get_tile_queue(multi_job_id)
            collected: Dict[int, Any] = {}
            done = set()
            recovery: List[Any] = []
            handled_dead = set()
            # overall deadline enforced INSIDE the loop so hitting it still
            # returns (and blends) everything collected so far — an outer
            # cancellation would discard the partial results the timeout
            # semantics exist to save (reference distributed_upscale.py:
            # 448-452)
            loop = asyncio.get_running_loop()
            deadline = loop.time() + C.TILE_COLLECTION_TIMEOUT
            # redispatch extensions must stay below the outer
            # run_async_in_loop backstop: cascading deaths extending
            # past it would get the whole drain cancelled and the
            # partial results discarded
            hard_deadline = loop.time() + 2 * C.TILE_COLLECTION_TIMEOUT \
                + C.TILE_WAIT_TIMEOUT
            last_progress = loop.time()
            # short polls only when the control plane can actually act
            # between tiles; otherwise keep the seed's long waits
            poll_s = C.CLUSTER_POLL_S if (ledger is not None
                                          and (registry is not None
                                               or hedge_on)) \
                else C.TILE_WAIT_TIMEOUT

            async def recover(units, reason, lost_owner=None):
                """Master-local refine racing the original owner; the
                ledger's first-wins check-in settles it."""
                attrs = {"job": multi_job_id, "units": len(units),
                         "to": "master"}
                if lost_owner:
                    attrs["lost"] = str(lost_owner)
                try:
                    with trace_mod.use_span(captured_span), \
                            trace_mod.span(reason, **attrs):
                        out = await loop.run_in_executor(
                            None, refine_window, list(units))
                except Exception as e:  # noqa: BLE001 - post-drain
                    # fallback still covers these units
                    log(f"tiled upscale master: {reason} of {units} "
                        f"failed: {type(e).__name__}: {e}")
                    if reason == "hedge":
                        # a failed hedge must not pin the units: still
                        # hedge-marked they'd be skipped by the in-drain
                        # dead-owner scan
                        ledger.unmark_hedged(multi_job_id, list(units))
                    return
                for idx, window in out.items():
                    # off the loop: a WAL-backed check-in spills the
                    # payload + fsyncs the record
                    if await loop.run_in_executor(
                            None, lambda i=idx, w=window: ledger.check_in(
                                multi_job_id, i, "master",
                                payload=([w], {"form": "window"}))):
                        collected[int(idx)] = {"window_tensor": window}

            async def handle_lost(owner, units, what):
                """Move a lost participant's units: redispatch the exact
                list to a healthy worker when the orchestrator (or crash
                recovery) registered a callback, else race a
                master-local refine through first-wins check-in.
                Returns True when a redispatch went out (the deadline
                gets extended for the replacement)."""
                redone = False
                if ledger.has_redispatcher(multi_job_id):
                    with trace_mod.use_span(captured_span), \
                            trace_mod.span("reassign",
                                           job=multi_job_id,
                                           units=len(units),
                                           lost=str(owner),
                                           to="remote") as rsp:
                        redone = await ledger.redispatch(
                            multi_job_id, sorted(units), owner)
                        if rsp is not None and not redone:
                            rsp.attrs["to"] = "none"
                if not redone and refine_window is not None:
                    # off the loop: a WAL-backed reassign appends +
                    # fsyncs the ownership record
                    moved = await loop.run_in_executor(
                        None, lambda: ledger.reassign(
                            multi_job_id, sorted(units), "master"))
                    if moved:
                        recovery.append(loop.create_task(
                            recover(moved, what, owner)))
                return redone

            def finished() -> bool:
                if ledger is not None:
                    return not ledger.pending(multi_job_id)
                return len(done) >= num_workers

            # crash recovery: a recovered job's pending non-master units
            # were dispatched by the DEAD master — their owners are
            # alive but will never (re)send.  Treat them as lost NOW
            # (redispatch the exact unit lists, else master-local),
            # instead of waiting out the no-progress timeout.
            stale = ledger.take_recovered_lost(multi_job_id) \
                if ledger is not None and policy != "partial" else {}
            try:
                for owner, units in stale.items():
                    if policy == "fail":
                        raise cluster_mod.ClusterFaultError(
                            f"recovered job {multi_job_id} lost units "
                            f"{sorted(units)} with the old master "
                            f"({C.FAULT_POLICY_ENV}=fail)")
                    log(f"tiled upscale master: recovered job "
                        f"{multi_job_id}: re-issuing units "
                        f"{sorted(units)} stranded on {owner}")
                    if await handle_lost(owner, units, "reassign"):
                        deadline = min(max(
                            deadline, loop.time()
                            + C.TILE_COLLECTION_TIMEOUT / 2),
                            hard_deadline)
                        last_progress = loop.time()
                while not finished():
                    recovery = [t for t in recovery if not t.done()]
                    remaining = deadline - loop.time()
                    if remaining <= 0:
                        log("tiled upscale master: collection deadline; "
                            "handing leftovers to the fault policy"
                            if ledger is not None else
                            "tiled upscale master: collection deadline; "
                            "blending partial results")
                        break
                    if ledger is not None and registry is not None \
                            and policy != "partial":
                        # lease-driven recovery: pending units owned by a
                        # DEAD worker move NOW, not at the deadline
                        by_owner: Dict[str, List[int]] = {}
                        for u, o in ledger.owners_of_pending(
                                multi_job_id, skip_hedged=True).items():
                            if o != "master" and o not in handled_dead \
                                    and registry.state(o) \
                                    == cluster_mod.DEAD:
                                by_owner.setdefault(o, []).append(u)
                        for owner, units in by_owner.items():
                            handled_dead.add(owner)
                            if policy == "fail":
                                raise cluster_mod.ClusterFaultError(
                                    f"worker {owner} died with units "
                                    f"{sorted(units)} outstanding "
                                    f"({C.FAULT_POLICY_ENV}=fail)")
                            log(f"tiled upscale master: worker {owner} "
                                f"lease expired; recovering units "
                                f"{sorted(units)}")
                            if await handle_lost(owner, units,
                                                 "reassign"):
                                # give the replacement worker room; the
                                # post-drain fallback still backstops it
                                deadline = min(max(
                                    deadline, loop.time()
                                    + C.TILE_COLLECTION_TIMEOUT / 2),
                                    hard_deadline)
                                last_progress = loop.time()
                    if hedge_on:
                        overdue = ledger.overdue_units(multi_job_id)
                        units = sorted(u for u, o in overdue.items()
                                       if o != "master")
                        if units:
                            # off the loop: the hedge mark is a WAL
                            # append (+ fsync under sync=always)
                            hedged = await loop.run_in_executor(
                                None, lambda: ledger.mark_hedged(
                                    multi_job_id, units, "master"))
                            if hedged:
                                log(f"tiled upscale master: hedging "
                                    f"overdue units {hedged}")
                                recovery.append(loop.create_task(
                                    recover(hedged, "hedge")))
                    try:
                        item = await asyncio.wait_for(
                            q.get(), timeout=max(min(poll_s, remaining),
                                                 0.01))
                    except asyncio.TimeoutError:
                        if recovery:
                            continue  # master-side work is in flight
                        if loop.time() - last_progress \
                                > C.TILE_WAIT_TIMEOUT:
                            log("tiled upscale master: timeout waiting "
                                "for tiles"
                                + ("; handing leftovers to the fault "
                                   "policy" if ledger is not None
                                   else "; blending partial results"))
                            break
                        continue
                    last_progress = loop.time()
                    idx = int(item["tile_idx"])
                    wid = str(item["worker_id"])
                    if registry is not None:
                        registry.touch(wid)
                    if ledger is None:
                        collected[idx] = item
                    else:
                        # off the loop: the WAL-backed check-in
                        # compresses + spills the tile and fsyncs
                        won = await loop.run_in_executor(
                            None, lambda: ledger.check_in(
                                multi_job_id, idx, wid,
                                payload=([item["tensor"]], {
                                    "form": "tile",
                                    "x": item["x"], "y": item["y"],
                                    "extracted_width":
                                        item["extracted_width"],
                                    "extracted_height":
                                        item["extracted_height"],
                                    "padding": item["padding"]})))
                        if won:
                            collected[idx] = item
                    if item.get("is_last"):
                        done.add(wid)
            finally:
                # let in-flight master-side recovery land (its results
                # are about to be blended) — but the queue drop must
                # survive a cancellation delivered AT the gather await,
                # so it lives in its own finally: an orphan queue would
                # accept late tensors forever
                try:
                    if recovery:
                        await asyncio.gather(*recovery,
                                             return_exceptions=True)
                finally:
                    await ctx.job_store.remove_tile_queue(multi_job_id)
            return collected

        with Timer("tile_collect"), \
                trace_mod.span("collect", job=multi_job_id,
                               n_workers=num_workers):
            # outer timeout is a backstop only; the deadline above governs
            return run_async_in_loop(
                drain(), ctx.server_loop,
                timeout=2 * C.TILE_COLLECTION_TIMEOUT
                + 2 * C.TILE_WAIT_TIMEOUT)
