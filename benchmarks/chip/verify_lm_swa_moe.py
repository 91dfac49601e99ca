#!/usr/bin/env python3
"""The served language model with window and full attention layers and
routed experts against its plain reference, at the published widths, on
what the TIMED path produced under the cell's traffic.

  python3 benchmarks/chip/verify_lm_swa_moe.py [--config <name>]
      [--seed <n>] [--requests <k>] [--together <m>] [--rehearse]
      [--out DIR]

``verify_lm_moe.py`` for ``reference/swa_moe.py``: its serve phase as it
is (``--requests`` requests of the configuration's graph ALONE in their
executions, then ``--together`` behind a plain request that holds the
executor, as the rows of ONE execution; every expander graph with the
``SaveLanguageModelOutput`` node behind it; 512-id prompts, all 64
steps), its threefold comparison as it is (`compare_served`: router
scores within a tolerance, choices that differ from the reference's only
where the reference's own cut is that close, logits against the
reference UNDER THE PROGRAM'S CHOICES), and a compare phase of its own:
the reference teacher-forced over the prompt's ids and the served ones,
block by block and EXPERT BY EXPERT under ``jax.jit`` (one block's
float32 weights exist at a time beside the 7.4 GB of bf16).

Then three readings that each have to come out NOT correct: the
reference with its weights rounded to 8 bits (``float8_e4m3fn``), the
program itself with both its caches held in 8 bits (run here, on the
first request's prompt), and **the reference with the window off**
(every layer sees every earlier key): a comparison that accepted it
could not see the mechanism this configuration was added for.

Prints one JSON line, last; exit code 0 only if every served request is
inside every limit AND each of the three readings is outside at least
one.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from verify_lm import LIMITS_FP32, rows_of                       # noqa: E402
from verify_lm_moe import (ROUTER_TOLERANCE_FP32, compare_served,  # noqa: E402
                           program_choices, serve_phase)

# The limits at the published widths on the chip, each between two
# readings (PERF.md section 6, PR 34, calls A and B): what the served
# path gave over ten requests, two alone and eight as the rows of two
# 4-row executions (477 real prompt ids alone, 471-480 together; all 64
# steps), and what the nearest precision below the stated bf16 gave.
#
# Why the served path differs at all: its matmul operands are bf16 (a
# relative rounding of 2**-9 per operand) where the reference keeps
# float32; both caches hold bf16 keys and values; five blocks add their
# roundings up in a float32 residual stream that no norm stands in front
# of (a block's input is the stream itself).  The router is float32 at
# the highest precision in both, so its scores differ only by what the
# blocks before it rounded.  A row of a shared execution reads higher
# than a request alone (0.0030 against 0.0017), as openPangu's do
# (0.0039 against 0.0027, PR 33); why is not known.
#
#                       mean_over_std    max_over_std   router scores
#   served, 10 requests 0.00168-0.00296  0.0127-0.0203  0.0032-0.0061
#   caches in 8 bits    0.0148-0.0154    0.0897-0.107   0.0307-0.0330 (float8_e4m3fn)
#   weights in 8 bits   0.0879-0.0885    0.515-0.601    0.156-0.177
#   the window off      0.461-0.465      2.85-2.88      0.716-0.720  (248-254 of
#                                          256 choices flipped unexcused)
#
# (0.4-4.3% of the 256 choices of a request flipped against the
# reference's, every one where the reference's own cut was that close.)
# Each limit is the geometric mean of the served path's largest reading
# and the 8-bit caches' smallest: a factor of two or more from either.  The seeded
# post-norm gains are 0.5 (models/swa_moe.py), as openPangu's share.
LIMITS = {"max_over_std": 0.042, "mean_over_std": 0.0066}
LIMITS["margin_over_std"] = 2.0 * LIMITS["max_over_std"]
ROUTER_TOLERANCE = 0.0137


# --- phase 2: the reference, block by block, expert by expert ----------------

def reference_rows(config: dict, params, ids, rows, experts_held,
                   choices=None, weights_dtype=None, window="configured"):
    """``reference.forward`` over ``ids`` with one block's attention, one
    expert, or the head under ``jax.jit`` at a time; returns the logits
    and the router scores of ``rows``.  ``choices [T - 1, Le, k]``
    (`program_choices`) are forced at the positions in front of the last
    (whose row nothing reads).  ``weights_dtype`` rounds every weight
    through that type first; ``window`` runs the sliding layers with
    another window than the configuration's (None: off)."""
    import functools
    import jax
    import jax.numpy as jnp
    from reference import swa_moe as ref

    def weight(w):
        if weights_dtype is not None:
            w = w.astype(weights_dtype)
        return ref.f32(w)

    def leaves(stack, l):
        return {name: weight(jax.lax.dynamic_index_in_dim(
            leaf, l, keepdims=False)) for name, leaf in stack.items()
            if not isinstance(leaf, dict)}

    @functools.partial(jax.jit, static_argnums=(3,))
    def attend(stack, l, x, sliding):
        return ref.attend(config, leaves(stack, l), x, sliding,
                          ref.window_of(config, window))

    router = jax.jit(lambda stack, l, h: ref.router(
        config, leaves(stack, l)["gate"], h))
    finish = jax.jit(lambda stack, l, h, m: ref.finish(
        config, leaves(stack, l), h, m))
    mlp = jax.jit(lambda stack, l, n: ref.gated_mlp(leaves(stack, l), n))

    @jax.jit
    def one_expert(experts, l, at, e, n, scores, chosen):
        own = {name: weight(jax.lax.dynamic_slice(
            w, (l, at, 0, 0), (1, 1, *w.shape[2:]))[0])
            for name, w in experts.items()}
        return ref.routed(config, own, [e], n, scores, chosen)

    x = weight(params["embed_tokens"])[jnp.asarray(ids)]
    dense = config["dense_layers_held"]
    all_scores = []
    for l in range(config["num_hidden_layers"]):
        at = l - dense
        stack = params["dense_layers" if at < 0 else "moe_layers"]
        li = jnp.int32(l if at < 0 else at)
        h = attend(stack, li, x, config["layer_types"][l] == ref.SLIDING)
        if at < 0:
            m = mlp(stack, li, h)
        else:
            scores, chosen = router(stack, li, h)
            if choices is not None:
                chosen = chosen.at[:len(choices)].set(
                    jnp.asarray(choices)[:, at])
            m = mlp(stack["shared_experts"], li, h)
            for slot, e in enumerate(experts_held):
                m = m + one_expert(stack["experts"], li, jnp.int32(slot),
                                   jnp.int32(e), h, scores, chosen)
            all_scores.append(scores[rows])
        x = finish(stack, li, h, m)
    logits = jax.jit(lambda p, x: ref.head(config, p, x))(
        {"norm": weight(params["norm"]),
         "lm_head": weight(params["lm_head"])}, x[rows])
    return logits, jnp.stack(all_scores, axis=1)


def with_8bit_caches(model, served, pad_to: int) -> dict:
    """The program run here on a served request's prompt with the ring
    and the full cache held in ``float8_e4m3fn``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from comfyui_distributed_tpu.models import swa_moe
    real = swa_moe.empty_cache
    swa_moe.empty_cache = lambda *a: jax.tree_util.tree_map(
        lambda c: c.astype(jnp.float8_e4m3fn), real(*a))
    try:
        ids = served["prompt_ids"]
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(ids)] = ids
        tokens, logits, aux, _ = swa_moe.make_program(
            model.cfg, len(served["tokens"]))(
            model.params, padded, np.int32(len(ids)), np.uint32(0),
            np.float32(0.0))
    finally:
        swa_moe.empty_cache = real
    return {"prompt_ids": ids, "tokens": np.asarray(tokens[0]),
            "logits": np.asarray(logits[0]),
            **{k: np.asarray(v[0]) for k, v in aux.items()}}


def compare_phase(npz_paths: list, lm_config: dict, model_name: str,
                  pad_to: int, rehearse: bool) -> dict:
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    if rehearse:
        os.environ["DTPU_DEFAULT_FAMILY"] = "tiny"
    sys.path.insert(0, ROOT)
    from comfyui_distributed_tpu.models import registry
    model = registry.load_language_model(model_name)
    cfg = model.cfg
    config = dict(lm_config)
    if rehearse:
        config = {k: v for k, v in dataclasses.asdict(cfg).items()
                  if k != "dtype"}
        config.update(router_outputs=cfg.num_experts,
                      dense_layers_held=cfg.first_k_dense_replace)
    held = range(cfg.experts_first, cfg.experts_first + cfg.experts_held)
    fp32 = cfg.dtype == jnp.float32
    limits = LIMITS_FP32 if fp32 else LIMITS
    tolerance = ROUTER_TOLERANCE_FP32 if fp32 else ROUTER_TOLERANCE

    def reference_of(served, **kw):
        ids, rows = rows_of(served)

        def reference(choices):
            logits, scores = reference_rows(config, model.params, ids, rows,
                                            held, choices, **kw)
            return np.asarray(logits), np.asarray(scores)
        return reference

    out = {"device": {"platform": jax.devices()[0].platform,
                      "kind": jax.devices()[0].device_kind},
           "experts_held": [held.start, held.stop],
           "layer_types": list(config["layer_types"]),
           "sliding_window": config["sliding_window"], "served": []}
    for path in npz_paths:
        served = dict(np.load(path))
        t0 = time.monotonic()
        reading = compare_served(served, reference_of(served), limits,
                                 tolerance)
        reading.update(file=os.path.basename(path),
                       prompt_ids=int(len(served["prompt_ids"])),
                       positions=int(len(served["tokens"])),
                       reference_s=time.monotonic() - t0)
        out["served"].append(reading)
    first = dict(np.load(npz_paths[0]))
    full = reference_of(first)
    # the mechanism: the served path held to a reference with no window
    out["window_off"] = compare_served(first, reference_of(first,
                                                           window=None),
                                       limits, tolerance)
    # the nearest precision below the stated one.  The weights: the
    # reference itself in 8 bits against the reference, both under the
    # program's choices
    low = reference_of(first, weights_dtype=jnp.float8_e4m3fn)
    logits, scores = low(program_choices(first))
    out["weights_8bit"] = compare_served(
        {**first, "logits": logits, "router_scores": scores}, full, limits,
        tolerance)
    cached = with_8bit_caches(model, first, pad_to)
    out["cache_8bit"] = compare_served(cached, reference_of(cached), limits,
                                       tolerance)
    out["ok"] = all(r["correct"] for r in out["served"]) and not any(
        out[k]["correct"] for k in ("window_off", "weights_8bit",
                                    "cache_8bit"))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="k-exaone-236b-expand-sd15-512")
    ap.add_argument("--seed", type=int, default=3400000011)
    ap.add_argument("--requests", type=int, default=1)
    ap.add_argument("--together", type=int, default=4)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: the tiny families")
    ap.add_argument("--out", default=None)
    ap.add_argument("--compare", nargs="+", default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    import run as bench
    config = bench.load_json(os.path.join(HERE, "configs",
                                          f"{args.config}.json"))
    if args.rehearse:
        config = bench.rehearsal_config(config)
        os.environ["JAX_PLATFORMS"] = "cpu"
    nodes = {n["class_type"]: n["inputs"] for n in config["graph"].values()}
    if args.compare:
        print(json.dumps(compare_phase(
            args.compare, config["lm"],
            nodes["LanguageModelLoader"]["model_name"],
            nodes["LanguageModelGenerate"]["prompt_tokens"], args.rehearse)))
        return 0
    out_dir = os.path.abspath(args.out or os.path.join(
        ROOT, "chiprun_out", "verify_lm_swa_moe", f"s{args.seed}"))
    os.makedirs(out_dir, exist_ok=True)
    scratch = tempfile.mkdtemp(prefix="verify-lm-swa-moe-")
    try:
        paths, shared = serve_phase(args, config, scratch)
        cmd = [sys.executable, os.path.abspath(__file__), "--config",
               args.config, "--compare", *paths]
        child = subprocess.run(cmd + (["--rehearse"] if args.rehearse
                                      else []),
                               capture_output=True, text=True, cwd=ROOT)
        sys.stderr.write(child.stderr[-4000:])
        if child.returncode != 0:
            print(f"verify_lm_swa_moe: the comparison failed to run "
                  f"(exit {child.returncode})", file=sys.stderr)
            return 1
        result = json.loads(child.stdout.strip().splitlines()[-1])
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    if shared is not None:
        result["together"] = shared
        want = {"executions": 1, "rows": args.together,
                "followers_served": args.together - 1,
                "followers_dropped": 0, "expert_pairs_dropped": 0}
        if {k: shared[k] for k in want} != want:
            print(f"verify_lm_swa_moe: {args.together} requests sent "
                  f"together did not run as one execution: {shared}",
                  file=sys.stderr)
            result["ok"] = False
    with open(os.path.join(out_dir, "verify_lm_swa_moe.json"), "w",
              encoding="utf-8") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
