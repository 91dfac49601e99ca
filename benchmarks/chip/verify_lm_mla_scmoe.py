#!/usr/bin/env python3
"""The served latent-attention language model with a shortcut-connected
expert layer and zero-compute experts against its plain reference, at the
published widths, on what the TIMED path produced under the cell's
traffic.

  python3 benchmarks/chip/verify_lm_mla_scmoe.py [--config <name>]
      [--seed <n>] [--requests <k>] [--together <m>] [--rehearse]
      [--out DIR]

``verify_lm_moe.py`` for ``reference/mla_scmoe.py``: its serve phase as it
is (``--requests`` requests of the configuration's graph ALONE in their
executions, then ``--together`` behind a plain request that holds the
executor, as the rows of ONE execution; every expander graph with the
``SaveLanguageModelOutput`` node behind it; 2048-id prompts, all 64 steps
through the eight latent cache slots), its threefold comparison as it is
(`compare_served`: what the routers SELECTED BY, ``p + b``, within a
tolerance; choices that differ from the reference's only where the
reference's own cut is that close; LOGITS against the reference UNDER THE
PROGRAM'S CHOICES), **a fourth reading** (``weights_max_diff``: the
weights the program gave its chosen pairs against ``6 x (what it selected
by - b)`` at those pairs, its OWN unbiased scores: the score-correction
bias moves the selection and never a weight; the first reading ties what
it selected by to the reference's, this one its weights to that.  On one
chip's share, where 496 of the 512 experts add nothing, a weight of the
order of 1/768 too many is below bf16's rounding of the logits, and held
to the REFERENCE's scores it would drown in what the sub-layers before
the router rounded), and a compare phase of its own: the
reference teacher-forced over the prompt's ids and the served ones,
sub-layer by sub-layer and EXPERT BY EXPERT under ``jax.jit`` (one
sub-layer's float32 weights exist at a time beside the 10.35 GB of bf16).

Then six readings that each have to come out NOT correct: what a program
with ONE departure would give (the reference with it, `reference.WRONG`:
the zero experts left out; the weights taken from ``p + b``; the expert
layer fed ``N_post1(h2)``, the usual place, instead of ``N_post0(h)``, or
its result added before the second attention; ``mla_scale_kv_lora`` left
off; and the reference with its weights rounded to 8 bits,
``float8_e4m3fn``) held to the reference as written, both under the first
request's choices: the departure's own effect, with none of bf16's
rounding to help it over a limit.

Prints one JSON line, last; exit code 0 only if every served request is
inside every limit AND each wrong reading is outside at least one.
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
# readings (PERF.md section 6, PR 49, call 1): what the served path gave
# over five requests, one alone and four as the rows of ONE 4-row
# execution (1,971-1,980 real prompt ids; all 64 steps), and the LOWEST
# reading of a wrong program that the limit has to refuse.
#
# Why the served path differs at all, and by more than the other expert
# families' (openPangu's mean is 0.003-0.004): its matmul operands are
# bf16 (a relative rounding of 2**-9 per operand) where the reference keeps
# float32; eight cache slots hold a bf16 latent; a decode step runs the
# attention ABSORBED, which rounds the 512-wide ``q_nope W_UK^T`` once more;
# the seeded scores are N(0, 9) over 2,000 keys, so a score's rounding
# (0.011) moves its key's weight by a percent and a few keys carry a
# query; and eight attentions and eight dense MLPs add their roundings up
# in a float32 stream that no norm damps (pre-norm blocks).  The router is
# float32 at the highest precision in both, so what it selected by differs
# only by what the sub-layers before it rounded: about 4% of a router
# logit, on scores of the order of 1/768 up to 0.05.
#
#                        mean_over_std  max_over_std  selected by    weights (own)
#   served, 5 requests   0.0194-0.0213  0.126-0.158   1.2e-3-2.1e-3  1.5e-8 (call 2)
#   zero experts out     0.278          1.88          1.9e-2
#   weights from p + b   0.0276         0.169         2.3e-3         2.5e-2 (6 |b|)
#   fed from 2nd norm    0.201          1.54          5.7e-2
#   added before 2nd     0.178          1.07          8.8e-3
#   latent's scale off   0.860          5.32          5.1e-2
#   weights in 8 bits    0.387          2.43          2.7e-2
#
# (Call 1 held the SERVED path to the reference with each departure; since
# call 2 a wrong program's own outputs are held to the reference as
# written, its effect without bf16's rounding: 0.175-0.853, 1.14-5.14,
# 1.8e-2-6.0e-2, and for the weights from p + b 0.0199, 0.155, 2.2e-3.
# 17-23% of a request's 256 choices flipped against the reference's: the
# top-12 of 768 near-equal scores.)  Each limit is the geometric mean of the
# served path's largest reading and the lowest of the wrong programs' that
# it has to refuse (the weights taken from p + b are NOT refused by the
# logits nor by what the router selected by: their effect, 0.020 of a
# logit's deviation, is the served path's own rounding; the fourth reading
# is theirs).
LIMITS = {"max_over_std": 0.41, "mean_over_std": 0.062}
LIMITS["margin_over_std"] = 2.0 * LIMITS["max_over_std"]
ROUTER_TOLERANCE = 4.3e-3
# |weight - 6 x (selected_by - b)| at the chosen pairs: float32's rounding
# for a program that weights by its unbiased scores, 6 |b| (the largest of
# a request's chosen outputs about 3 / 768: 0.029) for one that does not
WEIGHT_TOLERANCE = 1e-5

# the wrong programs that have to be refused (`reference.WRONG`, named here
# because this process must not import JAX while the server child lives)
REFUSED = ("zero_experts_left_out", "weights_from_biased_scores",
           "experts_fed_from_second_norm",
           "experts_added_before_second_attention", "kv_scale_left_off")


def compare_weights(served, bias, factor, tolerance) -> dict:
    """The fourth reading: the weights the program gave its choices
    against ``factor`` x its own UNBIASED scores there (what it selected
    by less the selection ``bias [L, E + Z]``)."""
    import numpy as np
    unbiased = np.asarray(served["router_scores"], np.float64) \
        - np.asarray(bias, np.float64)
    want = factor * np.take_along_axis(
        unbiased, np.asarray(served["expert_choices"]), axis=-1)
    diff = float(np.abs(np.asarray(served["expert_weights"], np.float64)
                        - want).max())
    return {"weights_max_diff": diff, "weight_tolerance": tolerance,
            "weights_correct": bool(diff <= tolerance)}


def compare_all(served, reference, bias, factor, limits, tolerance,
                weight_tolerance=WEIGHT_TOLERANCE) -> dict:
    """`verify_lm_moe.compare_served` (``reference(choices)`` -> logits
    and what the routers selected by, under the program's choices) and the
    fourth reading."""
    out = compare_served(served, lambda choices: reference(choices)[:2],
                         limits, tolerance)
    out.update(compare_weights(served, bias, factor, weight_tolerance))
    out["correct"] = out["correct"] and out["weights_correct"]
    return out


# --- phase 2: the reference, sub-layer by sub-layer, expert by expert ---------

def reference_rows(config: dict, params, ids, rows, experts_held,
                   choices=None, weights_dtype=None, wrong=None):
    """``reference.forward`` over ``ids`` with one sub-layer's attention,
    one MLP, one expert, or the head under ``jax.jit`` at a time; returns
    the logits, what the routers selected by and the weights of the
    choices used, of ``rows``.  ``choices [T - 1, L, k]``
    (`program_choices`) are forced at the positions in front of the last
    (whose row nothing reads).  ``weights_dtype`` rounds every weight
    through that type first; ``wrong`` is one of `reference.WRONG`."""
    import jax
    import jax.numpy as jnp
    from reference import mla_scmoe as ref
    assert wrong is None or wrong in ref.WRONG, wrong

    def weight(w):
        if weights_dtype is not None:
            w = w.astype(weights_dtype)
        return ref.f32(w)

    def leaves(stack, i):
        return {name: weight(jax.lax.dynamic_index_in_dim(
            leaf, i, keepdims=False)) for name, leaf in stack.items()}

    attend = jax.jit(lambda stack, i, x: ref.attend(
        config, leaves(stack, i), x, wrong))
    post_norm = jax.jit(lambda stack, i, h: ref.post_norm(
        config, leaves(stack, i), h))
    mlp = jax.jit(lambda stack, i, n: ref.gated_mlp(leaves(stack, i), n))
    router = jax.jit(lambda stack, l, u: ref.router(
        config, leaves(stack, l), u))

    @jax.jit
    def pair_weights(stack, l, p, chosen):
        return ref.pair_weights(config, leaves(stack, l), p, chosen, wrong)

    @jax.jit
    def one_expert(experts, l, at, e, u, chosen, weights):
        own = {name: weight(jax.lax.dynamic_slice(
            w, (l, at, 0, 0), (1, 1, *w.shape[2:]))[0])
            for name, w in experts.items()}
        return ref.routed(own, [e], u, chosen, weights)

    zero = jax.jit(lambda u, chosen, weights: ref.zero_experts(
        config, u, chosen, weights))

    def moe(l, u):
        li = jnp.int32(l)
        p, selected_by, chosen = router(params["router"], li, u)
        if choices is not None:
            chosen = chosen.at[:len(choices)].set(jnp.asarray(choices)[:, l])
        weights = pair_weights(params["router"], li, p, chosen)
        m = jnp.zeros_like(u)
        for slot, e in enumerate(experts_held):
            m = m + one_expert(params["experts"], li, jnp.int32(slot),
                               jnp.int32(e), u, chosen, weights)
        if wrong != "zero_experts_left_out":
            m = m + zero(u, chosen, weights)
        return m, selected_by[rows], weights[rows]

    x = weight(params["embed_tokens"])[jnp.asarray(ids)]
    sub = params["sublayers"]
    all_scores, all_weights = [], []
    for l in range(config["num_layers"]):
        s0, s1 = jnp.int32(2 * l), jnp.int32(2 * l + 1)
        h = attend(sub, s0, x)
        u = post_norm(sub, s0, h)
        if wrong != "experts_fed_from_second_norm":
            m, scores, weights = moe(l, u)
        h = h + mlp(sub, s0, u)
        if wrong == "experts_added_before_second_attention":
            h, m = h + m, 0.0
        h2 = attend(sub, s1, h)
        n = post_norm(sub, s1, h2)
        if wrong == "experts_fed_from_second_norm":
            m, scores, weights = moe(l, n)
        x = h2 + mlp(sub, s1, n) + m
        all_scores.append(scores)
        all_weights.append(weights)
    logits = jax.jit(lambda p, x: ref.head(config, p, x))(
        {"norm": weight(params["norm"]),
         "lm_head": weight(params["lm_head"])}, x[rows])
    return logits, jnp.stack(all_scores, axis=1), \
        jnp.stack(all_weights, axis=1)


def reference_config(cfg, lm_config: dict, rehearse: bool) -> dict:
    """The ``lm`` block of the configuration's file; for a rehearsal the
    tiny model's own keys."""
    import dataclasses
    if not rehearse:
        return dict(lm_config)
    config = {k: v for k, v in dataclasses.asdict(cfg).items()
              if k != "dtype"}
    return {**config, "router_outputs": cfg.router_outputs}


def compare_phase(npz_paths: list, lm_config: dict, model_name: str,
                  rehearse: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    if rehearse:
        os.environ["DTPU_DEFAULT_FAMILY"] = "tiny"
    sys.path.insert(0, ROOT)
    from comfyui_distributed_tpu.models import registry
    model = registry.load_language_model(model_name)
    cfg = model.cfg
    config = reference_config(cfg, lm_config, rehearse)
    held = range(cfg.experts_first, cfg.experts_first + cfg.experts_held)
    fp32 = cfg.dtype == jnp.float32
    limits = LIMITS_FP32 if fp32 else LIMITS
    tolerance = ROUTER_TOLERANCE_FP32 if fp32 else ROUTER_TOLERANCE
    bias = np.asarray(model.params["router"]["e_score_correction_bias"],
                      np.float32)

    def reference_of(served, **kw):
        """``reference(choices)`` -> logits, what the routers selected by
        and the weights of the choices; the last call's result is kept
        (the six wrong readings share the first request's)."""
        ids, rows = rows_of(served)
        kept = {}

        def reference(choices):
            key = np.asarray(choices).tobytes()
            if kept.get("key") != key:
                kept.update(key=key, value=tuple(
                    np.asarray(a) for a in reference_rows(
                        config, model.params, ids, rows, held, choices,
                        **kw)))
            return kept["value"]
        return reference

    def compare(served, reference):
        return compare_all(served, reference, bias,
                           cfg.routed_scaling_factor, limits, tolerance)

    out = {"device": {"platform": jax.devices()[0].platform,
                      "kind": jax.devices()[0].device_kind},
           "experts_held": [held.start, held.stop],
           "router_outputs": config["router_outputs"],
           "zero_expert_num": config["zero_expert_num"], "served": []}
    for path in npz_paths:
        served = dict(np.load(path))
        t0 = time.monotonic()
        reading = compare(served, reference_of(served))
        choices = served["expert_choices"]
        reading.update(file=os.path.basename(path),
                       prompt_ids=int(len(served["prompt_ids"])),
                       positions=int(len(served["tokens"])),
                       zero_share=float((choices >= cfg.n_routed_experts)
                                        .mean()),
                       local_share=float(((choices >= held.start)
                                          & (choices < held.stop)).mean()),
                       reference_s=time.monotonic() - t0)
        out["served"].append(reading)
    # what a program with ONE departure, or with 8-bit weights, would give
    # (the reference with it, under the first request's choices) held to
    # the reference as written
    first = dict(np.load(npz_paths[0]))
    right = reference_of(first)
    wrong = {name: reference_of(first, wrong=name) for name in REFUSED}
    wrong["weights_8bit"] = reference_of(first,
                                         weights_dtype=jnp.float8_e4m3fn)
    for name, program in wrong.items():
        logits, scores, weights = program(program_choices(first))
        out[name] = compare({**first, "logits": logits,
                             "router_scores": scores,
                             "expert_weights": weights}, right)
    out["ok"] = all(r["correct"] for r in out["served"]) and not any(
        out[k]["correct"] for k in wrong)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="longcat-flash-omni-expand-sd15-512")
    ap.add_argument("--seed", type=int, default=4900000011)
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
            nodes["LanguageModelLoader"]["model_name"], args.rehearse)))
        return 0
    out_dir = os.path.abspath(args.out or os.path.join(
        ROOT, "chiprun_out", "verify_lm_mla_scmoe", f"s{args.seed}"))
    os.makedirs(out_dir, exist_ok=True)
    scratch = tempfile.mkdtemp(prefix="verify-lm-mla-scmoe-")
    try:
        paths, shared = serve_phase(args, config, scratch)
        cmd = [sys.executable, os.path.abspath(__file__), "--config",
               args.config, "--compare", *paths]
        child = subprocess.run(cmd + (["--rehearse"] if args.rehearse
                                      else []),
                               capture_output=True, text=True, cwd=ROOT)
        sys.stderr.write(child.stderr[-4000:])
        if child.returncode != 0:
            print(f"verify_lm_mla_scmoe: the comparison failed to run "
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
            print(f"verify_lm_mla_scmoe: {args.together} requests sent "
                  f"together did not run as one execution: {shared}",
                  file=sys.stderr)
            result["ok"] = False
    with open(os.path.join(out_dir, "verify_lm_mla_scmoe.json"), "w",
              encoding="utf-8") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
