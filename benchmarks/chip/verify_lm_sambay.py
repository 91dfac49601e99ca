#!/usr/bin/env python3
"""The served decoder-hybrid-decoder (Mamba-1 and window differential
attention in front, ONE key-value cache and ONE state-space memory shared
by the layers behind) against its plain reference, at the published
widths, on what the TIMED path produced under the cell's traffic.

  python3 benchmarks/chip/verify_lm_sambay.py [--config <name>] [--seed <n>]
      [--requests <k>] [--together <m>] [--rehearse] [--out DIR]

``verify_lm_moe.py``'s serve phase as it is (``--requests`` requests of
the configuration's graph ALONE in their executions, through the 1-row
program; then ``--together`` of UNEQUAL real length behind a plain request
that holds the executor, as the rows of ONE 4-row execution; every
expander graph with the ``SaveLanguageModelOutput`` node behind it; an
8192-position prompt buffer, all 64 steps), and a compare phase of its
own: ``reference/sambay.py`` (float32, the highest matmul precision, ALL
32 layers at EVERY position, the recurrence position by position, the
masked square, no chunk, no cache, no ring, no padding) teacher-forced
over the prompt's real ids and the served ones, LAYER BY LAYER under
``jax.jit`` (one layer's float32 weights exist at a time beside the
7.7 GB of bf16; the square 256 query rows at a time; every request's ids
right-padded to ONE length, which a causal model cannot see, so that six
kinds of layer compile once), and
``verify_lm.compare_logits`` over the 64 decoded positions: logits, not
tokens.  The program ran its back half for a prompt's LAST position only
and the reference for every one: a back half that needed another
position's output fails here.

Then readings that have to come out NOT correct, each the reference
against itself over the first request: its weights rounded to 8 bits
(``float8_e4m3fn``: the nearest precision below the stated bf16), and the
gated memory units fed the memory layer's GATED output (``y * silu(z)``:
a comparison that accepted it could not see which memory the back half
reads), and the window layers' band taken off (every key up to the
query's own: the band decides a logit).

Prints one JSON line, last; exit code 0 only if every served request is
inside every limit AND each reading that has to fail is outside at least
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

from verify_lm import LIMITS_FP32, compare_logits, rows_of       # noqa: E402
from verify_lm_moe import serve_phase                            # noqa: E402

# The limits at the published widths on the chip, each between two
# readings (PERF.md section 6, PR 46, call 1): what the served path gave
# over its requests, alone through the 1-row program and as the rows of a
# 4-row execution (8,121 to 8,130 real prompt ids; all 64 steps), and what
# the reference gave against itself with something taken away.
#
# Why the served path differs at all: its matmul operands are bf16 (a
# relative rounding of 2**-9 an operand) where the reference keeps
# float32; u and z are rounded to bf16 behind in_proj, the gated products
# and the maps' outputs before the next product; the caches, the rings
# and the tails hold bf16; 32 layers add their roundings up in a float32
# residual stream.  The recurrent state, dt, every decay and the scan's
# output are float32 in both, and the program's back half over ONE
# position a row is held to the reference's over every position.
#
#                            mean_over_std     max_over_std
#   served, 1 alone          0.01389           0.1222
#   served, 4 together       0.01857-0.01922   0.1365-0.1481   (a row of
#       a shared execution reads a third higher, as in the other families)
#   memory_gated             0.2585            1.838
#   weights in 8 bits        0.3440            2.454      (float8_e4m3fn)
#   window_off               0.7068            4.740
#
# Each limit is the geometric mean of the served path's largest reading
# and the LOWEST of the readings that have to fail: a factor of 3.5 to
# 3.7 from either.
LIMITS = {"max_over_std": 0.52, "mean_over_std": 0.07}
LIMITS["margin_over_std"] = 2.0 * LIMITS["max_over_std"]

MUST_FAIL = ("weights_8bit", "memory_gated", "window_off")
ROWS_AT_ONCE = 256      # of the masked square: [40, 256, 8255] a map


# --- phase 2: the reference, layer by layer ----------------------------------

def make_reference(config: dict, weights_dtype=None,
                   memory_gated: bool = False):
    """``reference.forward`` with ONE layer under ``jax.jit`` at a time
    (its float32 weights exist only while it runs; one compile a KIND of
    layer, kept for every request) -> ``logits(params, ids, first, count)``:
    the logits of ``count`` rows from ``first`` on.  ``weights_dtype``
    rounds every weight through that type first; ``memory_gated`` hands
    the gated memory units ``y * silu(z)`` of the memory layer."""
    import functools
    import jax
    import jax.numpy as jnp
    from reference import sambay as ref

    def weight(w):
        if weights_dtype is not None:
            w = w.astype(weights_dtype)
        return ref.f32(w)

    @functools.partial(jax.jit, static_argnums=(0,))
    def layer(kind, stack, i, l, x, memory, cache):
        lp = {name: weight(jax.lax.dynamic_index_in_dim(
            leaf, i, keepdims=False)) for name, leaf in stack.items()}
        x_in = x
        x, hands_on = ref.block(config, kind, lp, x, l, memory, cache,
                                ROWS_AT_ONCE)
        if kind == ref.MEMORY and memory_gated:
            with jax.default_matmul_precision(ref.PRECISION):
                v = ref.layer_norm(x_in, lp["input_layernorm"],
                                   lp["input_layernorm_bias"],
                                   config["layer_norm_eps"])
                z = jnp.split(v @ lp["in_proj"], 2, axis=-1)[1]
            hands_on = hands_on * jax.nn.silu(z)
        return x, hands_on

    @functools.partial(jax.jit, static_argnums=(5,))
    def head(gain, bias, table, x, first, count):
        rows = jax.lax.dynamic_slice_in_dim(x, first, count)
        return ref.head(config, weight(gain), weight(bias), weight(table),
                        rows)

    embed = jax.jit(lambda table, ids: weight(table[ids]))

    def logits(params, ids, first, count):
        x = embed(params["embed_tokens"], jnp.asarray(ids))
        at = dict.fromkeys(ref.STACKS.values(), 0)
        memory = cache = None
        for l, kind in enumerate(ref.layer_kinds(config)):
            stack = ref.STACKS[kind]
            x, hands_on = layer(kind, params[stack], jnp.int32(at[stack]),
                                jnp.int32(l), x,
                                memory if kind == ref.GMU else None,
                                cache if kind == ref.CROSS else None)
            at[stack] += 1
            if kind == ref.MEMORY:
                memory = hands_on
            elif kind == ref.FULL:
                cache = hands_on
        return head(params["final_layernorm"],
                    params["final_layernorm_bias"], params["embed_tokens"],
                    x, jnp.int32(first), count)

    return logits


def teacher_forced(served, length: int):
    """`rows_of`'s ids right-padded to ``length`` (the model is causal:
    what stands behind a position does not reach it, so every request
    runs ONE compiled shape), and where its rows start."""
    import numpy as np
    ids, rows = rows_of(served)
    padded = np.zeros((length,), np.int32)
    padded[:len(ids)] = ids
    return padded, rows.start, rows.stop - rows.start


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
                  if k not in ("dtype", "state_dtype", "prefill_chunk")}
    fp32 = cfg.dtype == jnp.float32
    limits = LIMITS_FP32 if fp32 else LIMITS

    out = {"device": {"platform": jax.devices()[0].platform,
                      "kind": jax.devices()[0].device_kind},
           "state_dtype": str(jnp.dtype(cfg.state_dtype)), "served": []}
    reference = make_reference(config)
    first = full = None
    for path in npz_paths:
        served = dict(np.load(path))
        length = pad_to + len(served["tokens"])
        t0 = time.monotonic()
        logits = np.asarray(reference(model.params,
                                      *teacher_forced(served, length)))
        reading = compare_logits(served["logits"], logits, served["tokens"],
                                 limits)
        reading.update(file=os.path.basename(path),
                       prompt_ids=int(len(served["prompt_ids"])),
                       positions=int(len(served["tokens"])),
                       reference_s=time.monotonic() - t0)
        out["served"].append(reading)
        if first is None:
            first, full = served, logits
    # the reference against itself over the first request: what has to
    # be refused, and what is read
    for name, over, kw in (
            ("weights_8bit", {}, {"weights_dtype": jnp.float8_e4m3fn}),
            ("memory_gated", {}, {"memory_gated": True}),
            ("window_off", {"sliding_window": length}, {})):
        other = np.asarray(make_reference({**config, **over}, **kw)(
            model.params, *teacher_forced(first, length)))
        out[name] = compare_logits(other, full, first["tokens"], limits)
    out["ok"] = all(r["correct"] for r in out["served"]) \
        and not any(out[k]["correct"] for k in MUST_FAIL)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="phi-4-mini-flash-expand-sd15-512")
    ap.add_argument("--seed", type=int, default=4600000019)
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
        ROOT, "chiprun_out", "verify_lm_sambay", f"s{args.seed}"))
    os.makedirs(out_dir, exist_ok=True)
    scratch = tempfile.mkdtemp(prefix="verify-lm-sambay-")
    try:
        paths, shared = serve_phase(args, config, scratch)
        cmd = [sys.executable, os.path.abspath(__file__), "--config",
               args.config, "--compare", *paths]
        child = subprocess.run(cmd + (["--rehearse"] if args.rehearse
                                      else []),
                               capture_output=True, text=True, cwd=ROOT)
        sys.stderr.write(child.stderr[-4000:])
        if child.returncode != 0:
            print(f"verify_lm_sambay: the comparison failed to run "
                  f"(exit {child.returncode})", file=sys.stderr)
            return 1
        result = json.loads(child.stdout.strip().splitlines()[-1])
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    if shared is not None:
        result["together"] = {k: v for k, v in shared.items()
                              if not k.startswith("expert_")}
        want = {"executions": 1, "rows": args.together,
                "followers_served": args.together - 1,
                "followers_dropped": 0}
        if {k: shared[k] for k in want} != want:
            print(f"verify_lm_sambay: {args.together} requests sent "
                  f"together did not run as one execution: {shared}",
                  file=sys.stderr)
            result["ok"] = False
    with open(os.path.join(out_dir, "verify_lm_sambay.json"), "w",
              encoding="utf-8") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
