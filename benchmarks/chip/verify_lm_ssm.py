#!/usr/bin/env python3
"""The served language model of state-space (Mamba-2) and attention
layers against its plain reference, at the published widths, on what the
TIMED path produced under the cell's traffic.

  python3 benchmarks/chip/verify_lm_ssm.py [--config <name>] [--seed <n>]
      [--requests <k>] [--together <m>] [--rehearse] [--out DIR]

``verify_lm_moe.py``'s serve phase as it is (``--requests`` requests of
the configuration's graph ALONE in their executions, through the 1-row
program; then ``--together`` of UNEQUAL real length behind a plain request
that holds the executor, as the rows of ONE 4-row execution; every
expander graph with the ``SaveLanguageModelOutput`` node behind it; a
2048-position prompt buffer, all 64 steps), and a compare phase of its
own: ``reference/ssm_hybrid.py`` (float32, the highest matmul precision,
the recurrence position by position, no chunk, no cache, no padding)
teacher-forced over the prompt's real ids and the served ones, BLOCK BY
BLOCK under ``jax.jit`` (one block's float32 weights exist at a time
beside the 6.4 GB of bf16), and ``verify_lm.compare_logits`` over the 64
decoded positions: logits, not tokens.  A row of the shared execution
whose padding leaked into its state or its convolution fails here, since
the reference knows no padding.

Then readings that have to come out NOT correct.  At the full depth: the
reference with its weights rounded to 8 bits (``float8_e4m3fn``), and the
reference with the ``D`` skip dropped (a comparison that accepted it
could not see a term of the mixer).  The program with its recurrent
state in bf16 (the nearest precision below the stated float32) and with
its key-value cache in 8 bits are READ at the full depth too, and
reported: through 40 blocks of bf16 operands the logits cannot tell
either from the served path (LIMITS below says by how little they
differ).  So each is held where it can be seen: ONE block of its kind
(the model's first) at the published widths with FLOAT32 operands at the
highest matmul precision, run here on the first request's prompt through
the same program (the chunked scan over 8 chunks, then 64 steps on the
resident state) against the reference of that one block.  With nothing
else rounding, the block alone has to agree with the reference to
float32's own rounding, and the same block with a bf16 state (an 8-bit
cache) has to be refused.

Prints one JSON line, last; exit code 0 only if every served request and
both blocks alone are inside every limit AND each reading that has to
fail is outside at least one.
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
# readings (PERF.md section 6, PR 40): what the served path gave over its
# requests, alone through the 1-row program and as the rows of 4-row
# executions (about 1,990 real prompt ids; all 64 steps), and what the
# nearest precision below the stated one gave.
#
# Why the served path differs at all: its matmul operands are bf16 (a
# relative rounding of 2**-9 per operand) where the reference keeps
# float32, the chunked scan's products among them (the decay-weighted
# scores, the state at a chunk's start as an operand); z and xBC are
# rounded to bf16 behind in_proj; the cache and the tail hold bf16; 40
# blocks add their roundings up in a float32 residual stream that no
# block's update is small beside (the seeded embedding is 0.12 of it: a
# round-off anywhere goes through every block behind it).  The recurrent
# state itself, dt and every decay are float32 in both.  About ten
# roundings a block over 40 blocks: five to eight times what five blocks
# gave the two expert models (0.0017-0.004).
#
#                         mean_over_std    max_over_std
#   served, 1 alone       0.01527          0.1233
#   served, 4 together    0.02209-0.02316  0.1505-0.1723   (a row of a
#       shared execution reads 45% higher, as in the other families)
#   state in bf16         0.01692          0.1279    (the same request
#   cache in 8 bits       0.01532          0.1233     as the one alone)
#   weights in 8 bits     0.3392           2.458     (float8_e4m3fn)
#   the D skip dropped    1.033            7.024
#
# A bf16 state is one more bf16 rounding a block (+10%), an 8-bit cache
# touches 4 blocks of 40 (+0.3%): inside the spread between a request
# alone and a row of four, so no limit on the logits can refuse them at
# this depth, and ONE_BLOCK_LIMITS hold them instead.  Each limit here is
# the geometric mean of the served path's largest reading and the 8-bit
# weights': a factor of 3.8 from either.
LIMITS = {"max_over_std": 0.65, "mean_over_std": 0.089}
LIMITS["margin_over_std"] = 2.0 * LIMITS["max_over_std"]

# One block at the published widths, float32 operands, the highest matmul
# precision, against the reference of that block: what is left is
# float32's own rounding, the chip's transcendentals and the order of the
# sums (the chunked form against the recurrence).  Set from one Mamba
# block of this width on the CPU (a 512-position prompt, 64 steps: alone
# mean 1.3e-6, max 1.0e-5 of a logit's standard deviation; its state in
# bf16 2.6e-3 and 2.6e-2), with the room left on the side the chip's
# arithmetic (its exp, its 6-pass float32 products) had not been read
# on; then read on the chip (PERF.md section 6, PR 40, call 2; the first
# request's 1,977-id prompt, 8 chunks, 64 steps):
#
#                               mean_over_std   max_over_std
#   the Mamba block alone       7.3e-6          1.2e-4
#   its state in bf16           2.0e-3          3.0e-2
#   the attention block alone   2.9e-7          8.3e-6
#   its cache in 8 bits         4.7e-3          2.8e-2
#
# Each limit stands 7 to 10 times under the lower of the two readings
# that have to fail and 24 to 40 times over the Mamba block's own.
ONE_BLOCK_LIMITS = {"max_over_std": 3e-3, "mean_over_std": 3e-4,
                    "margin_over_std": 6e-3}

# what has to be refused: at the full depth what the logits can see
# there, and each kind of state in its block alone.  (A float32 model,
# the CPU tests' tiny one, has no bf16 operand to hide behind: every
# reading has to be refused.)
MUST_FAIL = ("weights_8bit", "skip_dropped", "mamba_block_state_bf16",
             "attention_block_cache_8bit")
READ_AT_DEPTH = ("state_bf16", "cache_8bit")


# --- phase 2: the reference, block by block ----------------------------------

def reference_logits(config: dict, params, ids, rows, weights_dtype=None,
                     drop_skip: bool = False):
    """``reference.forward`` over ``ids`` with ONE block under ``jax.jit``
    at a time (its float32 weights exist only while it runs); returns the
    logits of ``rows``.  ``weights_dtype`` rounds every weight through
    that type first; ``drop_skip`` runs the Mamba mixers with ``D`` = 0."""
    import functools
    import jax
    import jax.numpy as jnp
    from reference import ssm_hybrid as ref

    def weight(w):
        if weights_dtype is not None:
            w = w.astype(weights_dtype)
        return ref.f32(w)

    @functools.partial(jax.jit, static_argnums=(0,))
    def block(kind, stack, l, x):
        lp = {name: weight(jax.lax.dynamic_index_in_dim(
            leaf, l, keepdims=False)) for name, leaf in stack.items()}
        if drop_skip and kind == ref.MAMBA:
            lp["D"] = jnp.zeros_like(lp["D"])
        return ref.block(config, kind, lp, x)[0]

    table = jax.jit(weight)(params["embed_tokens"])
    x = ref.embed(config, table, ids)
    at = {}
    for kind in config["layer_types"]:
        stack = params["mamba_layers" if kind == ref.MAMBA
                       else "attention_layers"]
        x = block(kind, stack, jnp.int32(at.get(kind, 0)), x)
        at[kind] = at.get(kind, 0) + 1
    return jax.jit(lambda g, t, x: ref.head(config, weight(g), t, x))(
        params["norm"], table, x[rows])


def cache_8bit(state):
    import jax.numpy as jnp
    return {**state, **{k: state[k].astype(jnp.float8_e4m3fn)
                        for k in ("keys", "values")}}


def program_alone(params, cfg, served, pad_to: int, state=None) -> dict:
    """The program of ``cfg`` run here, one row, on a served request's
    prompt; ``state`` wraps ``ssm_hybrid.empty_state`` (another storage
    type for a part of the state)."""
    import numpy as np
    from comfyui_distributed_tpu.models import ssm_hybrid
    real = ssm_hybrid.empty_state
    if state is not None:
        ssm_hybrid.empty_state = lambda *a: state(real(*a))
    try:
        ids = served["prompt_ids"]
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(ids)] = ids
        tokens, logits, _, _ = ssm_hybrid.make_program(
            cfg, len(served["tokens"]))(
            params, padded, np.int32(len(ids)), np.uint32(0),
            np.float32(0.0))
    finally:
        ssm_hybrid.empty_state = real
    return {"prompt_ids": ids, "tokens": np.asarray(tokens[0]),
            "logits": np.asarray(logits[0])}


def block_alone(model, config: dict, kind: str, served, pad_to: int,
                limits, **lower) -> dict:
    """The model's first block of ``kind`` ALONE (embedding, the block,
    the final norm, the tied head) with float32 operands at the highest
    matmul precision: the program's own greedy run on a served request's
    prompt against the reference of that one block.  ``lower`` is
    `program_alone`'s ``state`` or a field of the config (``state_dtype``)
    held lower."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from comfyui_distributed_tpu.models import ssm_hybrid
    f32 = jnp.float32
    own = ssm_hybrid.STACKS[kind]
    params = {"embed_tokens": model.params["embed_tokens"].astype(f32),
              "norm": model.params["norm"].astype(f32),
              **{stack: jax.tree_util.tree_map(
                  lambda a, n=int(stack == own): a[:n].astype(f32),
                  model.params[stack]) for stack in ssm_hybrid.STACKS.values()}}
    state = lower.pop("state", None)
    cfg = dataclasses.replace(model.cfg, num_hidden_layers=1,
                              layer_types=(kind,), dtype=f32, **lower)
    with jax.default_matmul_precision("highest"):
        got = program_alone(params, cfg, served, pad_to, state)
    ids, rows = rows_of(got)
    want = reference_logits({**config, "num_hidden_layers": 1,
                             "layer_types": [kind]}, params, ids, rows)
    return compare_logits(got["logits"], np.asarray(want), got["tokens"],
                          limits)


def compare_phase(npz_paths: list, lm_config: dict, model_name: str,
                  pad_to: int, rehearse: bool) -> dict:
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    if rehearse:
        os.environ["DTPU_DEFAULT_FAMILY"] = "tiny"
    sys.path.insert(0, ROOT)
    from comfyui_distributed_tpu.models import registry, ssm_hybrid
    model = registry.load_language_model(model_name)
    cfg = model.cfg
    config = dict(lm_config)
    if rehearse:
        config = {k: v for k, v in dataclasses.asdict(cfg).items()
                  if k not in ("dtype", "state_dtype")}
    fp32 = cfg.dtype == jnp.float32
    limits = LIMITS_FP32 if fp32 else LIMITS
    alone = LIMITS_FP32 if fp32 else ONE_BLOCK_LIMITS

    def against_reference(served, **kw):
        ids, rows = rows_of(served)
        logits = np.asarray(reference_logits(config, model.params, ids, rows,
                                             **kw))
        return logits, compare_logits(served["logits"], logits,
                                      served["tokens"], limits)

    out = {"device": {"platform": jax.devices()[0].platform,
                      "kind": jax.devices()[0].device_kind},
           "layer_types": list(config["layer_types"]),
           "state_dtype": str(jnp.dtype(cfg.state_dtype)), "served": []}
    first = full = None
    for path in npz_paths:
        served = dict(np.load(path))
        t0 = time.monotonic()
        logits, reading = against_reference(served)
        reading.update(file=os.path.basename(path),
                       prompt_ids=int(len(served["prompt_ids"])),
                       positions=int(len(served["tokens"])),
                       reference_s=time.monotonic() - t0)
        out["served"].append(reading)
        if first is None:
            first, full = served, logits
    # the nearest precision below the stated one, in the program: the
    # recurrent state in bf16; the key-value cache in 8 bits.  At the full
    # depth, read; each in its block alone, held
    low = program_alone(model.params, dataclasses.replace(
        cfg, state_dtype=jnp.bfloat16), first, pad_to)
    out["state_bf16"] = against_reference(low)[1]
    low = program_alone(model.params, cfg, first, pad_to, cache_8bit)
    out["cache_8bit"] = against_reference(low)[1]
    for kind, name, lower in (
            (ssm_hybrid.MAMBA, "state_bf16", {"state_dtype": jnp.bfloat16}),
            (ssm_hybrid.ATTENTION, "cache_8bit", {"state": cache_8bit})):
        out[f"{kind}_block_alone"] = block_alone(
            model, config, kind, first, pad_to, alone)
        out[f"{kind}_block_{name}"] = block_alone(
            model, config, kind, first, pad_to, alone, **lower)
    # and in the reference: its weights in 8 bits, then the D skip
    # dropped, each against the reference itself over the first request
    ids, rows = rows_of(first)
    for name, kw in (("weights_8bit", {"weights_dtype": jnp.float8_e4m3fn}),
                     ("skip_dropped", {"drop_skip": True})):
        other = np.asarray(reference_logits(config, model.params, ids, rows,
                                            **kw))
        out[name] = compare_logits(other, full, first["tokens"], limits)
    must_fail = MUST_FAIL + (READ_AT_DEPTH if fp32 else ())
    out["ok"] = all(r["correct"] for r in out["served"]) \
        and out["mamba_block_alone"]["correct"] \
        and out["attention_block_alone"]["correct"] \
        and not any(out[k]["correct"] for k in must_fail)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="granite-4.0-h-micro-expand-sd15-512")
    ap.add_argument("--seed", type=int, default=4000000007)
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
        ROOT, "chiprun_out", "verify_lm_ssm", f"s{args.seed}"))
    os.makedirs(out_dir, exist_ok=True)
    scratch = tempfile.mkdtemp(prefix="verify-lm-ssm-")
    try:
        paths, shared = serve_phase(args, config, scratch)
        cmd = [sys.executable, os.path.abspath(__file__), "--config",
               args.config, "--compare", *paths]
        child = subprocess.run(cmd + (["--rehearse"] if args.rehearse
                                      else []),
                               capture_output=True, text=True, cwd=ROOT)
        sys.stderr.write(child.stderr[-4000:])
        if child.returncode != 0:
            print(f"verify_lm_ssm: the comparison failed to run "
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
            print(f"verify_lm_ssm: {args.together} requests sent together "
                  f"did not run as one execution: {shared}", file=sys.stderr)
            result["ok"] = False
    with open(os.path.join(out_dir, "verify_lm_ssm.json"), "w",
              encoding="utf-8") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
