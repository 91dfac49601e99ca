#!/usr/bin/env python3
"""The served language model with a learned key selection and routed
experts against its plain reference, at the published widths, on what
the TIMED path produced under the cell's traffic.

  python3 benchmarks/chip/verify_lm_dsa_moe.py [--config <name>]
      [--seed <n>] [--requests <k>] [--together <m>] [--rehearse]
      [--out DIR]

``verify_lm_moe.py`` for ``reference/dsa_moe.py``: its serve phase as it
is (``--requests`` requests of the configuration's graph ALONE in their
executions, then ``--together`` behind a plain request that holds the
executor, as the rows of ONE execution; every expander graph with the
``SaveLanguageModelOutput`` node behind it; 8192-id prompts, all 64
steps), and a compare phase of its own: the reference teacher-forced
over the prompt's ids and the served ones, block by block, a block of
512 QUERIES at a time and EXPERT BY EXPERT under ``jax.jit`` (one
expert's float32 weights exist at a time beside the 8.75 GB of bf16; the
``[32, 512, T]`` scores of a query block are 0.5 GB).

Routing AND key selection are discontinuous: a rounding flips an
8th-against-9th expert or a 2,048th-against-2,049th key and every number
behind it jumps.  So per request:

(i)   ONE pass of the reference FORCED to the program's expert choices
      and to the program's key selections (over the prompt: the packed
      record ``prompt_selected``; at the decoded positions:
      ``key_selections``).  It gives `verify_lm_moe.compare_served`'s
      three readings (router scores within ROUTER_TOLERANCE; no
      unexcused flip of an expert; the logits' ``max_over_std``,
      ``mean_over_std``, ``margin_over_std`` within LIMITS) and, of the
      index: the reference's OWN selection for the state the program's
      choices led to beside the program's, ``selection_agree`` (the share
      of the reference's keys that the program chose too) and
      ``selection_worst_margin`` (for every key one side chose and the
      other did not, how far the reference's score of it lies on the
      wrong side of the reference's own cut, in standard deviations of
      that query's scores; within INDEX_MARGIN).
(ii)  ONE pass FREE against free: the share of selected keys and of
      expert choices that agree when nothing is forced, and the logits'
      readings there (reported; what (i) excuses shows here as it is).

Then readings that each have to come out NOT correct, on the first
request: the reference with its weights rounded to 8 bits
(``float8_e4m3fn``); the program itself with all three caches held in 8
bits (run here, one row); and the reference with the mechanism broken
six ways (`reference.BREAKAGES`: no selection, the last ``topk`` keys,
the ReLU dropped, the heads' weights dropped; the top-7 of 8 experts; a
softmax router without renormalisation): a comparison that accepted one
could not see what this configuration was added for.

Prints one JSON line, last; exit code 0 only if every served request is
inside every limit AND each reading that has to fail is outside at least
one.
"""

from __future__ import annotations

import argparse
import functools
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
from verify_lm_moe import (ROUTER_TOLERANCE_FP32, compare_served,  # noqa: E402
                           program_choices, serve_phase)

# The limits at the published widths on the chip, each between two
# readings (PERF.md section 6, PR 42, calls 1 and 5: two seeds of
# traffic, the second on the final tree): what the served path gave over
# ten requests, two alone through the 1-row program and eight as the rows
# of two 4-row executions (8,121-8,130 real prompt ids; all 64 steps), and
# what the nearest precision below the stated bf16 gave.
#
# Why the served path differs at all: its matmul operands are bf16 (a
# relative rounding of 2**-9 per operand) where the reference keeps
# float32; all three caches hold bf16; the head norms' seeded gains of 2
# make a query's scores N(0, 16), so a rounding of a score moves a
# weight by as much as the score carries; six blocks add their roundings
# up in a float32 residual stream that no norm stands behind (pre-norm
# blocks): five to ten times what five sandwich-normed blocks gave the
# two other expert models (0.0017-0.004).  The router is float32 at the
# highest precision in both, so its scores differ only by what the blocks
# before it rounded; they are softmax probabilities over 128 (0.008 on
# average, the chosen ones 0.02-0.1).
#
#                       mean_over_std   max_over_std  router scores  index margin
#   served, 10 requests 0.0209-0.0218   0.150-0.177   0.0043-0.0085  0.223-0.275
#   caches in 8 bits    0.0841-0.0862   0.608-0.656   0.0224-0.0396  0.758-0.784
#   weights in 8 bits   0.315-0.316     2.19-2.30     0.086-0.131    (forced)
#   top-7 of 8 experts  0.239-0.242     1.76-2.07
#   no renormalisation  0.519-0.521     3.60-3.73
#   the ReLU dropped    0.686-0.691     4.64-4.82
#   no selection        0.720-0.724     4.95-5.17
#   no head weights     0.788-0.792     5.21-5.58
#   the last 2,048 keys 0.805-0.806     5.61-6.24
#
# (Call 1 ran the selected softmax as `jax.nn.softmax`, call 5 normalised
# behind the value product: the served readings are the same.)
# (8-13% of the 384 expert choices of a request flipped against the
# reference's, every one where the reference's own cut was that close:
# the 8th and 9th of 128 softmax probabilities lie 2e-6 apart at the
# least.  Under the program's choices upstream the program's selection is
# the reference's own on 99.29-99.30% of 75 M selecting query-key pairs;
# a key only one side chose lies at most 0.275 standard deviations of that
# query's scores on the wrong side of the reference's cut: the block's
# input differs by what the blocks before it rounded, over 75 M pairs.
# FREE against free the two part company: 91.8% of the keys and 27-31%
# of the choice sets agree and the logits differ by 0.39-0.41 of a standard
# deviation: reported, not held; a flip of either kind changes every
# number behind it, which is why (i) forces both.)
# Each limit is the geometric mean of the served path's largest reading
# and the 8-bit caches' smallest: a factor of 1.5-2 from either.
LIMITS = {"max_over_std": 0.31, "mean_over_std": 0.043}
LIMITS["margin_over_std"] = 2.0 * LIMITS["max_over_std"]
ROUTER_TOLERANCE = 0.013
# a key only one side selected lies, by the reference's scores, within
# this many standard deviations of the query's scores of the reference's
# own cut
INDEX_MARGIN = 0.45
INDEX_MARGIN_FP32 = 1e-4
# the least share of the reference's selected keys that the program
# selected too, under the program's choices upstream (a floor against a
# program that selects something else; the 8-bit caches read 0.9925
# beside the served path's 0.9929 and are refused by the other limits)
SELECTION_AGREE = 0.97

QUERY_BLOCK = 512
ROUTER_BREAKAGES = ("top7_of_8", "no_renormalisation")
MUST_FAIL = ("weights_8bit", "cache_8bit")


# --- what the program selected, as masks over a row's REAL positions ---------

def program_selections(served):
    """For every position of `rows_of`'s ids and every block, the keys
    the program attended to, as a mask over those positions: ``[T, L, T]``
    bool, ``T = prompt + new``.  The packed record covers the prompt
    buffer (a row's real ids at its end); ``key_selections[i]`` is the
    query whose logits drew token ``i`` (``i = 0``: the prompt's last id,
    which the record holds too).  The last position's query (the last
    token's: nothing reads its row) sees every key."""
    import numpy as np
    n, new = len(served["prompt_ids"]), len(served["tokens"])
    packed = served["prompt_selected"]              # [P, L, words]
    P, L = packed.shape[:2]
    first, T = P - n, n + new
    bits = np.unpackbits(
        np.ascontiguousarray(packed).view(np.uint8), axis=-1,
        bitorder="little")[..., :P].astype(bool)    # [P, L, P]
    out = np.zeros((T, L, T), bool)
    out[:n, :, :n] = bits[first:, :, first:]
    chosen = served["key_selections"]               # [new, L, topk]
    last = np.zeros((L, P), bool)
    for l in range(L):
        last[l, chosen[0, l][chosen[0, l] >= 0]] = True
    if not np.array_equal(last, bits[P - 1]):
        raise ValueError("the record of the prompt's last query is not "
                         "its key_selections")
    for i in range(1, new):
        for l in range(L):
            keys = chosen[i, l][chosen[i, l] >= 0] - first
            out[n - 1 + i, l, keys] = True
    out[T - 1] = True
    return out


# --- phase 2: the reference, block by block, query block by query block ------

class Reference:
    """``reference.forward`` with one block's attention over a block of
    queries, one expert, or the head under ``jax.jit`` at a time.  The
    jitted pieces are made once a variant (``weights_dtype`` rounds every
    weight through that type first; ``breakage`` is one of
    `reference.BREAKAGES` or ROUTER_BREAKAGES)."""

    def __init__(self, config: dict, weights_dtype=None, breakage=None):
        import jax
        import jax.numpy as jnp
        from reference import dsa_moe as ref
        self.ref, self.breakage = ref, breakage
        self.config = config = dict(config)
        if breakage == "no_renormalisation":
            config["norm_topk_prob"] = False
        attention = breakage if breakage in ref.BREAKAGES else None
        topk = config["sa_config"]["topk"]

        def weight(w):
            if weights_dtype is not None:
                w = w.astype(weights_dtype)
            return ref.f32(w)

        def leaves(stack, l):
            return jax.tree_util.tree_map(
                lambda leaf: weight(jax.lax.dynamic_index_in_dim(
                    leaf, l, keepdims=False)),
                {k: v for k, v in stack.items() if k != "experts"})

        self.weight = weight
        self.prepare = jax.jit(lambda stack, l, x, positions: ref.prepare(
            config, leaves(stack, l), x, positions))

        @functools.partial(jax.jit, static_argnums=(0,))
        @ref.highest
        def rows(force, stack, l, x, positions, at, u, k, v, ki, program):
            """``h`` of the queries ``at`` under the program's selection
            (``force``) or the reference's own, and the two compared."""
            lp = leaves(stack, l)
            scores = ref.index_scores(config, lp["indexer"], u[at],
                                      positions[:, at], ki, attention)
            own = ref.select(config, scores, at, attention)
            a, _ = ref.attention(config, lp, u[at], positions[:, at], at, k,
                                 v, ki, program if force else own)
            seen = jnp.arange(len(x))[None, :] <= at[:, None]
            count = jnp.sum(seen, axis=-1, keepdims=True)
            mean = jnp.sum(jnp.where(seen, scores, 0.0), axis=-1,
                           keepdims=True) / count
            std = jnp.sqrt(jnp.sum(jnp.where(seen, (scores - mean) ** 2, 0.0),
                                   axis=-1, keepdims=True) / count) + 1e-30
            cut = jnp.min(jnp.where(own, scores, jnp.inf), axis=-1,
                          keepdims=True)
            under = jnp.max(jnp.where(seen & ~own, scores, -jnp.inf),
                            axis=-1, keepdims=True)
            # a key only the program chose scores, by the reference, its
            # cut less the margin at least; one only the reference chose,
            # the best key it left out plus the margin at most
            off = jnp.maximum(
                jnp.where(program & ~own, (cut - scores) / std, 0.0),
                jnp.where(own & ~program, (scores - under) / std, 0.0))
            # (nothing reads the last position's row: it is not compared)
            selecting = (count > topk) & (at < len(x) - 1)[:, None]
            off = jnp.where(selecting, off, 0.0)
            return (x[at] + a @ lp["o_proj"],
                    jnp.sum(own & program & selecting),
                    jnp.sum(own & selecting), jnp.max(off))

        self.rows = rows
        self.mlp_input = jax.jit(lambda stack, l, h: ref.mlp_input(
            config, leaves(stack, l), h))

        @jax.jit
        def one_expert(experts, l, e, n, scores, chosen):
            own = {name: weight(jax.lax.dynamic_slice(
                w, (l, e, 0, 0), (1, 1, *w.shape[2:]))[0])
                for name, w in experts.items()}
            return ref.routed(config, own, [e], n, scores, chosen)

        self.one_expert = one_expert
        self.head = jax.jit(lambda p, x: ref.head(config, p, x))

    def forward(self, params, ids, rows, program, choices=None,
                force: bool = False):
        """The logits and router scores of ``rows``, the choices used
        there ``[N, L, k]`` and the index's readings against ``program``
        (`program_selections`' masks): forced to them and to ``choices
        [T - 1, L, k]`` (`program_choices`) with ``force``, free of them
        without."""
        import jax.numpy as jnp
        import numpy as np
        ref, stack = self.ref, params["layers"]
        x = self.weight(params["embed_tokens"])[jnp.asarray(ids)]
        T = len(x)
        positions = ref.text_positions(T)
        all_scores, used, agree, total, worst = [], [], 0, 0, 0.0
        for l in range(self.config["num_hidden_layers"]):
            li = jnp.int32(l)
            whole = self.prepare(stack, li, x, positions)
            out = []
            for start in range(0, T, QUERY_BLOCK):
                stop = min(start + QUERY_BLOCK, T)
                h, same, own, off = self.rows(
                    force, stack, li, x, positions, jnp.arange(start, stop),
                    *whole, jnp.asarray(program[start:stop, l]))
                out.append(h)
                agree, total = agree + int(same), total + int(own)
                worst = max(worst, float(off))
            h = jnp.concatenate(out)
            n, scores, chosen = self.mlp_input(stack, li, h)
            if force and choices is not None:
                chosen = chosen.at[:len(choices)].set(
                    jnp.asarray(choices)[:, l])
            if self.breakage == "top7_of_8":
                chosen = chosen[:, :-1]
            m = jnp.zeros_like(n)
            for e in range(self.config["num_experts"]):
                m = m + self.one_expert(stack["experts"], li, jnp.int32(e),
                                        n, scores, chosen)
            all_scores.append(scores[rows])
            used.append(chosen[rows])
            x = h + m
        logits = self.head({"norm": self.weight(params["norm"]),
                            "lm_head": self.weight(params["lm_head"])},
                           x[rows])
        return np.asarray(logits), np.asarray(jnp.stack(all_scores, axis=1)), \
            np.asarray(jnp.stack(used, axis=1)), \
            {"selection_agree": agree / total if total else 1.0,
             "selecting_keys": total, "selection_worst_margin": worst}


def compare_request(reference: Reference, params, served, limits, tolerance,
                    margin, free: bool = True) -> dict:
    """(i) and, with ``free``, (ii) of one served request."""
    import numpy as np
    ids, rows = rows_of(served)
    program = program_selections(served)
    index = {}

    def forced(choices):
        logits, scores, _, read = reference.forward(
            params, ids, rows, program, choices, force=True)
        index.update(read)
        return logits, scores

    out = compare_served(served, forced, limits, tolerance)
    out.update(index, index_margin=margin, selection_agree_least=(
        SELECTION_AGREE if free else None))
    out["selection_correct"] = bool(
        index["selection_worst_margin"] <= margin
        and (not free or index["selection_agree"] >= SELECTION_AGREE))
    out["correct"] = out["correct"] and out["selection_correct"]
    if free:
        logits, _, used, read = reference.forward(params, ids, rows, program)
        theirs = np.sort(served["expert_choices"], axis=-1)
        under = compare_logits(served["logits"], logits, served["tokens"],
                               limits)
        out["free"] = {
            "selection_agree": read["selection_agree"],
            "expert_choices_agree": float(
                (np.sort(used, axis=-1) == theirs).all(axis=-1).mean()),
            **{k: under[k] for k in ("max_over_std", "mean_over_std",
                                     "argmax_agree")}}
    return out


def with_8bit_caches(model, served, pad_to: int) -> dict:
    """The program run here, one row, on a served request's prompt with
    the keys, the values and the index keys held in ``float8_e4m3fn``."""
    import jax.numpy as jnp
    import numpy as np
    from comfyui_distributed_tpu.models import dsa_moe
    real = dsa_moe.empty_cache
    dsa_moe.empty_cache = lambda *a: tuple(
        c.astype(jnp.float8_e4m3fn) for c in real(*a))
    try:
        ids = served["prompt_ids"]
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(ids)] = ids
        tokens, logits, aux, _ = dsa_moe.make_program(
            model.cfg, len(served["tokens"]))(
            model.params, padded, np.int32(len(ids)), np.uint32(0),
            np.float32(0.0))
    finally:
        dsa_moe.empty_cache = real
    return {"prompt_ids": ids, "tokens": np.asarray(tokens[0]),
            "logits": np.asarray(logits[0]),
            **{k: np.asarray(v[0]) for k, v in aux.items()}}


def reference_config(cfg) -> dict:
    """A program config (the tiny one of a rehearsal) as the reference
    reads a ``config.json``."""
    import dataclasses
    config = {k: v for k, v in dataclasses.asdict(cfg).items()
              if k != "dtype"}
    config["sa_config"] = {k: config[k] for k in (
        "indexer_num_heads", "indexer_head_dim", "topk")}
    config["rope_scaling"] = {"mrope_section": list(cfg.mrope_section)}
    return config


def compare_phase(npz_paths: list, lm_config: dict, model_name: str,
                  pad_to: int, rehearse: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    if rehearse:
        os.environ["DTPU_DEFAULT_FAMILY"] = "tiny"
    sys.path.insert(0, ROOT)
    from comfyui_distributed_tpu.models import registry
    from reference import dsa_moe as ref
    model = registry.load_language_model(model_name)
    cfg = model.cfg
    config = reference_config(cfg) if rehearse else dict(lm_config)
    fp32 = cfg.dtype == jnp.float32
    limits = LIMITS_FP32 if fp32 else LIMITS
    tolerance = ROUTER_TOLERANCE_FP32 if fp32 else ROUTER_TOLERANCE
    margin = INDEX_MARGIN_FP32 if fp32 else INDEX_MARGIN
    full = Reference(config)

    def compare(served, reference=full, free=False):
        return compare_request(reference, model.params, served, limits,
                               tolerance, margin, free)

    out = {"device": {"platform": jax.devices()[0].platform,
                      "kind": jax.devices()[0].device_kind},
           "blocks": config["num_hidden_layers"],
           "topk": config["sa_config"]["topk"], "served": []}
    for path in npz_paths:
        served = dict(np.load(path))
        t0 = time.monotonic()
        reading = compare(served, free=True)
        reading.update(file=os.path.basename(path),
                       prompt_ids=int(len(served["prompt_ids"])),
                       positions=int(len(served["tokens"])),
                       reference_s=time.monotonic() - t0)
        out["served"].append(reading)
    first = dict(np.load(npz_paths[0]))
    # the nearest precision below the stated one.  The weights: the
    # reference itself in 8 bits against the reference, both under the
    # program's choices and selections
    ids, rows = rows_of(first)
    selections, choices = program_selections(first), program_choices(first)
    low = Reference(config, weights_dtype=jnp.float8_e4m3fn)
    logits, scores, _, _ = low.forward(model.params, ids, rows, selections,
                                       choices, force=True)
    out["weights_8bit"] = compare(
        {**first, "logits": logits, "router_scores": scores})
    out["cache_8bit"] = compare(with_8bit_caches(model, first, pad_to))
    # the mechanism: the served path held to a reference that breaks it
    for breakage in ref.BREAKAGES + ROUTER_BREAKAGES:
        broken = Reference(config, breakage=breakage)
        forced = breakage in ROUTER_BREAKAGES       # else: its own keys
        logits, _, _, _ = broken.forward(model.params, ids, rows, selections,
                                         choices, force=forced)
        out[breakage] = compare_logits(first["logits"], logits,
                                       first["tokens"], limits)
    out["ok"] = all(r["correct"] for r in out["served"]) and not any(
        out[k]["correct"]
        for k in MUST_FAIL + ref.BREAKAGES + ROUTER_BREAKAGES)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config",
                    default="keye-vl-2.0-30b-a3b-expand-sd15-512")
    ap.add_argument("--seed", type=int, default=4200000011)
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
        ROOT, "chiprun_out", "verify_lm_dsa_moe", f"s{args.seed}"))
    os.makedirs(out_dir, exist_ok=True)
    scratch = tempfile.mkdtemp(prefix="verify-lm-dsa-moe-")
    try:
        paths, shared = serve_phase(args, config, scratch)
        cmd = [sys.executable, os.path.abspath(__file__), "--config",
               args.config, "--compare", *paths]
        child = subprocess.run(cmd + (["--rehearse"] if args.rehearse
                                      else []),
                               capture_output=True, text=True, cwd=ROOT)
        sys.stderr.write(child.stderr[-4000:])
        if child.returncode != 0:
            print(f"verify_lm_dsa_moe: the comparison failed to run "
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
            print(f"verify_lm_dsa_moe: {args.together} requests sent "
                  f"together did not run as one execution: {shared}",
                  file=sys.stderr)
            result["ok"] = False
    with open(os.path.join(out_dir, "verify_lm_dsa_moe.json"), "w",
              encoding="utf-8") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
