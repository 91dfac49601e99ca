#!/usr/bin/env python3
"""The served expert language model against its plain reference, at the
published widths, on what the TIMED path produced.

  python3 benchmarks/chip/verify_lm_moe.py [--config <name>] [--seed <n>]
      [--requests <k>] [--together <m>] [--rehearse] [--out DIR]

``verify_lm.py`` for a model with routed experts
(``reference/mla_moe.py``), with its two phases, each in a process of
its own (a chip belongs to one):

1. ``cli serve`` as a child, as the benchmark starts it.  ``--requests``
   requests of the configuration's graph one after another, each ALONE in
   its execution, then (``--together``) one plain request that holds the
   executor with ``m`` expander requests of different text lengths
   queued behind it: the first of them leads the others through ONE
   execution of ``lm_generate`` as its rows.  Every expander graph has
   ONE node more, ``SaveLanguageModelOutput``: the ids, the float32
   logits each was drawn from, and what the routers of the expert blocks
   scored and chose there.  The server is then stopped.
2. ``--compare`` (a child too): the same seeded weights made again from
   the model's name, and the reference teacher-forced over the prompt's
   ids and the served ones, block by block and EXPERT BY EXPERT under
   ``jax.jit`` (one expert's float32 weights exist at a time: the
   published share is 9.8 GB of bf16 on a 16 GB chip).

Routing is discontinuous: a rounding flips an 8th-against-9th choice and
every logit behind it jumps.  So the comparison is threefold, per
request:

* ``scores_max_diff``: the served routers' scores against the
  reference's, over every decoded position, expert block and expert,
  within ROUTER_TOLERANCE (the reference's scores of a block are taken
  under the program's choices in the blocks and positions before it: a
  flip upstream moves everything downstream);
* ``unexcused_flips``: where the served top-k is not the reference's,
  every expert one side chose and the other did not must lie, by the
  REFERENCE's scores, within twice that tolerance of the reference's
  own cut between its k-th and (k+1)-th (both sides may be off by one
  tolerance).  ``flipped_share`` says what share of (position, block)
  choices differed at all;
* the logits (``verify_lm.compare_logits``: ``max_over_std``,
  ``mean_over_std``, ``margin_over_std``) against the reference run
  UNDER THE PROGRAM'S CHOICES, so that a flip the second reading excused
  is not counted again as a difference of every logit behind it.

Then two readings in the nearest precision below the stated bf16, each
of which has to come out NOT correct: the reference with its weights
rounded to 8 bits (``float8_e4m3fn``), and the program itself with its
latent cache held in 8 bits (run here, on the first request's prompt).

Prints one JSON line, last; exit code 0 only if every served request is
inside every limit AND each 8-bit reading is outside at least one.
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

from verify_lm import (LIMITS_FP32, compare_logits, rows_of,  # noqa: E402
                       verify_graph)

# The limits at the published widths on the chip, each between two
# readings (PERF.md section 6, PR 32, calls 1 and 2): what the served path
# gave over ten requests, two alone and eight as the rows of two 4-row
# executions, and what the nearest precision below the stated bf16 gave.
#
# Why the served path differs at all: its matmul operands are bf16 (a
# relative rounding of 2**-9 per operand) where the reference keeps
# float32; the cache holds a bf16 latent; a decode step runs the
# attention ABSORBED, which rounds ``q_nope W_UK^T`` and the weighted
# latent once more than the prefill does; five blocks add their roundings
# up in a float32 residual stream.  The router itself is float32 at the
# highest precision in both, so its scores differ only by what the blocks
# before it rounded.
#
#                      mean_over_std    max_over_std   router scores
#   served, 10 requests 0.00268-0.00397 0.0186-0.0283  0.0030-0.0053
#   cache in 8 bits     0.0190          0.130          0.0245  (float8_e4m3fn)
#   weights in 8 bits   0.0986          0.720          0.120
#
# (2.3-5.5% of the 256 choices of a request flipped against the
# reference's, every one where the reference's own cut was that close.)
# Each limit is the geometric mean of the served path's largest reading
# and the 8-bit cache's: a factor of two or more from either.  The seeded
# sandwich gains are 0.5 (models/mla_moe.py): five blocks do not amplify
# bf16's rounding as Ouro's 192 layer applications did, and the 8-bit
# cache stands 4.6-4.9 x clear of the served path in all three readings.
LIMITS = {"max_over_std": 0.06, "mean_over_std": 0.0087}
LIMITS["margin_over_std"] = 2.0 * LIMITS["max_over_std"]
ROUTER_TOLERANCE = 0.011

# the same comparison for a float32 model (the CPU tests' tiny size):
# only the order of the additions differs
ROUTER_TOLERANCE_FP32 = 1e-5


def compare_routing(scores, choices, ref_scores, tolerance) -> dict:
    """The first two readings.  ``scores [N, Le, E]`` and ``choices
    [N, Le, k]`` are the served routers', ``ref_scores`` the
    reference's."""
    import numpy as np
    scores = np.asarray(scores, np.float64)
    ref_scores = np.asarray(ref_scores, np.float64)
    choices = np.asarray(choices)
    k = choices.shape[-1]
    ranked = -np.sort(-ref_scores, axis=-1)
    kth, nxt = ranked[..., k - 1:k], ranked[..., k:k + 1]
    ref_chose = ref_scores >= kth
    served_chose = np.zeros(ref_scores.shape, bool)
    np.put_along_axis(served_chose, choices, True, axis=-1)
    # an expert only the program chose scores, by the reference, at least
    # its k-th less 2 tolerances; one only the reference chose at most
    # its (k+1)-th plus 2
    unexcused = (served_chose & ~ref_chose
                 & (ref_scores < kth - 2 * tolerance)) \
        | (ref_chose & ~served_chose & (ref_scores > nxt + 2 * tolerance))
    flipped = (served_chose != ref_chose).any(axis=-1)
    out = {"scores_max_diff": float(np.abs(scores - ref_scores).max()),
           "router_tolerance": tolerance,
           "flipped_share": float(flipped.mean()),
           "flipped": int(flipped.sum()), "choices": int(flipped.size),
           "unexcused_flips": int(unexcused.any(axis=-1).sum()),
           "smallest_margin": float((kth - nxt).min())}
    out["correct"] = bool(out["scores_max_diff"] <= tolerance
                          and out["unexcused_flips"] == 0)
    return out


def program_choices(served):
    """What the program chose at every position the reference's rows
    depend on: over the prompt (but for its last id, whose choices are
    the first decoded position's) and at every decoded position,
    ``[prompt + new - 1, Le, k]``."""
    import numpy as np
    n = len(served["prompt_ids"])
    prompt = served["prompt_choices"]
    return np.concatenate([prompt[len(prompt) - n:len(prompt) - 1],
                           served["expert_choices"]])


def compare_served(served, reference, limits=None, tolerance=None) -> dict:
    """The threefold comparison of one served request.  ``served`` maps
    ``tokens``, ``logits``, ``router_scores``, ``expert_choices``,
    ``prompt_choices`` (the save node's file); ``reference(choices)``
    gives the reference's logits and router scores over the same rows
    with the given choices forced.  ONE pass under the program's choices
    gives all three readings: a block's router scores there are the
    reference's own for the state the program's earlier choices led to,
    so an excused flip in one block is not counted again as a difference
    of the scores of the blocks behind it."""
    limits = LIMITS if limits is None else limits
    tolerance = ROUTER_TOLERANCE if tolerance is None else tolerance
    logits, scores = reference(program_choices(served))
    routing = compare_routing(served["router_scores"],
                              served["expert_choices"], scores, tolerance)
    under = compare_logits(served["logits"], logits, served["tokens"], limits)
    return {**routing, **under, "routing_correct": routing["correct"],
            "logits_correct": under["correct"],
            "correct": routing["correct"] and under["correct"]}


# --- phase 2: the reference, block by block, expert by expert ----------------

def reference_rows(config: dict, params, ids, rows, experts_held,
                   choices=None, weights_dtype=None):
    """``reference.forward`` over ``ids`` with one block's attention, one
    expert, or the head under ``jax.jit`` at a time; returns the logits
    and the router scores of ``rows``.  ``choices [T - 1, Le, k]``
    (`program_choices`) are forced at the positions in front of the last
    (whose row nothing reads).  ``weights_dtype`` rounds every weight through that type first."""
    import jax
    import jax.numpy as jnp
    from reference import mla_moe as ref

    def weight(w):
        if weights_dtype is not None:
            w = w.astype(weights_dtype)
        return ref.f32(w)

    def leaves(stack, l):
        return {name: weight(jax.lax.dynamic_index_in_dim(
            leaf, l, keepdims=False)) for name, leaf in stack.items()
            if not isinstance(leaf, dict)}

    attend = jax.jit(lambda stack, l, x: ref.attend(
        config, leaves(stack, l), x))
    mlp_input = jax.jit(lambda stack, l, h: ref.mlp_input(
        config, leaves(stack, l), h))
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
        h = attend(stack, li, x)
        n, *routing = mlp_input(stack, li, h)
        if at < 0:
            m = mlp(stack, li, n)
        else:
            scores, chosen = routing
            if choices is not None:
                chosen = chosen.at[:len(choices)].set(
                    jnp.asarray(choices)[:, at])
            m = mlp(stack["shared_experts"], li, n)
            for slot, e in enumerate(experts_held):
                m = m + one_expert(stack["experts"], li, jnp.int32(slot),
                                   jnp.int32(e), n, scores, chosen)
            all_scores.append(scores[rows])
        x = finish(stack, li, h, m)
    logits = jax.jit(lambda p, x: ref.head(config, p, x))(
        {"norm": weight(params["norm"]),
         "lm_head": weight(params["lm_head"])}, x[rows])
    return logits, jnp.stack(all_scores, axis=1)


def with_8bit_cache(model, served, pad_to: int) -> dict:
    """The program run here on a served request's prompt with its latent
    cache held in ``float8_e4m3fn``."""
    import jax.numpy as jnp
    import numpy as np
    from comfyui_distributed_tpu.models import mla_moe
    real = mla_moe.empty_cache
    mla_moe.empty_cache = lambda *a: real(*a).astype(jnp.float8_e4m3fn)
    try:
        ids = served["prompt_ids"]
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(ids)] = ids
        tokens, logits, aux, _ = mla_moe.make_program(
            model.cfg, len(served["tokens"]))(
            model.params, padded, np.int32(len(ids)), np.uint32(0),
            np.float32(0.0))
    finally:
        mla_moe.empty_cache = real
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
        config.update(router_outputs=cfg.n_routed_experts,
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
           "experts_held": [held.start, held.stop], "served": []}
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
    # the nearest precision below the stated one: each has to fail.  The
    # weights: the reference itself in 8 bits against the reference, both
    # under the program's choices (what a program with such weights
    # would give, its routing flips excused as the served path's are)
    first = dict(np.load(npz_paths[0]))
    full = reference_of(first)
    low = reference_of(first, weights_dtype=jnp.float8_e4m3fn)
    logits, scores = low(program_choices(first))
    out["weights_8bit"] = compare_served(
        {**first, "logits": logits, "router_scores": scores}, full, limits,
        tolerance)
    cached = with_8bit_cache(model, first, pad_to)
    out["cache_8bit"] = compare_served(cached, reference_of(cached), limits,
                                       tolerance)
    out["ok"] = all(r["correct"] for r in out["served"]) \
        and not out["weights_8bit"]["correct"] \
        and not out["cache_8bit"]["correct"]
    return out


# --- phase 1: the timed path, once more, with one node behind it -------------

def holder_graph(graph: dict) -> dict:
    """The expander's graph without the language model: a plain request
    that holds the executor while others queue behind it."""
    (gen,) = [nid for nid, node in graph.items()
              if node["class_type"] == "LanguageModelGenerate"]
    holder = {nid: json.loads(json.dumps(node))
              for nid, node in graph.items()
              if not node["class_type"].startswith(("LanguageModel",
                                                    "SaveLanguageModel"))}
    for node in holder.values():
        for name, value in node["inputs"].items():
            if value == [gen, 0]:
                node["inputs"][name] = "a plain request that holds the queue"
    return holder


def serve_phase(args, config: dict, scratch: str) -> tuple:
    """Start the server; ``--requests`` requests one after another, then
    ``--together`` behind a holder; stop the server.  Returns the paths
    of the ``.npz`` files and the ``lm.*`` counters of the shared
    execution."""
    from lib.server import Server, check
    from lib.traffic import Traffic
    import run as bench
    mix = bench.load_json(os.path.join(HERE, "traffic",
                                       "closed2_unique.json"))
    traffic = Traffic(mix, config["name"], args.seed)
    server = Server(ROOT, os.path.join(scratch, "server"),
                    os.path.join(scratch, "server.log"),
                    bench.server_env(1, args.rehearse))

    def finish(http, pids):
        deadline = time.monotonic() + 1500.0
        while True:
            server.require_alive()
            hist = http.get("/history")
            if all(p in hist for p in pids):
                break
            check(time.monotonic() < deadline, "a request never finished")
            time.sleep(0.25)
        check(all(hist[p].get("status") == "success" for p in pids),
              f"a request ended {[hist[p] for p in pids]}:\n"
              f"{server.log_tail()}")

    def post(http, graph):
        code, doc = http.post("/prompt", {"prompt": graph,
                                          "client_id": "verify_lm_moe"})
        check(code == 200 and doc.get("prompt_id"),
              f"POST /prompt answered {code}: {doc}")
        return doc["prompt_id"]

    shared = None
    try:
        status = server.wait_ready()
        check(status["platform"] == ("cpu" if args.rehearse else "tpu"),
              f"the server runs on {status['platform']!r}")
        http = server.http(timeout=1200.0)
        prefixes = [f"alone_s{args.seed}_{i}" for i in range(args.requests)]
        for prefix in prefixes:
            req = traffic.next_request()
            finish(http, [post(http, verify_graph(
                config, req["text"], req["seed"], prefix))])
        if args.together:
            before = http.get("/distributed/metrics")["pipeline"]["counters"]
            graphs = []
            for i in range(args.together):
                # texts of 6, 9, 12, 15 words: rows of different lengths
                req = Traffic({"loop": "closed", "text_words": 6 + 3 * i},
                              config["name"], args.seed + 1 + i
                              ).next_request()
                prefixes.append(f"together_s{args.seed}_{i}")
                graphs.append(verify_graph(config, req["text"], req["seed"],
                                           prefixes[-1]))
            finish(http, [post(http, g)
                          for g in [holder_graph(graphs[0])] + graphs])
            after = http.get("/distributed/metrics")["pipeline"]["counters"]
            shared = {k: after.get(f"lm.{k}", 0) - before.get(f"lm.{k}", 0)
                      for k in ("executions", "rows", "padded_rows",
                                "followers_served", "followers_dropped",
                                "expert_pairs", "expert_pairs_local",
                                "expert_hits", "expert_pairs_dropped")}
        http.close()
        rc = server.shut_down()
        check(rc == 0, f"server child exited with code {rc}")
    finally:
        server.kill()
    paths = [os.path.join(server.cwd, "output", f"{p}.npz")
             for p in prefixes]
    for path in paths:
        check(os.path.isfile(path), f"the save node wrote no {path}")
    return paths, shared


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="pangu-ultra-moe-expand-sd15-512")
    ap.add_argument("--seed", type=int, default=3200000011)
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
        ROOT, "chiprun_out", "verify_lm_moe", f"s{args.seed}"))
    os.makedirs(out_dir, exist_ok=True)
    scratch = tempfile.mkdtemp(prefix="verify-lm-moe-")
    try:
        paths, shared = serve_phase(args, config, scratch)
        cmd = [sys.executable, os.path.abspath(__file__), "--config",
               args.config, "--compare", *paths]
        child = subprocess.run(cmd + (["--rehearse"] if args.rehearse
                                      else []),
                               capture_output=True, text=True, cwd=ROOT)
        sys.stderr.write(child.stderr[-4000:])
        if child.returncode != 0:
            print(f"verify_lm_moe: the comparison failed to run "
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
            print(f"verify_lm_moe: {args.together} requests sent together "
                  f"did not run as one execution: {shared}", file=sys.stderr)
            result["ok"] = False
    with open(os.path.join(out_dir, "verify_lm_moe.json"), "w",
              encoding="utf-8") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
