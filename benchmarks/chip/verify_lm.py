#!/usr/bin/env python3
"""The served language model against its plain reference, at the
published widths, on what the TIMED path produced.

  python3 benchmarks/chip/verify_lm.py [--config <name>] [--seed <n>]
      [--requests <k>] [--rehearse] [--out DIR]

Two phases, each in a process of its own (a chip belongs to one):

1. ``cli serve`` as a child, as the benchmark starts it (the mesh's
   variable and no other).  ``--requests`` requests (texts and seeds
   drawn from ``--seed`` as a run draws them) of the configuration's graph
   with ONE node more: ``SaveLanguageModelOutput`` behind the generate node's
   second output, which writes the ids and the float32 logits every
   decoded token was drawn from (``[64, 49152]`` for the cell's request).
   The server is then stopped.
2. ``--compare`` (a child too): the same seeded weights made again from
   the model's name, the reference (``reference/looplm.py``: float32,
   ``precision="highest"``, no cache) teacher-forced over the prompt's
   ids and the served ones, ONE jitted layer called ``R x L`` times so
   that the published size fits the chip, and the two sets of logits
   compared position by position.  Then two readings in the nearest
   precision below the stated bf16, each of which has to come out NOT
   correct: the reference with its weights rounded to 8 bits
   (``float8_e4m3fn``), and the program itself with its cache held in 8
   bits (run here, in this process, on the first request's prompt).

What is compared, over the decoded positions, against the limits below:

* ``max_over_std``: the largest absolute difference of a logit over
  the standard deviation of the reference's logits;
* ``mean_over_std``: the mean absolute difference over the same;
* ``margin``: for every chosen id, how far the reference's logit for it
  lies under the reference's largest.  Greedy decoding chose the served
  path's largest; the reference may order two near-equal logits the
  other way, by at most twice the logit tolerance.

Prints one JSON line, last; exit code 0 only if every served request is
inside every limit AND each 8-bit reading is outside at least one.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

# The limits, each between two readings at the published widths on the
# chip (PERF.md section 6, PR 26, call 3): what the served path gave over
# three requests, and what the nearest precision below the stated bf16
# gave.
#
# Why the served path differs at all: its matmul operands are bf16 (8
# bits of mantissa: a relative rounding of 2**-9 per operand) where the
# reference keeps float32 throughout; the cache holds bf16 keys and
# values; 192 layer applications add their roundings up in a float32
# residual stream that a final norm rescales after every loop.
#
#                          mean_over_std     max_over_std
#   served, 3 requests     0.00292-0.00305   0.0212-0.0257
#   cache in 8 bits        0.01316           0.0929     (float8_e4m3fn)
#   weights in 8 bits      0.1306            0.842
#
# Each limit is the geometric mean of the served path's largest reading
# and the 8-bit cache's: a factor of two from either.  (The seeded
# sandwich gains are 0.1: with gains of 1 the served path itself read
# 0.087-0.090 / 0.53-0.58, the 8-bit cache 0.093 / 0.62, and no limit
# could have told them apart.)
LIMITS = {"max_over_std": 0.049, "mean_over_std": 0.0063}
LIMITS["margin_over_std"] = 2.0 * LIMITS["max_over_std"]

# the same comparison for a float32 model (the CPU tests' tiny size):
# only the order of the additions differs
LIMITS_FP32 = {"max_over_std": 1e-4, "mean_over_std": 1e-5,
               "margin_over_std": 2e-4}


def compare_logits(served, reference, tokens, limits=None) -> dict:
    """The three readings, the limits, and ``correct``.  ``served`` and
    ``reference`` are ``[N, V]`` float32, ``tokens [N]`` the ids the
    served path chose."""
    import numpy as np
    limits = LIMITS if limits is None else limits
    served = np.asarray(served, np.float64)
    reference = np.asarray(reference, np.float64)
    tokens = np.asarray(tokens)
    std = float(reference.std())
    diff = np.abs(served - reference)
    chosen = reference[np.arange(len(tokens)), tokens]
    out = {"std": std,
           "max_over_std": float(diff.max()) / std,
           "mean_over_std": float(diff.mean()) / std,
           "margin_over_std": float((reference.max(axis=-1) - chosen).max())
           / std,
           "argmax_agree": float((reference.argmax(axis=-1) == tokens).mean()),
           "limits": dict(limits)}
    out["correct"] = bool(all(np.isfinite(out[k]) and out[k] <= v
                              for k, v in limits.items()))
    return out


# --- phase 2: the reference, layer by layer ----------------------------------

def reference_logits(config: dict, params, ids, weights_dtype=None):
    """``reference.forward`` with ONE jitted layer called ``R x L`` times
    (a layer's float32 weights exist only while it runs), so that the
    published size fits one chip.  ``weights_dtype`` rounds every weight
    through that type first (the lower-precision reading)."""
    import jax
    import jax.numpy as jnp
    from reference import looplm as ref

    def weight(w):
        if weights_dtype is not None:
            w = w.astype(weights_dtype)
        return ref.f32(w)

    @jax.jit
    def layer(layers, l, x):
        lp = {name: weight(jax.lax.dynamic_index_in_dim(
            leaf, l, keepdims=False)) for name, leaf in layers.items()}
        return ref.layer(config, lp, x)

    end_of_loop = jax.jit(functools.partial(ref.end_of_loop, config))
    outer = {k: jax.tree_util.tree_map(weight, v)
             for k, v in params.items() if k != "layers"}
    x = outer["embed_tokens"][jnp.asarray(ids)]
    exits = []
    for _ in range(config["total_ut_steps"]):
        for l in range(config["num_hidden_layers"]):
            x = layer(params["layers"], jnp.int32(l), x)
        x, p_exit = end_of_loop(outer, x)
        exits.append(p_exit)
    return jax.jit(ref.head)(outer, x), jnp.stack(exits, axis=-1)


def rows_of(served) -> tuple:
    """The teacher-forced ids of a served request and the rows of a full
    forward pass its tokens were drawn from."""
    import numpy as np
    n, new = len(served["prompt_ids"]), len(served["tokens"])
    return (np.concatenate([served["prompt_ids"], served["tokens"]]),
            slice(n - 1, n + new - 1))


def with_8bit_cache(model, served, pad_to: int) -> dict:
    """The program run here on a served request's prompt with its cache
    held in ``float8_e4m3fn``: its own greedy ids and logits."""
    import jax.numpy as jnp
    import numpy as np
    from comfyui_distributed_tpu.models import looplm
    real = looplm.empty_cache
    looplm.empty_cache = lambda *a: tuple(
        c.astype(jnp.float8_e4m3fn) for c in real(*a))
    try:
        ids = served["prompt_ids"]
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(ids)] = ids
        tokens, logits, _ = looplm.make_generate(
            model.cfg, len(served["tokens"]))(
            model.params, padded, np.int32(len(ids)), np.uint32(0),
            np.float32(0.0))
    finally:
        looplm.empty_cache = real
    return {"prompt_ids": ids, "tokens": np.asarray(tokens[0]),
            "logits": np.asarray(logits[0])}


def compare_phase(npz_paths: list, lm_config: dict, model_name: str,
                  pad_to: int, rehearse: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    if rehearse:
        os.environ["DTPU_DEFAULT_FAMILY"] = "tiny"
    sys.path.insert(0, ROOT)
    from comfyui_distributed_tpu.models import registry
    model = registry.load_language_model(model_name)
    config = dict(lm_config)
    if rehearse:
        import dataclasses
        config = {k: v for k, v in dataclasses.asdict(model.cfg).items()
                  if k != "dtype"}
    limits = LIMITS_FP32 if model.cfg.dtype == jnp.float32 else LIMITS
    out = {"device": {"platform": jax.devices()[0].platform,
                      "kind": jax.devices()[0].device_kind},
           "served": []}
    reference = None
    for path in npz_paths:
        served = np.load(path)
        ids, rows = rows_of(served)
        t0 = time.monotonic()
        logits, exits = reference_logits(config, model.params, ids)
        reading = compare_logits(served["logits"], np.asarray(logits)[rows],
                                 served["tokens"], limits)
        reading["exit_probs_max_diff"] = float(np.abs(
            np.asarray(exits)[rows] - served["exit_probs"]).max())
        reading["prompt_ids"] = int(len(served["prompt_ids"]))
        reading["positions"] = int(len(served["tokens"]))
        reading["reference_s"] = time.monotonic() - t0
        out["served"].append(reading)
        if reference is None:
            reference, first = np.asarray(logits)[rows], served
    # the nearest precision below the stated one: each has to fail
    ids, rows = rows_of(first)
    low, _ = reference_logits(config, model.params, ids,
                              weights_dtype=jnp.float8_e4m3fn)
    out["weights_8bit"] = compare_logits(np.asarray(low)[rows], reference,
                                         first["tokens"], limits)
    cached = with_8bit_cache(model, first, pad_to)
    ids, rows = rows_of(cached)
    logits, _ = reference_logits(config, model.params, ids)
    out["cache_8bit"] = compare_logits(cached["logits"],
                                       np.asarray(logits)[rows],
                                       cached["tokens"], limits)
    out["ok"] = all(r["correct"] for r in out["served"]) \
        and not out["weights_8bit"]["correct"] \
        and not out["cache_8bit"]["correct"]
    return out


# --- phase 1: the timed path, once more, with one node behind it -------------

def verify_graph(config: dict, text: str, seed: int, prefix: str) -> dict:
    """The configuration's graph with the save node behind the generate
    node's second output: the only difference from a timed request."""
    from lib.traffic import fill_graph
    graph = fill_graph(config, {"index": 0, "text": text, "seed": seed},
                       prefix)
    (gen,) = [nid for nid, node in graph.items()
              if node["class_type"] == "LanguageModelGenerate"]
    graph["verify_lm"] = {"class_type": "SaveLanguageModelOutput",
                          "inputs": {"lm_output": [gen, 1],
                                     "filename_prefix": prefix}}
    return graph


def serve_phase(args, config: dict, scratch: str) -> list:
    """Start the server, send the requests one after another, stop the
    server.  Returns the paths of the ``.npz`` files the save node
    wrote."""
    from lib.server import Server, check
    from lib.traffic import Traffic
    import run as bench
    mix = bench.load_json(os.path.join(HERE, "traffic",
                                       "closed2_unique.json"))
    traffic = Traffic(mix, config["name"], args.seed)
    server = Server(ROOT, os.path.join(scratch, "server"),
                    os.path.join(scratch, "server.log"),
                    bench.server_env(1, args.rehearse))
    try:
        status = server.wait_ready()
        check(status["platform"] == ("cpu" if args.rehearse else "tpu"),
              f"the server runs on {status['platform']!r}")
        http = server.http(timeout=1200.0)
        prefixes = [f"verify_s{args.seed}_{i}" for i in range(args.requests)]
        for prefix in prefixes:
            req = traffic.next_request()
            code, doc = http.post("/prompt", {
                "prompt": verify_graph(config, req["text"], req["seed"],
                                       prefix),
                "client_id": "verify_lm"})
            check(code == 200 and doc.get("prompt_id"),
                  f"POST /prompt answered {code}: {doc}")
            deadline = time.monotonic() + 1500.0
            while True:
                server.require_alive()
                entry = http.get("/history").get(doc["prompt_id"])
                if entry is not None:
                    break
                check(time.monotonic() < deadline,
                      "the request never finished")
                time.sleep(0.25)
            check(entry.get("status") == "success",
                  f"the request ended {entry}:\n{server.log_tail()}")
        http.close()
        rc = server.shut_down()
        check(rc == 0, f"server child exited with code {rc}")
    finally:
        server.kill()
    paths = [os.path.join(server.cwd, "output", f"{p}.npz")
             for p in prefixes]
    for path in paths:
        check(os.path.isfile(path), f"the save node wrote no {path}")
    return paths


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="ouro-2.6b-expand-sd15-512")
    ap.add_argument("--seed", type=int, default=2600000011)
    ap.add_argument("--requests", type=int, default=3)
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
        ROOT, "chiprun_out", "verify_lm", f"s{args.seed}"))
    os.makedirs(out_dir, exist_ok=True)
    import shutil
    import tempfile
    scratch = tempfile.mkdtemp(prefix="verify-lm-")
    try:
        cmd = [sys.executable, os.path.abspath(__file__), "--config",
               args.config, "--compare", *serve_phase(args, config, scratch)]
        child = subprocess.run(cmd + (["--rehearse"] if args.rehearse
                                      else []),
                               capture_output=True, text=True, cwd=ROOT)
        sys.stderr.write(child.stderr[-4000:])
        if child.returncode != 0:
            print(f"verify_lm: the comparison failed to run "
                  f"(exit {child.returncode})", file=sys.stderr)
            return 1
        result = json.loads(child.stdout.strip().splitlines()[-1])
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    with open(os.path.join(out_dir, "verify_lm.json"), "w",
              encoding="utf-8") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
