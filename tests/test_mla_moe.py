"""The latent-attention decoder with routed experts (models/mla_moe.py)
against its plain reference (benchmarks/chip/reference/mla_moe.py) on
seeded weights, at a tiny size: d 64, a dense block and two expert
blocks, 4 heads (16 nope + 8 rope, v 16), ranks 32 / 16, experts 4..7 of
16 held, top-4, V 512.  Logits, not tokens: with random weights the
largest logit changes on rounding.

Routing is discontinuous, so the comparison is verify_lm_moe.py's
threefold one, the one the chip run uses at the published widths: router
scores within a tolerance, choices that differ from the reference's only
where the reference's own cut is that close, logits against the
reference under the PROGRAM's choices.  Each breakage the issue names has
to fail it where the served path passes.
"""

import dataclasses
import functools
import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import mla_moe, registry
from comfyui_distributed_tpu.ops.pallas import fewrow_dense as fd
from comfyui_distributed_tpu.parallel import sharding as shd
from comfyui_distributed_tpu.utils import trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sys.path.insert(0, BENCH)
ref = _load("mla_moe_reference",
            os.path.join(BENCH, "reference", "mla_moe.py"))
verify = _load("chipbench_verify_lm_moe",
               os.path.join(BENCH, "verify_lm_moe.py"))

TINY = mla_moe.TINY_MLA_MOE
PROMPT, NEW, PAD_TO = 9, 6, 16

# The limits for the tiny model in bf16 (the chip's, for five blocks of
# width 7680, are verify_lm_moe.LIMITS).  Why the served path differs
# from the float32 reference at all: its matmul operands and its latent
# cache are bf16 (8 bits of mantissa), a decode step rounds the absorbed
# query and the weighted latent once more than the prefill, and three
# blocks of width 64 add their roundings up.  Measured here (two weight
# seeds, alone and as rows of four): logits mean 0.0029-0.0046, max
# 0.014-0.029 of a logit's standard deviation, router scores within
# 0.0017-0.0046.  With the cache in 8 bits (3 bits of mantissa): mean
# 0.014-0.020, max 0.079-0.139, scores off by 0.010-0.020.  Each limit is
# the geometric mean of the served path's largest reading and the 8-bit
# cache's smallest.
TINY_BF16_LIMITS = {"max_over_std": 0.048, "mean_over_std": 0.008,
                    "margin_over_std": 0.096}
TINY_BF16_ROUTER_TOLERANCE = 0.0068


def limits_of(dtype):
    if jnp.dtype(dtype) == jnp.float32:
        return verify.LIMITS_FP32, verify.ROUTER_TOLERANCE_FP32
    return TINY_BF16_LIMITS, TINY_BF16_ROUTER_TOLERANCE


def hf(cfg):
    """The config as the reference reads it (the configuration file's
    ``lm`` block)."""
    out = {k: v for k, v in dataclasses.asdict(cfg).items() if k != "dtype"}
    return {**out, "router_outputs": cfg.n_routed_experts,
            "dense_layers_held": cfg.first_k_dense_replace}


def held(cfg):
    return range(cfg.experts_first, cfg.experts_first + cfg.experts_held)


def prompt(seed=0, n=PROMPT):
    ids = np.zeros((1, PAD_TO), np.int32)
    ids[0, :n] = np.random.default_rng(seed).integers(3, TINY.vocab_size, n)
    return ids


def serve_rows(cfg, params, lens, new=NEW, ids=None, temperatures=None,
               seeds=None):
    """One execution over rows of the real lengths ``lens`` (row ``b``'s
    prompt is ``prompt(b, lens[b])``); per row what the save node would
    write, and the execution's ``stats``."""
    if ids is None:
        ids = np.concatenate([prompt(b, n) for b, n in enumerate(lens)])
    tokens, logits, aux, stats = mla_moe.make_program(cfg, new)(
        params, jnp.asarray(ids), np.asarray(lens, np.int32),
        np.asarray(seeds or [3] * len(lens), np.uint32),
        np.asarray(temperatures or [0.0] * len(lens), np.float32))
    rows = [{"prompt_ids": ids[b, :n], "tokens": np.asarray(tokens[b]),
             "logits": np.asarray(logits[b]),
             **{k: np.asarray(v[b]) for k, v in aux.items()}}
            for b, n in enumerate(lens)]
    return rows, {k: np.asarray(v) for k, v in stats.items()}


def serve(cfg, params, ids=None, n=PROMPT, **kw):
    rows, _ = serve_rows(cfg, params, [n],
                         ids=prompt() if ids is None else ids, **kw)
    return rows[0]


def reference_of(cfg, params, served):
    """``reference(choices)`` of verify_lm_moe.compare_served: the
    reference's full forward pass, teacher-forced over the prompt and the
    served ids, at the rows each served token was drawn from."""
    ids, rows = verify.rows_of(served)

    def reference(choices):
        # the last position's row is not read
        choices = np.concatenate([choices, choices[-1:]])
        logits, scores, _ = ref.forward(hf(cfg), params, ids, held(cfg),
                                        choices)
        return np.asarray(logits)[rows], np.asarray(scores)[rows]
    return reference


def compare(cfg, params, served, reference_params=None):
    limits, tolerance = limits_of(cfg.dtype)
    return verify.compare_served(
        served, reference_of(cfg, reference_params or params, served),
        limits, tolerance)


@pytest.fixture(scope="module")
def params():
    return mla_moe.seeded_params(TINY, np.uint32(7))


# --- the served path against the reference ---------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_through_the_latent_cache_match_the_reference(
        dtype):
    """One request alone.  float32: 1e-4 of a standard deviation (measured
    3e-6: only the order of the additions differs; no choice flips).
    bf16: TINY_BF16_LIMITS, their reasons beside them."""
    cfg = dataclasses.replace(TINY, dtype=jnp.dtype(dtype))
    p = mla_moe.seeded_params(cfg, np.uint32(7))
    assert {x.dtype for x in jax.tree_util.tree_leaves(p)} \
        == {jnp.dtype(dtype)}
    served = serve(cfg, p)
    assert served["logits"].dtype == np.float32
    assert served["router_scores"].shape == (NEW, 2, 16)
    assert served["expert_choices"].shape == (NEW, 2, 4)
    assert served["prompt_choices"].shape == (PAD_TO, 2, 4)
    got = compare(cfg, p, served)
    assert got["correct"], got
    if dtype == "float32":
        assert got["flipped"] == 0
    else:
        assert got["mean_over_std"] > 1e-4         # and bf16 is what ran
    print(f"{dtype}: {got['flipped']} of {got['choices']} choices flipped")


LENS = [9, 5, 16, 12]               # PAD_TO = 16: one row has no padding


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows, real", [(4, 4), (4, 3)])
def test_rows_of_different_lengths_match_the_reference_and_their_own_runs(
        rows, real, dtype):
    """Rows of different real lengths in one execution, all real or three
    real ones in a program of four (a padded row repeats the first): each
    row's ids are those of its single-row run, its logits those of the
    reference inside the limits, and the share of choices that flipped is
    said."""
    cfg = dataclasses.replace(TINY, dtype=jnp.dtype(dtype))
    p = mla_moe.seeded_params(cfg, np.uint32(7))
    source = [*range(real), *[0] * (rows - real)]
    lens = [LENS[i] for i in source]
    ids = np.concatenate([prompt(i, LENS[i]) for i in source])
    served, stats = serve_rows(cfg, p, lens, ids=ids)
    flipped = choices = 0
    for b in range(real):
        alone = serve(cfg, p, ids[b:b + 1], n=lens[b])
        assert np.array_equal(served[b]["tokens"], alone["tokens"]), b
        got = compare(cfg, p, served[b])
        assert got["correct"], (b, got)
        flipped, choices = flipped + got["flipped"], choices + got["choices"]
    print(f"{dtype}, {real} of {rows} rows: {flipped} of {choices} choices "
          f"({100.0 * flipped / choices:.2f}%) flipped against the "
          f"reference's")
    if dtype == "float32":
        assert flipped == 0
    # what the program counted: the padded row routes as the first does
    assert stats["expert_pairs_dropped"] == 0
    assert stats["expert_pairs_local"].shape == (rows,)
    if real < rows:
        assert stats["expert_pairs_local"][-1] == \
            stats["expert_pairs_local"][0]


def test_rows_do_not_read_each_other_and_padding_changes_no_real_row(params):
    lens = LENS[:3]
    base = np.concatenate([prompt(b, n) for b, n in enumerate(lens)])
    want, _ = serve_rows(TINY, params, lens + [lens[0]],
                         ids=np.concatenate([base, base[:1]]))
    noisy = np.concatenate([base, prompt(9, 16)])
    for b, real in enumerate(lens):
        noisy[b, real:] = 77 + b
    got, _ = serve_rows(TINY, params, lens + [16], ids=noisy)
    for b in range(3):
        assert np.array_equal(got[b]["tokens"], want[b]["tokens"])
        np.testing.assert_allclose(got[b]["logits"], want[b]["logits"],
                                   atol=1e-5)


def test_sampling_follows_the_seed_and_greedy_ignores_it(params):
    greedy = serve(TINY, params, seeds=[1])["tokens"]
    assert np.array_equal(greedy, serve(TINY, params, seeds=[2])["tokens"])
    a = serve(TINY, params, temperatures=[1.0], seeds=[1])["tokens"]
    b = serve(TINY, params, temperatures=[1.0], seeds=[2])["tokens"]
    assert not np.array_equal(a, b) and not np.array_equal(a, greedy)


def test_the_absorbed_path_is_the_expanded_one(params):
    """Two paths for one layer: the prompt through `_stack` with its
    queries expanded against this call's latent (the prefill) and
    absorbed against the cache (what a decode step runs) give the same
    state, float32 to the order of the additions."""
    x = mla_moe._embed(params, jnp.asarray(prompt()[:, :PROMPT]))
    first = jnp.zeros(1, jnp.int32)
    out = [mla_moe._stack(TINY, params, x, jnp.arange(PROMPT), first,
                          mla_moe.empty_cache(TINY, 1, PROMPT + 3),
                          absorbed=how) for how in (False, True)]
    np.testing.assert_allclose(out[0][0], out[1][0], atol=2e-5)
    np.testing.assert_allclose(out[0][1], out[1][1], atol=2e-5)    # the cache


# --- the share is tied to the model ------------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(params):
    """For one expert layer: the routed parts that all 16 / 4 shares give
    (the program's `_routed`, each share told which experts it holds),
    plus the shared expert counted once, are the uncut layer of the
    reference (every expert held, a loop over all 16)."""
    rng = np.random.default_rng(5)
    n = jnp.asarray(rng.standard_normal((2, 7, TINY.hidden_size)),
                    jnp.float32)
    whole = dataclasses.replace(TINY, experts_first=0, experts_held=16)
    full = mla_moe.seeded_params(whole, np.uint32(11))["moe_layers"]
    lp = jax.tree_util.tree_map(lambda w: w[1], full)         # block 1
    x = n.reshape(-1, TINY.hidden_size)
    scores, chosen, weights = mla_moe.route(TINY, lp["gate"], x)
    total = mla_moe._gated_mlp(TINY, lp["shared_experts"], x)
    pairs = 0
    for first in range(0, 16, 4):
        share = dataclasses.replace(TINY, experts_first=first)
        experts = {k: w[:, first:first + 4]
                   for k, w in full["experts"].items()}
        part, local, hits, dropped, _ = mla_moe._routed(
            share, experts, jnp.int32(1), x, chosen, weights)
        total, pairs = total + part, pairs + int(local.sum())
        assert int(dropped) == 0 and 0 <= int(hits) <= 4
    # every pair was somebody's
    assert pairs == x.shape[0] * TINY.num_experts_per_tok
    ref_lp = jax.tree_util.tree_map(ref.f32, lp)
    ref_scores, ref_chosen = ref.router(hf(whole), ref_lp["gate"], x)
    want = ref.gated_mlp(ref_lp["shared_experts"], x) + ref.routed(
        hf(whole), ref_lp["experts"], range(16), x, ref_scores, ref_chosen)
    assert np.array_equal(np.sort(chosen, -1), np.sort(ref_chosen, -1))
    np.testing.assert_allclose(total, want, atol=2e-5)
    # and one share alone is NOT the layer
    assert float(jnp.abs(total - part).max()) > 1e-2


# --- an expert multiplies its own tokens, in tiles ------------------------------

def every_expert_over_all_tokens(cfg, experts, l, x, chosen, weights):
    """What `_routed` has to give, the plain way: every held expert over
    ALL tokens, times each token's weight for it (zero where the token
    did not choose it).  -> the sum and the pairs of each held expert."""
    onehot = (chosen - cfg.experts_first)[..., None] \
        == jnp.arange(cfg.experts_held)
    combine = jnp.sum(jnp.where(onehot, weights[..., None], 0.0), axis=1)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(cfg.experts_held):
        own = {name: w[l, e] for name, w in experts.items()}
        y = y + combine[:, e:e + 1] * mla_moe._gated_mlp(cfg, own, x)
    return y, np.asarray(onehot.sum(axis=(0, 1))), \
        np.asarray(onehot.sum(axis=(1, 2)))


def choices(tokens, takers, seed=3):
    """``chosen [tokens, 4]`` in which held expert 4 + ``e`` is chosen by
    exactly the tokens ``takers[e]``; every other choice goes to an absent
    expert (0..3, 8..15), a token's four all distinct."""
    rng = np.random.default_rng(seed)
    absent = [e for e in range(16) if not 4 <= e < 8]
    rows = []
    for t in range(tokens):
        mine = [4 + e for e, who in takers.items() if t in who]
        fill = rng.permutation(absent)[:4 - len(mine)]
        rows.append(rng.permutation(np.concatenate([mine, fill])))
    return jnp.asarray(np.stack(rows), jnp.int32)


TILE = 4
# case: (tokens, {held expert: the tokens that choose it})
TILINGS = {
    "an expert nobody chose": (11, {0: range(0, 11, 2), 1: [3, 7], 2: [10]}),
    "more pairs than one tile": (11, {0: range(9), 1: [0], 2: [5], 3: [6]}),
    "exactly a tile": (11, {0: [1, 4, 6, 9], 1: range(8), 3: [2]}),
    "every pair to an absent expert": (11, {}),
    "one pair in all": (11, {2: [10]}),
    "no more tokens than a tile": (TILE, {0: [0, 3], 2: range(4)}),
    "fewer tokens than a tile": (3, {1: [2], 3: range(3)}),
    "two tiles' tokens, not gathered": (2 * TILE, {0: range(8), 3: [7]}),
    "one more token than two tiles": (2 * TILE + 1, {0: range(9), 3: [7]}),
}


@pytest.mark.parametrize("case", TILINGS)
def test_an_expert_multiplies_its_own_tokens_in_tiles(case, monkeypatch):
    """The tiled `_routed` (a small tile patched in) against every expert
    over all tokens, weighted: the same sum to float32's rounding (only
    the order of an output row's terms differs), the same pairs and hits,
    none dropped, and as many rows multiplied as the tiles hold."""
    monkeypatch.setattr(mla_moe, "EXPERT_TILE", TILE)
    tokens, takers = TILINGS[case]
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((tokens, TINY.hidden_size)),
                    jnp.float32)
    chosen = choices(tokens, takers)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, chosen.shape), jnp.float32)
    experts = mla_moe.seeded_params(TINY, np.uint32(11))["moe_layers"][
        "experts"]
    want, count, want_pairs = every_expert_over_all_tokens(
        TINY, experts, 1, x, chosen, weights)
    assert list(count) == [len(takers.get(e, ())) for e in range(4)]
    y, pairs, hits, dropped, rows = jax.jit(
        lambda *a: mla_moe._routed(TINY, experts, jnp.int32(1), *a))(
            x, chosen, weights)
    np.testing.assert_allclose(y, want, atol=2e-5)
    assert np.array_equal(pairs, want_pairs)
    assert int(hits) == int((count > 0).sum()) and int(dropped) == 0
    tile = TILE if tokens > 2 * TILE else tokens
    assert int(rows) == sum(-(-int(c) // tile) * tile for c in count)
    if not takers:
        assert int(rows) == 0 and not np.asarray(y).any()
    else:
        assert float(jnp.abs(want).max()) > 1e-2


# --- a few rows on a TPU: the hit experts through the grouped kernel (PR 44) ------

def _family(name):
    """A family's tiny configuration (all three share `_routed`, which
    reads the fields it needs under one name in each)."""
    from comfyui_distributed_tpu.models import dsa_moe, swa_moe
    return {"pangu": mla_moe, "exaone": swa_moe,
            "keye": dsa_moe}[name].CONFIGS["tiny"]


def _routing(cfg, router_width, tokens, seed, takers=None):
    """``x``, ``chosen`` and ``weights`` of a call of ``tokens`` tokens:
    each token's distinct choices among the router's experts (the first
    token's first the last expert held), or (with ``takers``) none to an
    expert held here."""
    rng = np.random.default_rng(seed)
    last = cfg.experts_first + cfg.experts_held - 1
    absent = [e for e in range(router_width) if not
              cfg.experts_first <= e <= last]
    chosen = np.stack([
        rng.permutation(absent if takers == "nobody" else router_width)[
            :cfg.num_experts_per_tok] for _ in range(tokens)])
    if takers is None and last not in chosen[0]:
        chosen[0, 0] = last
    return (jnp.asarray(rng.standard_normal((tokens, 128)), jnp.float32),
            jnp.asarray(chosen, jnp.int32),
            jnp.asarray(rng.uniform(0.1, 1.0, chosen.shape), jnp.float32))


@pytest.mark.parametrize("tokens", [2, 4, 8])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("family", ["pangu", "exaone", "keye"])
def test_the_grouped_path_gives_the_loops_sum_and_its_four_counts(
        family, dtype, tokens, monkeypatch):
    """`_routed` of a few rows with the rule read as a TPU's (the kernel in
    the Pallas interpreter, small blocks so that K is walked) against the
    loop of conditionals on the same operands: the same sum to float32's
    rounding (the order of a product's sum over K alone differs), the
    same pairs a token, hits, none dropped, rows multiplied; and
    ``dense_paths`` names the path its three call sites took."""
    cfg = _family(family)
    cfg = dataclasses.replace(cfg, dtype=jnp.dtype(dtype))
    router_width = getattr(cfg, "n_routed_experts", None) or cfg.num_experts
    rng = np.random.default_rng(5)
    experts = {name: jnp.asarray(
        rng.standard_normal((2, cfg.experts_held, *shape)) / 12, cfg.dtype)
        for name, shape in (("gate_proj", (128, 256)), ("up_proj", (128, 256)),
                            ("down_proj", (256, 128)))}
    x, chosen, weights = _routing(cfg, router_width, tokens, seed=tokens)

    def routed():
        return jax.jit(lambda *a: mla_moe._routed(
            cfg, experts, jnp.int32(1), *a))(x, chosen, weights)

    want = routed()
    assert int(want[2]) > 0 and float(jnp.abs(want[0]).max()) > 1e-2
    monkeypatch.setattr(mla_moe, "routed_path", lambda *a, **kw: "grouped")
    monkeypatch.setattr(mla_moe, "fewrow_grouped", functools.partial(
        fd.fewrow_grouped, interpret=True, blocks=(128, 128)))
    before = trace.DENSE_PATHS.snapshot().get("fewrow_grouped_few", 0)
    got = routed()
    assert trace.DENSE_PATHS.snapshot()["fewrow_grouped_few"] == before + 3
    np.testing.assert_allclose(
        got[0], want[0], rtol=0,
        atol=2e-5 if dtype == "float32" else 1e-2 * float(
            jnp.abs(want[0]).max()))
    for a, b in zip(got[1:], want[1:]):
        assert np.array_equal(a, b)
    assert int(got[3]) == 0 and int(got[4]) == int(got[2]) * tokens


@pytest.mark.parametrize("family", ["pangu", "exaone"])
def test_a_few_rows_that_hit_no_expert_held_launch_nothing(family,
                                                           monkeypatch):
    """Every pair to an absent expert: both kernel calls lie in the hit
    branch of ONE conditional, which is not taken; the sum is zero and the
    four counts are."""
    cfg = _family(family)
    monkeypatch.setattr(mla_moe, "routed_path", lambda *a, **kw: "grouped")
    monkeypatch.setattr(mla_moe, "fewrow_grouped", functools.partial(
        fd.fewrow_grouped, interpret=True))
    experts = {name: jnp.ones((2, cfg.experts_held, *shape), cfg.dtype)
               for name, shape in (("gate_proj", (128, 256)),
                                   ("up_proj", (128, 256)),
                                   ("down_proj", (256, 128)))}
    x, chosen, weights = _routing(
        cfg, getattr(cfg, "n_routed_experts", None) or cfg.num_experts, 4,
        seed=1, takers="nobody")

    def routed(*a):
        return mla_moe._routed(cfg, experts, jnp.int32(0), *a)

    jaxpr = jax.make_jaxpr(routed)(x, chosen, weights).jaxpr
    eqns = list(_equations(jaxpr))
    # (a kernel's own ``pl.when`` is a conditional inside its call)
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1 and not any(
        e.primitive.name == "while" for e in eqns)
    inside = [e.primitive.name for e in _equations(
        conds[0].params["branches"][1].jaxpr)]
    assert inside.count("pallas_call") == 2 \
        == sum(e.primitive.name == "pallas_call" for e in eqns)
    y, pairs, hits, dropped, rows = jax.jit(routed)(x, chosen, weights)
    assert not np.asarray(y).any() and not np.asarray(pairs).any()
    assert (int(hits), int(dropped), int(rows)) == (0, 0, 0)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    from jax._src import core
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("tokens, tiled", [(1, False), (4, False),
                                           (TILE * 8, False),
                                           (TILE * 16, False),
                                           (TILE * 16 + 1, True),
                                           (TILE * 64, True)])
def test_a_call_of_no_more_tokens_than_two_tiles_is_not_sorted_or_gathered(
        tokens, tiled, monkeypatch):
    """The rule over ``t`` and the tile, on the traced `_routed`: a decode
    step (1 or 4 tokens) and a prefill of no more tokens than two tiles hold
    no sort, gather or scatter and multiply ``[t, d]`` by an expert, as
    before there were tiles; a longer call multiplies ``[tile, d]`` by an
    expert and never ``[t, d]``."""
    tile = TILE * 8
    monkeypatch.setattr(mla_moe, "EXPERT_TILE", tile)
    spec = jax.ShapeDtypeStruct
    experts = {name: spec((2, 4, *shape[2:]), jnp.float32) for name, shape
               in mla_moe.param_shapes(TINY)["moe_layers"]["experts"].items()}
    jaxpr = jax.make_jaxpr(
        lambda *a: mla_moe._routed(TINY, a[0], jnp.int32(1), *a[1:]))(
            experts, spec((tokens, TINY.hidden_size), jnp.float32),
            spec((tokens, 4), jnp.int32), spec((tokens, 4), jnp.float32))
    eqns = list(_equations(jaxpr.jaxpr))
    moved = {e.primitive.name for e in eqns} \
        & {"sort", "gather", "scatter", "scatter-add", "scatter_add"}
    products = {e.invars[0].aval.shape for e in eqns
                if e.primitive.name == "dot_general"}
    width = TINY.moe_intermediate_size
    if tiled:
        assert moved == {"sort", "gather", "scatter-add"}
        assert products == {(tile, TINY.hidden_size), (tile, width)}
    else:
        assert not moved
        assert products == {(tokens, TINY.hidden_size), (tokens, width)}


# --- each breakage fails the comparison -------------------------------------

def _fp8(tree):
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype), tree)


def _break(name, monkeypatch, cfg, params):
    """The served path with one thing wrong; returns (cfg, params)."""
    if name == "W_UK left out of the absorbed query":
        real = mla_moe._kv_b

        def no_w_uk(cfg, lp):
            w = real(cfg, lp)
            eye = jnp.eye(cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                          dtype=w.dtype)[:, None]
            return w.at[..., :cfg.qk_nope_head_dim].set(eye)
        # in the absorbed path alone (the expanded one reads its keys
        # through `_kv_b` too)
        absorbed = mla_moe._attend_absorbed

        def attend(cfg, lp, *a):
            mla_moe._kv_b = no_w_uk
            try:
                return absorbed(cfg, lp, *a)
            finally:
                mla_moe._kv_b = real
        monkeypatch.setattr(mla_moe, "_attend_absorbed", attend)
    elif name == "k_r not shared across heads":
        # each head reads a rotary key of its own: head h the shared one
        # rolled by h
        real = mla_moe.xla_attention

        def per_head(q, k, v, *a):
            dr = cfg.qk_rope_head_dim
            if k.shape[2] > 1:      # the expanded path: [B, N, H, dn + dr]
                rolled = jnp.stack([jnp.roll(k[:, :, h, -dr:], h, axis=-1)
                                    for h in range(k.shape[2])], axis=2)
                k = k.at[..., -dr:].set(rolled)
            return real(q, k, v, *a)
        monkeypatch.setattr(mla_moe, "xla_attention", per_head)
    elif name == "a sandwich norm left out":
        monkeypatch.setattr(mla_moe, "_sandwich",
                            lambda x, update, gain, eps: x + update)
    elif name == "top-k weights not renormalised":
        return dataclasses.replace(cfg, norm_topk_prob=False), params
    elif name == "top-k weights not scaled":
        return dataclasses.replace(cfg, routed_scaling_factor=1.0), params
    elif name == "a local pair dropped":
        # a capacity of one pair an expert: the second token routed to
        # it is dropped
        real = mla_moe.route

        def capped(cfg, gate, n):
            scores, chosen, weights = real(cfg, gate, n)
            flat = chosen.reshape(-1)
            seen = jnp.cumsum(jax.nn.one_hot(flat, cfg.n_routed_experts),
                              axis=0)
            nth = jnp.take_along_axis(seen, flat[:, None], axis=1)[:, 0]
            return scores, chosen, jnp.where(
                nth.reshape(chosen.shape) > 1, 0.0, weights)
        monkeypatch.setattr(mla_moe, "route", capped)
    elif name == "pairs to absent experts given to a local expert":
        real = mla_moe.route

        def folded(cfg, gate, n):
            scores, chosen, weights = real(cfg, gate, n)
            return scores, cfg.experts_first \
                + chosen % cfg.experts_held, weights
        monkeypatch.setattr(mla_moe, "route", folded)
    elif name == "the cache in 8 bits":
        real = mla_moe.empty_cache
        monkeypatch.setattr(
            mla_moe, "empty_cache",
            lambda *a: real(*a).astype(jnp.float8_e4m3fn))
    elif name == "the weights in 8 bits":
        return cfg, _fp8(params)
    return cfg, params


BREAKAGES = ["W_UK left out of the absorbed query",
             "k_r not shared across heads", "a sandwich norm left out",
             "top-k weights not renormalised", "top-k weights not scaled",
             "a local pair dropped",
             "pairs to absent experts given to a local expert",
             "the cache in 8 bits", "the weights in 8 bits"]


@pytest.mark.parametrize("rows", [1, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("what", BREAKAGES)
def test_each_breakage_fails_the_comparison(what, dtype, rows, monkeypatch):
    """Alone and as a row of a 4-row execution, in float32 and in the
    stated bf16: the served path passes and the broken one fails at
    least one reading of the threefold comparison."""
    cfg = dataclasses.replace(TINY, dtype=jnp.dtype(dtype))
    p = mla_moe.seeded_params(cfg, np.uint32(7))
    lens = LENS[:rows]
    good, _ = serve_rows(cfg, p, lens)
    assert all(compare(cfg, p, row)["correct"] for row in good)
    broken_cfg, broken_p = _break(what, monkeypatch, cfg, p)
    broken, stats = serve_rows(broken_cfg, broken_p, lens)
    monkeypatch.undo()
    readings = [compare(cfg, p, row) for row in broken]
    assert not any(r["correct"] for r in readings), (what, readings)


# --- the latent cache ---------------------------------------------------------

def test_the_cache_holds_the_latent_and_the_gauge_says_so():
    """576 values a position a layer at the published widths (c_kv 512 +
    k_r 64), never the 128 heads' keys and values."""
    full = mla_moe.OPENPANGU_ULTRA_MOE_SHARE
    assert full.latent_dim == 576
    cache = jax.eval_shape(lambda: mla_moe.empty_cache(full, 4, 128))
    assert cache.shape == (5, 4, 128, 576) and cache.dtype == jnp.bfloat16
    assert mla_moe.kv_cache_bytes(full, 4, 128) == 5 * 4 * 128 * 576 * 2 \
        == math.prod(cache.shape) * 2
    # a cache of expanded heads would be 128 x (192 + 128) values: 71 x
    expanded = full.num_attention_heads * (
        full.qk_nope_head_dim + full.qk_rope_head_dim + full.v_head_dim)
    assert expanded == 40960 and mla_moe.kv_cache_bytes(full, 1, 1) \
        == 5 * 576 * 2 < 5 * expanded * 2
    tiny = mla_moe.empty_cache(TINY, 2, 12)
    assert tiny.shape == (3, 2, 12, 16 + 8)
    assert mla_moe.kv_cache_bytes(TINY, 2, 12) == tiny.nbytes


def test_the_published_share_is_the_issues_arithmetic():
    full = mla_moe.OPENPANGU_ULTRA_MOE_SHARE
    mla = 7680 * 1536 + 1536 * 128 * 192 + 7680 * 576 + 512 * 128 * 256 \
        + 128 * 128 * 7680
    norms = 4 * 7680 + 1536 + 512
    expert = 3 * 7680 * 2048
    dense = mla + norms + 3 * 7680 * 18432
    moe = mla + norms + 7680 * 256 + expert + 16 * expert
    assert mla_moe.param_count(full) == dense + 4 * moe \
        + 2 * 19200 * 7680 + 7680
    assert round(mla_moe.param_count(full) / 1e9, 2) == 4.92    # 9.84 GB
    assert full.layer_applications == 5 and full.moe_layers == 4
    assert (full.experts_first, full.experts_held) == (48, 16)
    with pytest.raises(ValueError, match="not among the router's"):
        dataclasses.replace(full, experts_first=250)


# --- through the registry: counters, gauge, families ---------------------------

def counters():
    return dict(trace.GLOBAL_COUNTERS.snapshot())


def test_the_registry_serves_it_and_counts_its_routing(monkeypatch):
    """`load_language_model` by name -> `generate_rows`: the ``lm.*``
    counters of PR 28-31 keep their meaning (layer applications = tokens
    x blocks held), the routing counters come over in the same read, and
    the gauge is the latent cache's."""
    monkeypatch.setenv("DTPU_DEFAULT_FAMILY", "tiny")
    model = registry.load_language_model("openpangu-ultra-moe-718b.safetensors")
    assert model.family == "pangu" and model.cfg == TINY
    assert model.row_counts == (1, 4)
    before = counters()
    rows = [registry.LMRow(f"a lighthouse at dawn number {i}", i)
            for i in range(3)]
    out = model.generate_rows(rows, max_new_tokens=5, prompt_tokens=32)
    after = counters()
    got = {k: after.get(k, 0) - before.get(k, 0) for k in after
           if k.startswith("lm.")}
    assert got["lm.executions"] == 1 and got["lm.rows"] == 3
    assert got["lm.padded_rows"] == 1 and got["lm.tokens_decoded"] == 15
    assert got["lm.layer_applications"] == 15 * 3          # 3 blocks held
    assert got["lm.expert_pairs"] == 3 * 5 * 2 * 4         # rows x steps x Le x k
    assert 0 < got["lm.expert_pairs_local"] < got["lm.expert_pairs"]
    assert 0 < got["lm.expert_hits"] <= 5 * 2 * 4
    assert got["lm.expert_pairs_dropped"] == 0
    # the prefill's pairs over the 4 x 32 positions of the program, and
    # the rows its experts multiplied for them: one tile (the whole
    # buffer) a hit expert and block
    assert 0 < got["lm.expert_pairs_local_prefill"] < 4 * 32 * 2 * 4
    assert got["lm.expert_rows_computed_prefill"] % (4 * 32) == 0
    assert got["lm.expert_pairs_local_prefill"] \
        <= got["lm.expert_rows_computed_prefill"] <= 2 * 4 * 4 * 32
    assert trace.GLOBAL_GAUGES.snapshot()["lm.kv_cache_bytes"] == \
        mla_moe.kv_cache_bytes(TINY, 4, 32 + 5) == 3 * 4 * 37 * 24 * 4
    words, lm_out = out[2]
    assert lm_out.row == 2 and set(lm_out.aux) == {
        "router_scores", "expert_choices", "prompt_choices"}
    assert lm_out.aux["router_scores"].shape == (4, 5, 2, 16)
    assert len(words.split()) <= 5


@pytest.mark.parametrize("name, want", [
    ("ouro-2.6b.safetensors", ("ouro", "full")),
    ("openpangu-ultra-moe-718b.safetensors", ("pangu", "full")),
    ("openPangu-tiny.safetensors", ("pangu", "tiny")),
    ("tiny-refusals", ("ouro", "tiny")),
    ("another-test-lm.safetensors", ("ouro", "tiny")),
])
def test_a_model_name_names_its_family(name, want, monkeypatch):
    monkeypatch.delenv("DTPU_DEFAULT_FAMILY", raising=False)
    assert registry.detect_lm_family(name) == want
    monkeypatch.setenv("DTPU_DEFAULT_FAMILY", "tiny")
    assert registry.detect_lm_family(name) == (want[0], "tiny")


def test_an_unknown_model_name_is_refused_with_the_families_it_knows(
        monkeypatch):
    """Every name used to be Ouro."""
    monkeypatch.delenv("DTPU_DEFAULT_FAMILY", raising=False)
    with pytest.raises(ValueError) as e:
        registry.load_language_model("llama-3-8b.safetensors")
    assert "ouro" in str(e.value) and "pangu" in str(e.value)
    assert "llama-3-8b.safetensors" in str(e.value)
    assert not any(k.startswith("lm:llama") for k in registry._pipeline_cache)


def test_the_registry_names_no_model_file_outside_its_family_table():
    with open(registry.__file__) as f:
        source = f.read()
    start = source.index("LM_FAMILIES = {")
    table = source[start:source.index("\n}\n", start)]
    rest = source.replace(table, "")
    for module in ("looplm", "mla_moe"):
        assert f'"{module}"' in table
        assert f"import {module}" not in rest
        assert f"{module}." not in rest.replace(f"models/{module}.py", "")


# --- the expert axis ------------------------------------------------------------

def test_expert_leaves_are_never_column_split():
    """``[L, E_here, in, out]`` is not a batch of kernels to the
    shape-driven rule: the expert axis is the one it may be split by, and
    no mesh the repo runs splits it."""
    assert shd.LOGICAL_AXIS_RULES["expert"] is None
    path = "['moe_layers']['experts']['gate_proj']"
    assert shd.param_spec(path, (4, 16, 7680, 2048), 4) \
        == shd.mesh_spec(None, None, None, None)
    # the shared expert and every other kernel still are
    assert shd.param_spec("['moe_layers']['shared_experts']['gate_proj']",
                          (4, 7680, 2048), 4) \
        == shd.mesh_spec(None, None, "tensor")
    assert shd.logical_spec(None, "expert", None, None) \
        == shd.mesh_spec(None, None, None, None)
    x = jnp.zeros((2, 4, 8, 8))
    assert shd.constrain(x, None, "expert", None, None) is x


# --- names in a compiled program -------------------------------------------------

def test_every_class_of_the_expert_model_is_in_its_compiled_program(params):
    import re
    text = mla_moe.make_program(TINY, 2).lower(
        params, jnp.zeros((1, 8), jnp.int32), np.int32(5), np.uint32(0),
        np.float32(0.0)).compile().as_text()
    names = re.findall(r'op_name="([^"]+)"', text)
    classes = {trace.classify(n) for n in names if "PanguUltraMoE" in n}
    assert classes >= {"lm_proj", "lm_attn", "lm_cache", "lm_mlp",
                       "lm_experts", "lm_norm", "lm_head", "embed"}
    assert trace.OTHER not in classes


@pytest.mark.parametrize("path, want", [
    ("moe_layers/while/body/self_attn/q_a_proj/dot_general", "lm_proj"),
    ("moe_layers/while/body/self_attn/q_a_layernorm/mul", "lm_norm"),
    ("dense_layers/while/body/self_attn/kv_a_proj_with_mqa/dot_general",
     "lm_proj"),
    ("moe_layers/while/body/self_attn/absorb_q/dot_general", "lm_proj"),
    ("moe_layers/while/body/self_attn/absorb_v/dot_general", "lm_proj"),
    ("moe_layers/while/body/self_attn/kv_cache/dynamic_update_slice",
     "lm_cache"),
    ("moe_layers/while/body/self_attn/rotary/cos", "lm_attn"),
    ("moe_layers/while/body/self_attn/bnhd,bmhd->bhnm/dot_general",
     "lm_attn"),
    ("moe_layers/while/body/mlp/gate/dot_general", "lm_experts"),
    ("moe_layers/while/body/mlp/gate/top_k", "lm_experts"),
    ("moe_layers/while/body/mlp/dispatch/eq", "lm_experts"),
    ("moe_layers/while/body/mlp/experts/while/body/cond/branch_1_fun/"
     "dot_general", "lm_experts"),
    ("moe_layers/while/body/mlp/combine/add", "lm_experts"),
    ("moe_layers/while/body/mlp/shared_experts/up_proj/dot_general",
     "lm_mlp"),
    ("dense_layers/while/body/mlp/down_proj/dot_general", "lm_mlp"),
    ("moe_layers/while/body/post_mlp_layernorm/rsqrt", "lm_norm"),
    ("final_norm/mul", "lm_norm"), ("lm_head/dot_general", "lm_head"),
    ("sample/argmax", "lm_head"), ("embed_tokens/gather", "embed"),
    ("moe_layers/while/body/add", "lm_proj"),
    # the few-row kernel's custom calls (PR 33)
    ("while/body/closed_call/moe_layers/while/body/closed_call/self_attn/"
     "q_a_proj/fewrow_dense/pallas_call", "lm_proj"),
    ("while/body/closed_call/moe_layers/while/body/closed_call/self_attn/"
     "q_b_proj/fewrow_dense/pallas_call", "lm_proj"),
    ("while/body/closed_call/dense_layers/while/body/closed_call/self_attn/"
     "o_proj/fewrow_dense/pallas_call", "lm_proj"),
    ("while/body/closed_call/moe_layers/while/body/closed_call/mlp/"
     "shared_experts/gate_proj/fewrow_dense_gate_proj_up_proj/pallas_call",
     "lm_mlp"),
    ("while/body/closed_call/dense_layers/while/body/closed_call/mlp/"
     "down_proj/fewrow_dense/pallas_call", "lm_mlp"),
    ("while/body/closed_call/lm_head/fewrow_dense/pallas_call", "lm_head"),
    # a prefill's tiles (PR 35): the pairs' sort, a tile's gather, its
    # products and its scatter-add, as the compiled program names them
    ("moe_layers/while/body/closed_call/mlp/dispatch/sort", "lm_experts"),
    ("moe_layers/while/body/closed_call/mlp/dispatch/cumsum", "lm_experts"),
    ("moe_layers/while/body/closed_call/mlp/experts/while/body/closed_call/"
     "cond/branch_1_fun/while/body/gather", "lm_experts"),
    ("moe_layers/while/body/closed_call/mlp/experts/while/body/closed_call/"
     "cond/branch_1_fun/while/body/dot_general", "lm_experts"),
    ("moe_layers/while/body/closed_call/mlp/experts/while/body/closed_call/"
     "cond/branch_1_fun/while/body/scatter-add", "lm_experts"),
    ("moe_layers/while/body/closed_call/mlp/experts/while/body/closed_call/"
     "cond/branch_1_fun/while/body/row_scatter_add/pallas_call",
     "lm_experts"),
])
def test_the_expert_models_scopes_fall_in_their_classes(path, want):
    assert trace.classify("jit(lm_generate)/PanguUltraMoE/" + path) == want
