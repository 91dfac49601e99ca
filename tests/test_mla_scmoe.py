"""The latent-attention decoder whose layer is two attentions and two dense
MLPs around a shortcut-connected expert layer with zero-compute experts
(models/mla_scmoe.py: two latent cache slots a layer, the MLA functions and
the routed experts of models/mla_moe.py) against its plain reference
(benchmarks/chip/reference/mla_scmoe.py) on seeded weights, at a tiny
size: d 64, two layers (four attentions), 4 heads of 16 + 8 / 16, ranks 32
and 16, experts 4..7 of 16 held beside 8 zero experts, top-4, V 512.
Prompts of 17 to 24 ids behind a buffer of 24 and 8 decoded tokens.

The comparison is verify_lm_mla_scmoe.py's fourfold one (what the routers
selected by, choices excused only where the reference's own cut is that
close, logits under the PROGRAM's choices, the weights of the chosen
pairs), the one the chip run uses at the published widths.  Each wrong
program the issue names has to fail it where the served path passes.
One tiny program a shape, compiled once and shared (`served_rows`).
"""

import dataclasses
import functools
import hashlib
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import mla_moe, mla_scmoe, registry
from comfyui_distributed_tpu.utils import trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sys.path.insert(0, BENCH)
ref = _load("mla_scmoe_reference",
            os.path.join(BENCH, "reference", "mla_scmoe.py"))
verify = _load("chipbench_verify_lm_mla_scmoe",
               os.path.join(BENCH, "verify_lm_mla_scmoe.py"))

TINY = mla_scmoe.TINY_MLA_SCMOE
NEW, PAD_TO = 8, 24
LENS = [19, 24, 17, 21]             # PAD_TO = 24: one row has no padding

# The limits for the tiny model in bf16 (the chip's, at width 6144, are
# verify_lm_mla_scmoe.LIMITS).  Measured here over two weight seeds, rows
# alone and four together: the served path's logits mean 0.0030-0.0041,
# max 0.016-0.032 of a logit's standard deviation, what the routers
# selected by within 0.0008-0.0017.  The LOWEST readings of the five
# wrong programs: 0.064, 0.43, 0.011.  Each limit is the geometric mean
# of the two: four times from either.  (The fourth reading, a program's
# weights against its own unbiased scores, is float32's rounding in both.)
TINY_BF16_LIMITS = {"max_over_std": 0.117, "mean_over_std": 0.016,
                    "margin_over_std": 0.234}
TINY_BF16_ROUTER_TOLERANCE = 0.0043


def limits_of(dtype):
    if jnp.dtype(dtype) == jnp.float32:
        return verify.LIMITS_FP32, verify.ROUTER_TOLERANCE_FP32
    return TINY_BF16_LIMITS, TINY_BF16_ROUTER_TOLERANCE


def hf(cfg):
    """The config as the reference reads it (the configuration file's
    ``lm`` block)."""
    return verify.reference_config(cfg, {}, rehearse=True)


def held(cfg):
    return range(cfg.experts_first, cfg.experts_first + cfg.experts_held)


def prompt(seed=0, n=LENS[0]):
    ids = np.zeros((1, PAD_TO), np.int32)
    ids[0, :n] = np.random.default_rng(seed).integers(3, TINY.vocab_size, n)
    return ids


@functools.lru_cache(maxsize=None)
def of_dtype(dtype):
    cfg = dataclasses.replace(TINY, dtype=jnp.dtype(dtype))
    return cfg, mla_scmoe.seeded_params(cfg, np.uint32(7))


def serve(cfg, params, lens, new=NEW):
    """One execution over rows of the real lengths ``lens`` (row ``b``'s
    prompt is ``prompt(b, lens[b])``); per row what the save node would
    write, and the execution's ``stats``."""
    ids = np.concatenate([prompt(b, n) for b, n in enumerate(lens)])
    tokens, logits, aux, stats = mla_scmoe.make_program(cfg, new)(
        params, jnp.asarray(ids), np.asarray(lens, np.int32),
        np.asarray([3] * len(lens), np.uint32),
        np.asarray([0.0] * len(lens), np.float32))
    rows = [{"prompt_ids": ids[b, :n], "tokens": np.asarray(tokens[b]),
             "logits": np.asarray(logits[b]),
             **{k: np.asarray(v[b]) for k, v in aux.items()}}
            for b, n in enumerate(lens)]
    return rows, {k: np.asarray(v) for k, v in stats.items()}


@functools.lru_cache(maxsize=None)
def served_rows(dtype, rows):
    """The shared executions: one compile a (dtype, rows)."""
    return serve(*of_dtype(dtype), LENS[:rows])


def compare(cfg, params, served, **kw):
    """The fourfold comparison of one served row, the reference's full
    forward pass teacher-forced over the prompt and the served ids."""
    limits, tolerance = limits_of(cfg.dtype)
    ids, at = verify.rows_of(served)

    def reference(choices):
        # the last position's row is not read
        choices = np.concatenate([choices, choices[-1:]])
        logits, scores, _, weights = ref.forward(hf(cfg), params, ids,
                                                 held(cfg), choices, **kw)
        return tuple(np.asarray(a)[at] for a in (logits, scores, weights))
    return verify.compare_all(
        served, reference, params["router"]["e_score_correction_bias"],
        cfg.routed_scaling_factor, limits, tolerance)


# --- the served path against the reference ---------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows", [1, 4])
def test_prefill_then_decode_through_the_cache_slots_match_the_reference(
        rows, dtype):
    """Alone and as the rows of one execution, prompts of unequal length
    in one buffer: the prefill expanded, 8 steps absorbed on four cache
    slots, against the reference's full forward pass: logits, what the
    routers selected by, choices, weights.  float32: 1e-4 of a standard
    deviation (measured 3e-7: only the order of the additions differs; no
    choice flips).  bf16: TINY_BF16_LIMITS, their reasons beside them."""
    cfg, p = of_dtype(dtype)
    assert {x.dtype for x in jax.tree_util.tree_leaves(p)} \
        == {jnp.dtype(dtype)}
    served, stats = served_rows(dtype, rows)
    assert served[0]["logits"].dtype == np.float32
    assert served[0]["router_scores"].shape == (NEW, 2, 24)
    assert served[0]["expert_choices"].shape == (NEW, 2, 4) \
        == served[0]["expert_weights"].shape
    assert served[0]["prompt_choices"].shape == (PAD_TO, 2, 4)
    for b, row in enumerate(served):
        got = compare(cfg, p, row)
        assert got["correct"], (b, got)
        assert dtype == "bfloat16" or got["flipped"] == 0
        if dtype == "bfloat16":
            assert got["mean_over_std"] > 1e-4     # and bf16 is what ran
    lens = np.asarray(LENS[:rows])
    # four attentions: a step's query sees the row's real prompt and what
    # it has decoded; the prefill's the triangle of its real ids
    assert list(stats["keys_attended"]) == [
        4 * sum(n + i + 1 for i in range(NEW)) for n in lens]
    assert list(stats["keys_attended_prefill"]) == list(
        4 * lens * (lens + 1) // 2)
    assert stats["prefill_positions"] == rows * PAD_TO
    # k a token a layer: to experts held here, to zero experts, or absent
    for phase, tokens in (("", NEW), ("_prefill", PAD_TO)):
        local, zero = (stats[f"expert_pairs_{kind}{phase}"]
                       for kind in ("local", "zero"))
        assert local.shape == zero.shape == (rows,)
        assert (local + zero <= tokens * 2 * 4).all() and (zero > 0).all()
    assert stats["expert_pairs_dropped"] == 0
    assert 0 <= stats["expert_hits"] <= NEW * 2 * 4
    # what the routers weighted: 6 p of the chosen, never renormalised
    weights = np.stack([row["expert_weights"] for row in served])
    assert (weights > 0).all() and weights.sum(-1).std() > 1e-3


def test_a_row_of_a_shared_execution_is_its_single_row_run():
    cfg, p = of_dtype("float32")
    together, _ = served_rows("float32", 4)
    alone, _ = served_rows("float32", 1)
    assert np.array_equal(together[0]["tokens"], alone[0]["tokens"])
    np.testing.assert_allclose(together[0]["logits"], alone[0]["logits"],
                               atol=1e-5)


def test_the_prefill_in_row_groups_is_the_prefill_at_once(monkeypatch):
    """A prefill too large for one pass takes its rows through the blocks
    in groups (`rows_a_pass`): each row's result is what it is at once."""
    assert mla_scmoe.rows_a_pass(4, 2048) == 2      # the cell's execution
    assert mla_scmoe.rows_a_pass(1, 2048) == mla_scmoe.rows_a_pass(1, 8192) \
        == mla_scmoe.rows_a_pass(4, 4096) == 1
    assert mla_scmoe.rows_a_pass(4, 64) == 4
    cfg, p = of_dtype("float32")
    at_once, stats = served_rows("float32", 4)
    monkeypatch.setattr(mla_scmoe, "PREFILL_POSITIONS", 2 * PAD_TO)
    grouped, grouped_stats = serve(cfg, p, LENS)
    for a, b in zip(at_once, grouped):
        assert np.array_equal(a["tokens"], b["tokens"])
        assert np.array_equal(a["prompt_choices"], b["prompt_choices"])
        np.testing.assert_allclose(a["logits"], b["logits"], atol=2e-5)
    for name, value in stats.items():
        if name != "expert_rows_computed_prefill":  # tiles are a group's
            assert np.array_equal(value, grouped_stats[name]), name


def test_absorbed_equals_expanded_with_both_scales():
    """The two ways through one attention, over the same positions: the
    latent expanded to every head's keys and values, and the queries
    absorbed onto the (scaled) latent as a decode step runs them."""
    cfg, p = of_dtype("float32")
    assert cfg.q_scale == pytest.approx(2 ** 0.5) and cfg.kv_scale == 2.0
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, cfg.hidden_size))
    first, index = jnp.asarray([0, 3]), jnp.arange(9)
    out = {absorbed: mla_scmoe._stack(
        cfg, p, x, index, first, mla_scmoe.empty_cache(cfg, 2, 9), absorbed)
        for absorbed in (False, True)}
    np.testing.assert_allclose(out[False][0][1, 3:], out[True][0][1, 3:],
                               atol=2e-5)
    np.testing.assert_allclose(out[False][1], out[True][1], atol=1e-5)
    # and the scales are in both: without them the state is another
    plain = dataclasses.replace(cfg, mla_scale_q_lora=False,
                                mla_scale_kv_lora=False)
    other = mla_scmoe._stack(plain, p, x, index, first,
                             mla_scmoe.empty_cache(cfg, 2, 9), True)
    assert float(jnp.abs(other[0] - out[True][0]).max()) > 0.1
    # the cache holds the SCALED latent, the rotary key unscaled
    assert float(jnp.abs(other[1][0, ..., :16] * 2.0
                         - out[True][1][0, ..., :16]).max()) < 1e-5
    np.testing.assert_allclose(other[1][0, ..., 16:],
                               out[True][1][0, ..., 16:], atol=1e-6)


# --- each wrong program fails the comparison ----------------------------------

def test_the_verify_script_refuses_every_departure_the_reference_names():
    assert set(verify.REFUSED) == set(ref.WRONG)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("wrong", ref.WRONG)
def test_each_wrong_program_fails_the_comparison(wrong, dtype):
    """The served path held to the reference with ONE departure (the
    block's wiring: the expert layer fed from the second norm, or added
    before the second attention; the zero experts left out; the weights
    from ``p + b``; the latent's scale left off) fails at least one
    reading, for a row alone and for rows of a 4-row execution, in
    float32 and in the stated bf16; the same rows pass against the
    reference as written (the test above)."""
    cfg, p = of_dtype(dtype)
    rows = [served_rows(dtype, 1)[0][0], *served_rows(dtype, 4)[0][1:3]]
    readings = [compare(cfg, p, row, wrong=wrong) for row in rows]
    assert not any(r["correct"] for r in readings), (wrong, readings)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_weights_taken_from_the_biased_scores_fail_the_fourth_reading(dtype):
    """What the chip comparison rests on where the logits cannot tell
    (6 b x u is below bf16's rounding at 1 / 768): a program's weights are
    6 x its own unbiased scores to float32's rounding, and weights from
    ``p + b`` are off by 6 |b|."""
    cfg, p = of_dtype(dtype)
    bias = np.asarray(p["router"]["e_score_correction_bias"], np.float32)
    row = served_rows(dtype, 4)[0][2]
    got = verify.compare_weights(row, bias, 6.0, verify.WEIGHT_TOLERANCE)
    assert got["weights_correct"] and got["weights_max_diff"] < 1e-6
    chosen_bias = np.take_along_axis(
        np.broadcast_to(bias, row["router_scores"].shape),
        row["expert_choices"], axis=-1)
    biased = {**row, "expert_weights": row["expert_weights"]
              + 6.0 * chosen_bias}
    got = verify.compare_weights(biased, bias, 6.0, verify.WEIGHT_TOLERANCE)
    assert not got["weights_correct"] and got["weights_max_diff"] > 0.05


def test_weights_in_8_bits_fail_the_comparison():
    cfg, p = of_dtype("bfloat16")
    low = jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype), p)
    served, _ = serve(cfg, low, LENS[:1])
    assert not compare(cfg, p, served[0])["correct"]


# --- the router ----------------------------------------------------------------

def test_selection_goes_by_the_biased_scores_and_weights_by_the_unbiased():
    cfg, p = of_dtype("float32")
    x = jax.random.normal(jax.random.PRNGKey(1), (40, cfg.hidden_size))
    gate = p["router"]["classifier"][0]
    bias = p["router"]["e_score_correction_bias"][0]
    scores, chosen, weights = mla_moe.route(cfg, gate, x, bias)
    assert scores.shape == (40, 24) and chosen.shape == weights.shape \
        == (40, 4)
    np.testing.assert_allclose(scores.sum(-1), 1.0, atol=1e-5)  # a softmax
    assert np.array_equal(np.sort(chosen, -1), np.sort(
        jax.lax.top_k(scores + bias, 4)[1], -1))
    np.testing.assert_allclose(
        weights, 6.0 * jnp.take_along_axis(scores, chosen, -1), rtol=1e-6)
    # the bias is of the order of a score: it moves choices, never a weight
    plain = mla_moe.route(cfg, gate, x)
    assert (np.sort(plain[1], -1) != np.sort(chosen, -1)).any()
    assert 0.2 < float(jnp.std(bias)) * 24 < 3.0
    # and the weights are not renormalised: a token's sum is its own
    assert float(weights.sum(-1).std()) > 0.01
    ref_p, ref_by, ref_chosen = ref.router(
        hf(cfg), {k: ref.f32(v[0]) for k, v in p["router"].items()}, x)
    np.testing.assert_allclose(ref_by, scores + bias, atol=1e-6)
    assert np.array_equal(np.sort(ref_chosen, -1), np.sort(chosen, -1))


def _layer(p, l):
    return (mla_scmoe.layer_of(p["router"], l), p["experts"])


def test_a_token_whose_choices_are_all_zero_experts_runs_no_expert():
    """Its part is (the sum of its weights) x its own input, no expert is
    hit, and the program's steps count no hit."""
    cfg, p = of_dtype("float32")
    bias = jnp.where(jnp.arange(24) >= 16, 10.0, 0.0)
    zeroed = {**p, "router": {
        **p["router"], "e_score_correction_bias":
            jnp.broadcast_to(bias, (2, 24)).astype(jnp.float32)}}
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 5, cfg.hidden_size))
    rp, experts = _layer(zeroed, 1)
    m, ((by, chosen, weights), (local, zero, hits, dropped, rows)) = \
        mla_scmoe._moe(cfg, rp, experts, jnp.int32(1), u)
    assert (chosen >= 16).all() and int(hits) == int(rows) == 0
    assert list(local) == [0] and list(zero) == [5 * 4]
    np.testing.assert_allclose(m, weights.sum(-1, keepdims=True) * u,
                               rtol=1e-6)
    served, stats = serve(cfg, zeroed, LENS[:1], new=3)
    assert stats["expert_hits"] == 0 and stats["expert_pairs_local"][0] == 0
    assert stats["expert_pairs_zero"][0] == 3 * 2 * 4
    assert stats["expert_pairs_zero_prefill"][0] == PAD_TO * 2 * 4
    assert stats["expert_rows_computed_prefill"] == 0
    assert compare(cfg, zeroed, served[0])["correct"]


def test_the_shares_add_up_to_the_uncut_layer_with_the_zero_part_once():
    """For one expert layer: the routed parts that all 4 shares give (the
    program's `_moe`, each share told which 4 of the 16 experts it holds),
    with the zero experts' part, which EVERY share computes for its own
    tokens, counted once, are the uncut layer of the reference (every
    expert held, a loop over all 16, and the zero part)."""
    whole = dataclasses.replace(TINY, experts_first=0, experts_held=16)
    full = mla_scmoe.seeded_params(whole, np.uint32(11))
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 14, TINY.hidden_size))
    rp = mla_scmoe.layer_of(full["router"], 1)
    parts, zero_part, pairs = [], None, 0
    for first in range(0, 16, 4):
        share = dataclasses.replace(TINY, experts_first=first, experts_held=4)
        experts = {k: w[:, first:first + 4]
                   for k, w in full["experts"].items()}
        m, ((_, chosen, weights), (local, zero, hits, dropped, _)) = \
            mla_scmoe._moe(share, rp, experts, jnp.int32(1), u)
        own_zero = jnp.sum(jnp.where(chosen >= 16, weights, 0.0), -1,
                           keepdims=True) * u
        parts.append(m - own_zero)                  # the share's real experts
        zero_part = own_zero                        # the same on every share
        pairs += int(local.sum())
        assert int(dropped) == 0 and 0 <= int(hits) <= 4
    total = sum(parts) + zero_part
    # every pair was one share's, or a zero expert's
    assert pairs + int(zero.sum()) == 14 * TINY.moe_topk and int(zero.sum())
    lp = ref.layer_params(full, 1)
    want, (_, ref_chosen, _) = ref.moe(hf(whole), lp["router"],
                                       lp["experts"], range(16), u[0])
    assert np.array_equal(np.sort(chosen[0], -1), np.sort(ref_chosen, -1))
    np.testing.assert_allclose(total[0], want, atol=2e-5)
    # one share alone is NOT the layer, nor is the sum without the zero
    # part, nor with it counted four times
    assert float(jnp.abs(total - m).max()) > 1e-2
    assert float(jnp.abs(zero_part).max()) > 1e-2


def test_the_shared_functions_are_mla_moes_not_copies():
    for name in ("_routed", "_self_attn", "_gated_mlp", "route"):
        assert getattr(mla_scmoe, name) is getattr(mla_moe, name)
    with open(mla_scmoe.__file__) as f:
        source = f.read()
    assert not re.search(
        r"^def (route|_routed|_queries|_latent|_attend_\w+|_gated_mlp)\b",
        source, re.M)


# openPangu's lowered program (StableHLO without locations) at the tiny
# size, one row and four: the text `jax.jit(generate).lower(...).as_text()`
# gave on the PARENT of PR 49 (a2e06f7).  The shared functions' new
# arguments (two scales, a selection bias) default to adding no operation.
PANGU_LOWERED = {1: "3c5b044e54df3729", 4: "1b0d6dd0b95faaf4"}


@pytest.mark.parametrize("rows", [1, 4])
def test_openpangus_lowered_program_is_what_it_was(rows):
    cfg = mla_moe.TINY_MLA_MOE
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, cfg.dtype),
        mla_moe.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))

    def lm_generate(params, ids, lens, seed, temp):
        return mla_moe.generate(cfg, 4, params, ids, lens, seed, temp)

    def row(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    text = jax.jit(lm_generate).lower(
        params, row(np.int32, rows, 32), row(np.int32, rows),
        row(np.uint32, rows), row(np.float32, rows)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PANGU_LOWERED[rows]


# --- the counts, the cache -----------------------------------------------------

def test_the_published_share_is_the_issues_arithmetic():
    full = mla_scmoe.LONGCAT_FLASH_OMNI_SHARE
    mla = 6144 * 1536 + 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 \
        + 512 * 64 * 256 + 8192 * 6144
    dense, expert = 3 * 6144 * 12288, 3 * 6144 * 2048
    outside = 2 * mla + 2 * dense + 6144 * 768 + 768 + 4 * 6144
    assert (mla, dense, expert, outside) == (
        90_572_800, 226_492_416, 37_748_736, 638_874_368)
    assert mla_scmoe.param_count(full) == 5_172_749_312 \
        == 4 * (outside + 16 * expert) + 2 * 16384 * 6144 + 6144
    assert round(2 * mla_scmoe.param_count(full) / 1e9, 2) == 10.35
    assert mla_scmoe.published_param_count(full, 28, 131072) \
        == 560_664_980_480 \
        == 28 * (outside + 512 * expert) + 2 * 131072 * 6144 + 6144
    assert (full.router_outputs, full.moe_topk, full.sublayers,
            full.layer_applications) == (768, 12, 8, 4)
    assert (full.experts_first, full.experts_held) == (96, 16)
    assert (full.q_scale, round(full.kv_scale, 4)) == (2.0, 3.4641)
    assert (full.scoring_func, full.norm_topk_prob) == ("softmax", False)
    with pytest.raises(ValueError, match="not among the 512 real ones"):
        dataclasses.replace(full, experts_first=500)


def test_two_latent_slots_a_layer():
    """9,216 B a position a row at the published widths: eight slots of
    576 bf16 values."""
    full = mla_scmoe.LONGCAT_FLASH_OMNI_SHARE
    cache = jax.eval_shape(lambda: mla_scmoe.empty_cache(full, 4, 2112))
    assert cache.shape == (8, 4, 2112, 576) and cache.dtype == jnp.bfloat16
    assert mla_scmoe.kv_cache_bytes(full, 1, 1) == 9216
    assert mla_scmoe.kv_cache_bytes(full, 4, 2112) == 77_856_768 \
        == cache.size * 2
    shapes = mla_scmoe.param_shapes(full)
    assert shapes["sublayers"]["q_a_proj"] == (8, 6144, 1536)
    assert shapes["sublayers"]["gate_proj"] == (8, 6144, 12288)
    assert shapes["router"] == {"classifier": (4, 6144, 768),
                                "e_score_correction_bias": (4, 768)}
    assert shapes["experts"]["down_proj"] == (4, 16, 2048, 6144)


# --- the compiled program -------------------------------------------------------

LM_CLASSES = {"lm_proj", "lm_attn", "lm_cache", "lm_mlp", "lm_experts",
              "lm_zero", "lm_norm", "lm_head", "embed"}


@pytest.fixture(scope="module")
def compiled_text():
    cfg, p = of_dtype("float32")
    return mla_scmoe.make_program(cfg, 3).lower(
        p, jnp.zeros((4, 16), jnp.int32), np.zeros(4, np.int32) + 9,
        np.zeros(4, np.uint32), np.zeros(4, np.float32)).compile().as_text()


def test_every_class_and_both_phases_are_in_the_compiled_program(
        compiled_text):
    names = [n for n in re.findall(r'op_name="([^"]+)"', compiled_text)
             if "LongcatFlash" in n]
    assert len(names) > 200
    assert {trace.classify(n) for n in names} == LM_CLASSES
    assert {trace.phase_of(n) for n in names} == {"prefill", "decode"}
    zero = [n for n in names if trace.classify(n) == "lm_zero"]
    assert zero and all("/mlp/zero_experts/" in n for n in zero)
    # no product and no gather among the zero experts' operations
    assert not [n for n in zero if re.search(r"dot_general|gather|while",
                                             n.split("zero_experts/")[1])]


def test_a_decode_step_copies_no_cache(compiled_text):
    """The four slots go through the decode scan's carry and a step
    writes one position of each in place: no instruction under ``decode``
    but the loops' own tuples has the whole cache ``[4, 4, 19, 24]`` as
    its RESULT unless it is the in-place ``dynamic-update-slice``."""
    copies = []
    for line in compiled_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.-]+) = (\S+) (\w[\w-]*)\(", line)
        if not m or "LongcatFlash/decode" not in line:
            continue
        name, result, op = m.groups()
        if result.split("{")[0] == "f32[4,4,19,24]" and op not in (
                "dynamic-update-slice", "get-tuple-element", "parameter",
                "bitcast") and "dynamic_update_slice" not in line \
                and "dynamic-update-slice" not in name:
            copies.append(line.strip()[:160])
    assert not copies, copies


# --- through the registry: counters, gauges, names ------------------------------

def counters():
    return dict(trace.GLOBAL_COUNTERS.snapshot())


@pytest.fixture
def model(monkeypatch):
    monkeypatch.setenv("DTPU_DEFAULT_FAMILY", "tiny")
    return registry.load_language_model("longcat-flash-omni.safetensors")


def test_the_registry_serves_it_and_counts_the_three_kinds_of_pair(
        model, assert_nothing_compiled):
    """`load_language_model` by name -> `generate_rows`: the ``lm.*``
    counters keep their meaning, the pairs to zero experts, the positions
    and the keys come over in the same read; a second execution of the
    shape compiles nothing."""
    assert model.family == "longcat" and model.cfg == TINY
    assert model.row_counts == (1, 4)
    rows = [registry.LMRow(f"a lighthouse at dawn number {i}", i,
                           instructions="draw what the user asks for")
            for i in range(3)]
    model.generate_rows(rows[:1], max_new_tokens=5, prompt_tokens=32)
    before, mark = counters(), trace.GLOBAL_RETRACES.mark()
    out = model.generate_rows(rows, max_new_tokens=5, prompt_tokens=32)
    assert_nothing_compiled(trace.GLOBAL_RETRACES.since(mark))
    after = counters()
    got = {k: after.get(k, 0) - before.get(k, 0) for k in after
           if k.startswith("lm.")}
    assert got["lm.executions"] == 1 and got["lm.rows"] == 3
    assert got["lm.padded_rows"] == 1 and got["lm.tokens_decoded"] == 15
    assert got["lm.layer_applications"] == 15 * 2          # 2 layers held
    assert got["lm.expert_pairs"] == 3 * 5 * 2 * 4      # rows x steps x L x k
    assert 0 < got["lm.expert_pairs_zero"] < got["lm.expert_pairs"]
    assert got["lm.expert_pairs_local"] + got["lm.expert_pairs_zero"] \
        <= got["lm.expert_pairs"]
    assert 0 <= got["lm.expert_hits"] <= 5 * 2 * 4
    assert got["lm.expert_pairs_dropped"] == 0
    assert got["lm.expert_pairs_local_prefill"] \
        + got["lm.expert_pairs_zero_prefill"] <= 4 * 32 * 2 * 4
    assert got["lm.expert_pairs_local_prefill"] \
        <= got["lm.expert_rows_computed_prefill"]
    assert got["lm.prefill_positions"] == 4 * 32        # every program row
    real = got["lm.prompt_tokens"]                      # of three rows
    assert got["lm.keys_attended"] == 4 * (5 * real + 3 * (1 + 2 + 3 + 4 + 5))
    assert got["lm.keys_attended_prefill"] > 0
    gauges = trace.GLOBAL_GAUGES.snapshot()
    assert gauges["lm.kv_cache_bytes"] == \
        mla_scmoe.kv_cache_bytes(TINY, 4, 37) == 4 * 4 * 37 * 24 * 4
    words, lm_out = out[2]
    assert lm_out.row == 2 and set(lm_out.aux) == {
        "router_scores", "expert_choices", "expert_weights",
        "prompt_choices"}
    assert len(words.split()) <= 5


@pytest.mark.parametrize("name, want", [
    ("longcat-flash-omni.safetensors", ("longcat", "full")),
    ("LongCat-Flash-tiny.safetensors", ("longcat", "tiny")),
])
def test_a_model_name_names_the_seventh_family(name, want, monkeypatch):
    monkeypatch.delenv("DTPU_DEFAULT_FAMILY", raising=False)
    assert registry.detect_lm_family(name) == want
    assert list(registry.LM_FAMILIES)[-1] == "longcat"
    with pytest.raises(ValueError) as e:
        registry.detect_lm_family("a-decoder-of-no-family-7b.safetensors")
    assert "longcat" in str(e.value) and "zero-compute" in str(e.value)


def test_a_second_language_model_that_cannot_fit_is_refused_by_name(
        monkeypatch):
    monkeypatch.delenv("DTPU_DEFAULT_FAMILY", raising=False)
    monkeypatch.setattr(registry, "_device_free_bytes",
                        lambda: int(16.9e9 - 7.71e9 - 2.6e9))
    name = "longcat-flash-omni-of-another-graph.safetensors"   # not cached
    with pytest.raises(ValueError) as e:
        registry.load_language_model(name)
    assert name in str(e.value)
    assert "10.35 GB" in str(e.value) and "serve one language model a chip" \
        in str(e.value)
