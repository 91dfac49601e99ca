"""The decoder with window and full attention layers in one stack
(models/swa_moe.py: a ring of slots beside a full cache, grouped
key-value heads, the expert layer of models/mla_moe.py) against its plain
reference (benchmarks/chip/reference/swa_moe.py) on seeded weights, at a
tiny size: d 64, the published order of layer types over a dense block
and four expert blocks, a window of 8, 4 query heads over 2 key-value
heads of 16, experts 4..7 of 16 held, top-4, V 512.  Prompts of 17 to 24
ids behind a buffer of 24 and 12 decoded tokens: over two windows long,
so the ring wraps in the prefill and in every decode step.

The comparison is verify_lm_moe.py's threefold one (router scores,
choices excused only where the reference's own cut is that close, logits
under the PROGRAM's choices), the one the chip run uses at the published
widths.  Each breakage the issue names has to fail it where the served
path passes.
"""

import dataclasses
import importlib.util
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import layers, looplm, mla_moe, \
    registry, swa_moe
from comfyui_distributed_tpu.models.swa_moe import FULL, SLIDING
from comfyui_distributed_tpu.utils import trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sys.path.insert(0, BENCH)
ref = _load("swa_moe_reference",
            os.path.join(BENCH, "reference", "swa_moe.py"))
verify = _load("chipbench_verify_lm_moe_for_swa",
               os.path.join(BENCH, "verify_lm_moe.py"))

TINY = swa_moe.TINY_SWA_MOE
NEW, PAD_TO = 12, 24
LENS = [19, 24, 17, 21]             # PAD_TO = 24: one row has no padding

# The limits for the tiny model in bf16 (the chip's, at width 6144, are
# verify_lm_swa_moe.LIMITS).  Why the served path differs from the
# float32 reference at all: its matmul operands and both its caches are
# bf16 (8 bits of mantissa) and five blocks of width 64 add their
# roundings up.  Measured here (two weight seeds, rows alone and four
# together): logits mean 0.0033-0.0046, max 0.017-0.030 of a logit's
# standard deviation, router scores within 0.0044-0.0078.  With the
# caches in 8 bits (3 bits of mantissa): mean 0.019-0.030, max
# 0.097-0.34, scores off by 0.029-0.064.  Each limit is the geometric
# mean of the served path's largest reading and the 8-bit caches'
# smallest.
TINY_BF16_LIMITS = {"max_over_std": 0.054, "mean_over_std": 0.0092,
                    "margin_over_std": 0.108}
TINY_BF16_ROUTER_TOLERANCE = 0.015


def limits_of(dtype):
    if jnp.dtype(dtype) == jnp.float32:
        return verify.LIMITS_FP32, verify.ROUTER_TOLERANCE_FP32
    return TINY_BF16_LIMITS, TINY_BF16_ROUTER_TOLERANCE


def hf(cfg):
    """The config as the reference reads it (the configuration file's
    ``lm`` block)."""
    out = {k: v for k, v in dataclasses.asdict(cfg).items() if k != "dtype"}
    return {**out, "router_outputs": cfg.num_experts,
            "dense_layers_held": cfg.first_k_dense_replace}


def held(cfg):
    return range(cfg.experts_first, cfg.experts_first + cfg.experts_held)


def prompt(seed=0, n=LENS[0]):
    ids = np.zeros((1, PAD_TO), np.int32)
    ids[0, :n] = np.random.default_rng(seed).integers(3, TINY.vocab_size, n)
    return ids


def serve_rows(cfg, params, lens, new=NEW):
    """One execution over rows of the real lengths ``lens`` (row ``b``'s
    prompt is ``prompt(b, lens[b])``); per row what the save node would
    write, and the execution's ``stats``."""
    ids = np.concatenate([prompt(b, n) for b, n in enumerate(lens)])
    tokens, logits, aux, stats = swa_moe.make_program(cfg, new)(
        params, jnp.asarray(ids), np.asarray(lens, np.int32),
        np.asarray([3] * len(lens), np.uint32),
        np.asarray([0.0] * len(lens), np.float32))
    rows = [{"prompt_ids": ids[b, :n], "tokens": np.asarray(tokens[b]),
             "logits": np.asarray(logits[b]),
             **{k: np.asarray(v[b]) for k, v in aux.items()}}
            for b, n in enumerate(lens)]
    return rows, {k: np.asarray(v) for k, v in stats.items()}


def reference_of(cfg, params, served, **kw):
    """``reference(choices)`` of verify_lm_moe.compare_served: the
    reference's full forward pass, teacher-forced over the prompt and the
    served ids, at the rows each served token was drawn from."""
    ids, rows = verify.rows_of(served)

    def reference(choices):
        # the last position's row is not read
        choices = np.concatenate([choices, choices[-1:]])
        logits, scores, _ = ref.forward(hf(cfg), params, ids, held(cfg),
                                        choices, **kw)
        return np.asarray(logits)[rows], np.asarray(scores)[rows]
    return reference


def compare(cfg, params, served, **kw):
    limits, tolerance = limits_of(cfg.dtype)
    return verify.compare_served(
        served, reference_of(cfg, params, served, **kw), limits, tolerance)


def of_dtype(dtype):
    cfg = dataclasses.replace(TINY, dtype=jnp.dtype(dtype))
    return cfg, swa_moe.seeded_params(cfg, np.uint32(7))


@pytest.fixture(scope="module")
def params():
    return swa_moe.seeded_params(TINY, np.uint32(7))


# --- the served path against the reference ---------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows", [1, 4])
def test_prefill_then_decode_through_both_caches_match_the_reference(
        rows, dtype):
    """Alone and as the rows of one execution, prompts of unequal length:
    logits, router scores and choices.  float32: 1e-4 of a standard
    deviation (measured 2e-6: only the order of the additions differs; no
    choice flips).  bf16: TINY_BF16_LIMITS, their reasons beside them."""
    cfg, p = of_dtype(dtype)
    assert {x.dtype for x in jax.tree_util.tree_leaves(p)} \
        == {jnp.dtype(dtype)}
    served, stats = serve_rows(cfg, p, LENS[:rows])
    assert served[0]["logits"].dtype == np.float32
    assert served[0]["router_scores"].shape == (NEW, 4, 16)
    assert served[0]["expert_choices"].shape == (NEW, 4, 4)
    assert served[0]["prompt_choices"].shape == (PAD_TO, 4, 4)
    flipped = 0
    for b, row in enumerate(served):
        got = compare(cfg, p, row)
        assert got["correct"], (b, got)
        flipped += got["flipped"]
        if dtype == "bfloat16":
            assert got["mean_over_std"] > 1e-4     # and bf16 is what ran
    assert dtype == "bfloat16" or flipped == 0
    # the ring is full from the first step on and wraps in every one; a
    # full layer sees a row's real prompt and what it has decoded
    assert list(stats["keys_attended_window"]) == [NEW * 4 * 8] * rows
    assert list(stats["keys_attended_full"]) == [
        sum(n + i + 1 for i in range(NEW)) for n in LENS[:rows]]
    assert stats["expert_pairs_dropped"] == 0
    assert stats["expert_pairs_local_prefill"].shape == (rows,)
    # a prompt buffer of rows x PAD_TO tokens is one tile: every hit expert
    # multiplies it whole, at most 4 blocks x 4 experts of them
    tokens = rows * PAD_TO
    assert tokens <= mla_moe.EXPERT_TILE
    assert stats["expert_rows_computed_prefill"] % tokens == 0
    assert 0 < stats["expert_rows_computed_prefill"] <= 16 * tokens


def test_a_row_of_a_shared_execution_is_its_single_row_run(params):
    together, _ = serve_rows(TINY, params, LENS)
    for b in (1, 3):
        ids = prompt(b, LENS[b])
        tokens, logits, _, _ = swa_moe.make_program(TINY, NEW)(
            params, jnp.asarray(ids), np.int32(LENS[b]), np.uint32(3),
            np.float32(0.0))
        assert np.array_equal(together[b]["tokens"], tokens[0])
        np.testing.assert_allclose(together[b]["logits"], logits[0],
                                   atol=1e-5)


def test_a_prompt_buffer_shorter_than_the_window_fills_the_first_slots(
        params):
    """6 ids behind a buffer of 6, a window of 8: the ring is part empty
    (a slot never written is never seen) and fills as the decode goes."""
    ids = prompt(0, 6)[:, :6]
    tokens, logits, aux, stats = swa_moe.make_program(TINY, 5)(
        params, jnp.asarray(ids), np.int32(6), np.uint32(3), np.float32(0.0))
    served = {"prompt_ids": ids[0], "tokens": np.asarray(tokens[0]),
              "logits": np.asarray(logits[0]),
              **{k: np.asarray(v[0]) for k, v in aux.items()}}
    assert compare(TINY, params, served)["correct"]
    assert int(stats["keys_attended_window"][0]) == 4 * (7 + 8 + 8 + 8 + 8)


# --- each breakage fails the comparison -------------------------------------

def _fp8(tree):
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype), tree)


def _qkv_as(kind):
    real = swa_moe._qkv
    return lambda cfg, _, *a: real(cfg, kind, *a)


def _attend_head_mod_groups(q, k, v, q_positions, **mask):
    """Query head ``h`` reading key-value head ``h mod G``."""
    B, N, H, D = q.shape
    G = k.shape[2]
    out = layers.xla_attention(
        q.reshape(B, N * (H // G), G, D), k, v, 1.0 / math.sqrt(D),
        jnp.repeat(q_positions, H // G), **mask)
    return out.reshape(B, N, -1)


def _break(name, monkeypatch, cfg, params):
    """The served path with one thing wrong; returns (cfg, params)."""
    replace = dataclasses.replace
    if name == "the window one too wide":
        return replace(cfg, sliding_window=cfg.sliding_window + 1), params
    if name == "the window one too narrow":
        return replace(cfg, sliding_window=cfg.sliding_window - 1), params
    if name == "a stale ring slot attended to":
        # a step's key lands one slot on: the slot that should hold it
        # still holds the key of a window ago, and the mask lets it in
        real = jax.lax.dynamic_update_slice

        def one_slot_on(cache, new, at):
            ring = cache.shape[2] == cfg.sliding_window and new.shape[2] == 1
            at = (*at[:2], (at[2] + 1) % cfg.sliding_window, *at[3:]) \
                if ring else at
            return real(cache, new, at)
        monkeypatch.setattr(swa_moe.jax.lax, "dynamic_update_slice",
                            one_slot_on)
    elif name == "the rotation put on the full layer":
        monkeypatch.setattr(swa_moe, "_qkv", _qkv_as(SLIDING))
    elif name == "the rotation taken off the sliding layers":
        monkeypatch.setattr(swa_moe, "_qkv", _qkv_as(FULL))
    elif name == "the full layer at another index":
        return replace(cfg, layer_types=(SLIDING, SLIDING, FULL, SLIDING,
                                         SLIDING)), params
    elif name == "q_norm and k_norm left out":
        real = swa_moe._rms_norm
        monkeypatch.setattr(
            swa_moe, "_rms_norm", lambda x, gain, eps:
            x.astype(jnp.float32) if x.ndim == 4 else real(x, gain, eps))
    elif name == "key-value head h mod G for h // (H / G)":
        monkeypatch.setattr(swa_moe, "_attend", _attend_head_mod_groups)
    elif name == "the shared expert left out":
        moe = dict(params["moe_layers"])
        moe["shared_experts"] = jax.tree_util.tree_map(
            jnp.zeros_like, moe["shared_experts"])
        return cfg, {**params, "moe_layers": moe}
    elif name == "the 2.5 left out":
        return replace(cfg, routed_scaling_factor=1.0), params
    elif name == "the caches in 8 bits":
        real = swa_moe.empty_cache
        monkeypatch.setattr(
            swa_moe, "empty_cache", lambda *a: jax.tree_util.tree_map(
                lambda c: c.astype(jnp.float8_e4m3fn), real(*a)))
    elif name == "the weights in 8 bits":
        return cfg, _fp8(params)
    return cfg, params


BREAKAGES = ["the window one too wide", "the window one too narrow",
             "a stale ring slot attended to",
             "the rotation put on the full layer",
             "the rotation taken off the sliding layers",
             "the full layer at another index", "q_norm and k_norm left out",
             "key-value head h mod G for h // (H / G)",
             "the shared expert left out", "the 2.5 left out",
             "the caches in 8 bits", "the weights in 8 bits"]


@pytest.mark.parametrize("rows", [1, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("what", BREAKAGES)
def test_each_breakage_fails_the_comparison(what, dtype, rows, monkeypatch):
    """Alone and as a row of a 4-row execution, in float32 and in the
    stated bf16: the broken path fails at least one reading of the
    threefold comparison for EVERY row (the served path passes them all:
    the test above)."""
    cfg, p = of_dtype(dtype)
    broken_cfg, broken_p = _break(what, monkeypatch, cfg, p)
    broken, _ = serve_rows(broken_cfg, broken_p, LENS[:rows])
    monkeypatch.undo()
    readings = [compare(cfg, p, row) for row in broken]
    assert not any(r["correct"] for r in readings), (what, readings)


@pytest.mark.parametrize("window", [None, 7, 9])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_reference_with_another_window_is_refused(window, dtype):
    """The comparison sees the mechanism: the served path held to the
    reference with the window off (every layer a plain causal one), or
    one position off, is refused."""
    cfg, p = of_dtype(dtype)
    served, _ = serve_rows(cfg, p, LENS[:1])
    assert compare(cfg, p, served[0])["correct"]
    assert not compare(cfg, p, served[0], window=window)["correct"]


# --- the share is tied to the model ------------------------------------------

def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """For one expert layer: the routed parts that all 8 shares give (the
    program's `_routed`, each share told which 2 of the 16 experts it
    holds), plus the shared expert counted once, are the uncut layer of
    the reference (every expert held, a loop over all 16)."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((14, TINY.hidden_size)), jnp.float32)
    whole = dataclasses.replace(TINY, experts_first=0, experts_held=16)
    full = swa_moe.seeded_params(whole, np.uint32(11))["moe_layers"]
    lp = jax.tree_util.tree_map(lambda w: w[1], full)         # block 1
    scores, chosen, weights = mla_moe.route(TINY, lp["gate"], x)
    total = mla_moe._gated_mlp(TINY, lp["shared_experts"], x)
    pairs = 0
    for first in range(0, 16, 2):
        share = dataclasses.replace(TINY, experts_first=first,
                                    experts_held=2)
        experts = {k: w[:, first:first + 2]
                   for k, w in full["experts"].items()}
        part, local, hits, dropped, rows = mla_moe._routed(
            share, experts, jnp.int32(1), x, chosen, weights)
        total, pairs = total + part, pairs + int(local.sum())
        assert int(dropped) == 0 and 0 <= int(hits) <= 2
        assert int(rows) == int(hits) * 14      # 14 tokens: one tile each
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


def test_the_expert_layer_is_shared_not_copied():
    """Both families run ``models/mla_moe.py``'s expert layer."""
    for name in ("_moe", "_gated_mlp"):
        assert getattr(swa_moe, name) is getattr(mla_moe, name)
    with open(swa_moe.__file__) as f:
        source = f.read()
    assert not re.search(r"^def (route|_routed|_moe|_gated_mlp)\b", source,
                         re.M)


# --- the two caches ----------------------------------------------------------

def test_the_published_share_is_the_issues_arithmetic():
    full = swa_moe.K_EXAONE_SHARE
    attention = 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144
    expert = 3 * 6144 * 2048
    dense = attention + 3 * 6144 * 18432
    moe = attention + expert + 6144 * 128 + 16 * expert
    assert (attention, expert, dense, moe) == (
        113_246_208, 37_748_736, 452_984_832, 755_761_152)
    values = dense + 4 * moe + 2 * 19200 * 6144
    assert values == 3_711_959_040
    gains = 5 * (2 * 6144 + 2 * 128) + 6144
    assert swa_moe.param_count(full) == values + gains
    assert round(2 * swa_moe.param_count(full) / 1e9, 2) == 7.42
    assert full.layer_applications == 5 and full.moe_layers == 4
    assert (full.experts_first, full.experts_held) == (32, 16)
    assert full.layer_types == (SLIDING,) * 3 + (FULL, SLIDING)
    with pytest.raises(ValueError, match="not among the router's"):
        dataclasses.replace(full, experts_first=120)
    with pytest.raises(ValueError, match="do not name 5 blocks"):
        dataclasses.replace(full, layer_types=(SLIDING, FULL))


def test_a_ring_beside_a_full_cache_and_the_gauge_says_each():
    """4 KiB a key and value a layer at the published widths (8 heads of
    128, bf16, keys and values): four rings of 128 slots and one full
    layer, 2.3 MB a row at 576 positions."""
    full = swa_moe.K_EXAONE_SHARE
    caches = jax.eval_shape(lambda: swa_moe.empty_cache(full, 4, 576))
    assert [c.shape for c in caches[SLIDING]] == [(4, 4, 128, 8, 128)] * 2
    assert [c.shape for c in caches[FULL]] == [(1, 4, 576, 8, 128)] * 2
    parts = swa_moe.kv_cache_bytes_by_kind(full, 4, 576)
    assert parts == {"ring": 4 * 4 * 128 * 4096, "full": 4 * 576 * 4096}
    assert swa_moe.kv_cache_bytes(full, 4, 576) == sum(parts.values()) \
        == sum(math.prod(c.shape) * 2 for kind in caches.values()
               for c in kind)
    assert swa_moe.kv_cache_bytes(full, 1, 576) == 4456448    # 2.3 MB a row
    # a full cache in every layer would be 2.6 x that
    assert 5 * 576 * 4096 / swa_moe.kv_cache_bytes(full, 1, 576) > 2.6


def test_the_runs_of_a_stack_follow_the_layer_types():
    runs = [(r.stack, r.kind, r.start, r.count, r.cache_start)
            for r in TINY.runs]
    assert runs == [("dense_layers", SLIDING, 0, 1, 0),
                    ("moe_layers", SLIDING, 0, 2, 1),
                    ("moe_layers", FULL, 2, 1, 0),
                    ("moe_layers", SLIDING, 3, 1, 3)]
    other = dataclasses.replace(
        TINY, layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL))
    assert [(r.kind, r.start, r.count, r.cache_start)
            for r in other.runs] == [(FULL, 0, 1, 0), (SLIDING, 0, 3, 0),
                                     (FULL, 3, 1, 1)]


def test_the_slots_of_a_ring_hold_the_last_window_positions():
    assert list(swa_moe.ring_positions(jnp.int32(21), 8)) == \
        [16, 17, 18, 19, 20, 21, 14, 15]
    # before the ring has wrapped a slot never written is below every start
    assert list(swa_moe.ring_positions(jnp.int32(2), 8)) == \
        [0, 1, 2, -5, -4, -3, -2, -1]
    k = jnp.arange(21)[None, :, None, None]
    assert list(swa_moe._ring_of(k, 8)[0, :, 0, 0]) == \
        [16, 17, 18, 19, 20, 13, 14, 15]
    assert list(swa_moe._ring_of(k[:, :5], 8)[0, :, 0, 0]) == [0, 1, 2, 3, 4]


@pytest.mark.parametrize("window, kv_positions, want", [
    (None, None, [[0, 0, 1, 1, 1, 0]]),
    (2, None, [[0, 0, 0, 1, 1, 0]]),
    (3, [6, 1, 2, 3, 4, 5], [[0, 0, 1, 1, 1, 0]]),
])
def test_the_mask_of_a_masked_call(window, kv_positions, want):
    """Query at position 4 of a row that starts at 2: causal, banded, and
    over the slots of a ring (slot 0 holds a later position)."""
    seen = layers.visible_keys(
        6, jnp.asarray([4]), jnp.asarray([2]),
        None if kv_positions is None else jnp.asarray(kv_positions), window)
    assert seen.shape == (1, 1, 6)
    assert seen[0].astype(int).tolist() == want


def test_the_attention_rule_names_the_banded_and_the_ring_calls():
    rule = layers.attention_path
    assert rule("tpu", 4, 512, 512, 64, masked=True, banded=True) \
        == "xla_banded"
    assert rule("tpu", 4, 1, 128, 64, masked=True, banded=True) == "xla_ring"
    assert rule("tpu", 4, 512, 512, 64, masked=True) == "xla_causal"
    assert rule("tpu", 4, 1, 576, 64, masked=True) == "xla_decode"
    before = dict(trace.ATTENTION_PATHS.snapshot())
    jax.eval_shape(
        swa_moe.make_program(TINY, 2), swa_moe.seeded_params(TINY, 0),
        jnp.zeros((1, 16), jnp.int32), np.int32(5), np.uint32(0),
        np.float32(0.0))
    after = trace.ATTENTION_PATHS.snapshot()
    got = {k: after[k] - before.get(k, 0) for k in after
           if after[k] != before.get(k, 0)}
    # a call site a run: three sliding runs and one full, in each phase
    assert got == {"xla_banded": 3, "xla_causal": 1, "xla_ring": 3,
                   "xla_decode": 1}


# --- the compiled program ------------------------------------------------------

@pytest.fixture(scope="module")
def compiled_text(params):
    return swa_moe.make_program(TINY, 3).lower(
        params, jnp.zeros((4, 16), jnp.int32), np.zeros(4, np.int32) + 9,
        np.zeros(4, np.uint32), np.zeros(4, np.float32)).compile().as_text()


def _four_rows(program, params):
    return program.lower(
        params, jnp.zeros((4, 16), jnp.int32), np.zeros(4, np.int32) + 9,
        np.zeros(4, np.uint32), np.zeros(4, np.float32)).compile().as_text()


def _exaone(request):
    cfg, p = of_dtype("float32")
    served, _ = serve_rows(cfg, p, LENS[:1])
    return (request.getfixturevalue("compiled_text"), LM_CLASSES_WITH_EXPERTS,
            compare(cfg, p, served[0]))


def _pangu(request):
    import test_mla_moe as t
    cfg = dataclasses.replace(t.TINY, dtype=jnp.dtype("float32"))
    p = mla_moe.seeded_params(cfg, np.uint32(7))
    return (_four_rows(mla_moe.make_program(cfg, 3), p),
            LM_CLASSES_WITH_EXPERTS, t.compare(cfg, p, t.serve(cfg, p)))


def _ouro(request):
    import test_looplm as t
    p = t.make_params(t.TINY)
    tokens, logits, _ = t.serve(t.TINY, p, t.prompt())
    want, _ = t.reference_rows(t.TINY, p, t.prompt(), tokens)
    return (_four_rows(looplm.make_program(t.TINY, 3), p),
            LM_CLASSES_WITH_EXPERTS - {"lm_experts"},
            t.verify.compare_logits(logits, want, tokens,
                                    t.verify.LIMITS_FP32))


LM_CLASSES_WITH_EXPERTS = {"lm_proj", "lm_attn", "lm_cache", "lm_mlp",
                           "lm_experts", "lm_norm", "lm_head", "embed"}


@pytest.mark.parametrize("model, family", [
    ("ExaoneMoe", _exaone), ("PanguUltraMoE", _pangu), ("LoopLM", _ouro)])
def test_every_class_and_both_phases_are_in_the_compiled_program(
        model, family, request):
    """Every language model's ``lm_generate`` carries ``prefill`` and
    ``decode`` RIGHT under its model's scope (PR 34: K-EXAONE's; PR 38:
    Ouro's and openPangu's).  The scopes are names and nothing else: an
    operation under a phase falls in the class its path without the
    phase falls in, and the tiny program's ids and logits are the plain
    float32 reference's."""
    text, classes, against_reference = family(request)
    names = [n for n in re.findall(r'op_name="([^"]+)"', text) if model in n]
    assert len(names) > 200
    assert {trace.classify(n) for n in names} == classes
    assert {trace.phase_of(n) for n in names} == {"prefill", "decode"}
    for n in names:
        segments = n.split("/")
        at = segments.index(model)
        assert segments[at + 1] in trace.PHASES, n
        bare = "/".join(segments[:at + 1] + segments[at + 2:])
        assert trace.phase_of(bare) is None
        assert trace.classify(n) == trace.classify(bare), n
    assert against_reference["correct"], against_reference


def test_a_decode_step_copies_no_cache(compiled_text):
    """Two cache pytrees go through the decode scan's carry, and a step
    writes one position of each in place: no instruction of the compiled
    program under ``decode`` but the loops' own tuples has a whole cache
    (keys or values of a kind: ``[L, 4, positions, 2, 16]``) as its
    RESULT unless it is the in-place ``dynamic-update-slice`` (or a
    fusion rooted in one)."""
    shapes = {f"f32[{layers_},4,{positions},2,16]"
              for layers_, positions in ((4, 8), (1, 19))}
    copies = []
    for line in compiled_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.-]+) = (\S+) (\w[\w-]*)\(", line)
        if not m or "ExaoneMoe/decode" not in line:
            continue
        name, result, op = m.groups()
        if result.split("{")[0] in shapes and op not in (
                "dynamic-update-slice", "get-tuple-element", "parameter",
                "bitcast") and "dynamic_update_slice" not in line \
                and "dynamic-update-slice" not in name:
            copies.append(line.strip()[:160])
    assert not copies, copies


@pytest.mark.parametrize("path, want, phase", [
    ("prefill/moe_layers/while/body/self_attn/q_proj/dot_general",
     "lm_proj", "prefill"),
    ("decode/while/body/moe_layers/while/body/self_attn/k_proj/dot_general",
     "lm_proj", "decode"),
    ("decode/while/body/moe_layers/while/body/self_attn/q_norm/mul",
     "lm_norm", "decode"),
    ("decode/while/body/dense_layers/while/body/self_attn/kv_cache/"
     "dynamic_update_slice", "lm_cache", "decode"),
    ("prefill/moe_layers/while/body/self_attn/rotary/cos", "lm_attn",
     "prefill"),
    ("prefill/moe_layers/while/body/self_attn/bnhd,bmhd->bhnm/dot_general",
     "lm_attn", "prefill"),
    ("prefill/moe_layers/while/body/mlp/gate/top_k", "lm_experts",
     "prefill"),
    ("decode/while/body/moe_layers/while/body/mlp/experts/while/body/cond/"
     "branch_1_fun/dot_general", "lm_experts", "decode"),
    ("decode/while/body/moe_layers/while/body/mlp/shared_experts/up_proj/"
     "dot_general", "lm_mlp", "decode"),
    ("prefill/dense_layers/while/body/mlp/down_proj/dot_general", "lm_mlp",
     "prefill"),
    ("prefill/moe_layers/while/body/post_feedforward_layernorm/rsqrt",
     "lm_norm", "prefill"),
    ("decode/while/body/final_norm/mul", "lm_norm", "decode"),
    ("decode/while/body/lm_head/fewrow_dense/pallas_call", "lm_head",
     "decode"),
    ("decode/while/body/sample/argmax", "lm_head", "decode"),
    ("prefill/embed_tokens/gather", "embed", "prefill"),
    ("prefill/moe_layers/while/body/add", "lm_proj", "prefill"),
    ("decode/while/body/closed_call/moe_layers/while/body/closed_call/"
     "self_attn/k_proj/fewrow_dense_k_proj_v_proj/pallas_call", "lm_proj",
     "decode"),
])
def test_the_scopes_fall_in_their_classes_and_phases(path, want, phase):
    name = "jit(lm_generate)/ExaoneMoe/" + path
    assert trace.classify(name) == want
    assert trace.phase_of(name) == phase


def test_a_program_without_phase_scopes_has_no_phase():
    for name in ("jit(lm_generate)/PanguUltraMoE/moe_layers/while/body/mlp/"
                 "gate/dot_general",
                 "jit(lm_generate)/LoopLM/layers/while/body/self_attn/"
                 "q_proj/dot_general", "jit(core)/while/body/UNet/mid_attn",
                 "fusion.12", ""):
        assert trace.phase_of(name) is None


# --- through the registry: counters, gauges, the prompt ------------------------

def counters():
    return dict(trace.GLOBAL_COUNTERS.snapshot())


@pytest.fixture
def model(monkeypatch):
    monkeypatch.setenv("DTPU_DEFAULT_FAMILY", "tiny")
    return registry.load_language_model("k-exaone-236b-a23b.safetensors")


def test_the_registry_serves_it_and_counts_keys_and_routing(
        model, assert_nothing_compiled):
    """`load_language_model` by name -> `generate_rows`: the ``lm.*``
    counters of PR 28-32 keep their meaning, the keys each kind of layer
    attended to come over in the same read, the gauge is ring + full and
    says each part; a second execution of the shape compiles nothing."""
    assert model.family == "exaone" and model.cfg == TINY
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
    assert got["lm.layer_applications"] == 15 * 5          # 5 blocks held
    assert got["lm.expert_pairs"] == 3 * 5 * 4 * 4         # rows x steps x Le x k
    assert 0 < got["lm.expert_pairs_local"] < got["lm.expert_pairs"]
    assert 0 < got["lm.expert_hits"] <= 5 * 4 * 4
    assert got["lm.expert_pairs_dropped"] == 0
    assert 0 < got["lm.expert_pairs_local_prefill"] < 4 * 32 * 4 * 4
    # what the prefill's experts multiplied for those pairs: never fewer
    # rows than pairs, and at most one partly filled tile an expert held,
    # expert block and execution beyond them
    assert got["lm.expert_pairs_local_prefill"] \
        <= got["lm.expert_rows_computed_prefill"] \
        < got["lm.expert_pairs_local_prefill"] \
        + got["lm.executions"] * TINY.moe_layers * TINY.experts_held \
        * mla_moe.EXPERT_TILE
    assert got["lm.keys_attended_window"] == 3 * 5 * 4 * 8
    real = got["lm.prompt_tokens"]                          # of three rows
    assert got["lm.keys_attended_full"] == 5 * real + 3 * (1 + 2 + 3 + 4 + 5)
    gauges = trace.GLOBAL_GAUGES.snapshot()
    assert gauges["lm.kv_cache_bytes"] == \
        swa_moe.kv_cache_bytes(TINY, 4, 37) == \
        gauges["lm.kv_cache_bytes_ring"] + gauges["lm.kv_cache_bytes_full"]
    assert gauges["lm.kv_cache_bytes_ring"] == 4 * 4 * 8 * 2 * 2 * 16 * 4
    assert gauges["lm.kv_cache_bytes_full"] == 1 * 4 * 37 * 2 * 2 * 16 * 4
    words, lm_out = out[2]
    assert lm_out.row == 2 and set(lm_out.aux) == {
        "router_scores", "expert_choices", "prompt_choices"}
    assert len(words.split()) <= 5


def test_empty_instructions_give_the_ids_of_today(model):
    """The ids are ``instructions`` + the template with the user's text;
    with none they are what they were."""
    text = "a lighthouse on a cliff at dawn"
    today = model.tokenizer.encode(registry.EXPAND_TEMPLATE.format(text=text))
    assert list(model.prompt_ids(text, 64)) == today
    assert list(model.prompt_ids(text, 64, "")) == today
    assert registry.LMRow(text, 3) == registry.LMRow(text, 3, 0.0, "")
    shots = "example one a red fox example two a blue door"
    ids = list(model.prompt_ids(text, 64, shots))
    assert ids == model.tokenizer.encode(shots) + today[1:]
    assert len(ids) == len(today) + 10
    # cut to the buffer like every prompt
    assert list(model.prompt_ids(text, 12, shots)) == ids[:12]


@pytest.mark.parametrize("name, want", [
    ("k-exaone-236b-a23b.safetensors", ("exaone", "full")),
    ("K-EXAONE-tiny.safetensors", ("exaone", "tiny")),
])
def test_a_model_name_names_the_third_family(name, want, monkeypatch):
    monkeypatch.delenv("DTPU_DEFAULT_FAMILY", raising=False)
    assert registry.detect_lm_family(name) == want
    with pytest.raises(ValueError) as e:
        registry.detect_lm_family("a-decoder-of-no-family-7b.safetensors")
    assert "exaone" in str(e.value)


def test_a_second_language_model_that_cannot_fit_is_refused_by_name(
        monkeypatch):
    """7.4 + 9.8 GB do not share a chip: the loader says which model
    needs what, before the allocator fails with an error that names
    nothing."""
    monkeypatch.delenv("DTPU_DEFAULT_FAMILY", raising=False)
    monkeypatch.setattr(registry, "_device_free_bytes",
                        lambda: int(15.7e9 - 9.84e9 - 2.6e9))
    name = "k-exaone-236b-a23b-of-another-graph.safetensors"   # not cached
    with pytest.raises(ValueError) as e:
        registry.load_language_model(name)
    assert name in str(e.value)
    assert "7.42 GB" in str(e.value) and "serve one language model a chip" \
        in str(e.value)
