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


# --- a prefix shared between requests ---------------------------------------
#
# Every row's prompt is the same 37 ids (a prime: no multiple of anything a
# buffer is cut by, the tokenizer's words among them) and then its own: 12,
# 11, 5 and 1 ids behind them in a suffix buffer of 12 (one row fills it,
# one is a single id; their offsets in the buffer are 0, 1, 7 and 11).  The
# whole prompt is 49 positions.

PREFIX, SUFFIX = 37, 12
OWN = [12, 11, 5, 1]


def shared_prompts():
    """Per row the whole prompt: the same 37 ids, then the row's own."""
    rng = np.random.RandomState(12)
    head = rng.randint(3, TINY.vocab_size, PREFIX)
    return [np.concatenate([head, rng.randint(3, TINY.vocab_size, n)]
                           ).astype(np.int32) for n in OWN]


@functools.lru_cache(maxsize=None)
def shared_programs(dtype):
    """The maker and the served program, jitted once a dtype."""
    cfg, _ = of_dtype(dtype)
    return mla_scmoe.make_prefix_program(cfg), mla_scmoe.make_program(cfg, NEW)


@functools.lru_cache(maxsize=None)
def snapshot_of(dtype):
    return shared_programs(dtype)[0](
        of_dtype(dtype)[1], jnp.asarray(shared_prompts()[0][:PREFIX]))


def buffers(picked, held):
    """The prompt buffer of the rows ``picked`` with their first ``held``
    ids left out (a snapshot stands for them), and the lengths."""
    whole = shared_prompts()
    ids = np.zeros((len(picked), PREFIX + SUFFIX - held), np.int32)
    for b, i in enumerate(picked):
        ids[b, :len(whole[i]) - held] = whole[i][held:]
    return ids, np.asarray([len(whole[i]) - held for i in picked], np.int32)


def serve_shared(dtype, snapshot, picked=(0, 1, 2, 3), temperature=0.0,
                 program=None):
    """One execution over the rows ``picked`` of `shared_prompts`: from
    the ``snapshot`` of the 37 ids, or with None the whole prompts through
    the five-argument program."""
    ids, lens = buffers(picked, 0 if snapshot is None else PREFIX)
    tokens, logits, aux, stats = (program or shared_programs(dtype)[1])(
        of_dtype(dtype)[1], ids, lens, np.asarray(picked, np.uint32) + 3,
        np.asarray([temperature] * len(picked), np.float32),
        *(() if snapshot is None else (snapshot,)))
    whole = shared_prompts()
    return [{"prompt_ids": whole[i], "tokens": np.asarray(tokens[b]),
             "logits": np.asarray(logits[b]),
             **{k: np.asarray(v[b]) for k, v in aux.items()}}
            for b, i in enumerate(picked)], {
                k: np.asarray(v) for k, v in stats.items()}


@pytest.mark.parametrize("picked", [(0,), (3,), (0, 1, 2, 3), (2, 1, 1, 1)])
@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_rows_started_from_a_snapshot_are_the_whole_prompts_rows(
        picked, temperature):
    """Alone or four of unequal length (a row that fills the suffix
    buffer, a single id, a padded execution whose last rows repeat one),
    greedy or sampled with seeds: the ids of the program over the whole
    prompt, its logits to float32's rounding (the suffix's queries run
    ABSORBED on the cache slots, the whole prompt's expanded), over each
    row's REAL positions the same record of the experts chosen; and the
    reference forced to that record agrees (the fourfold comparison, which
    reads all of it)."""
    cfg, p = of_dtype("float32")
    snapshot = snapshot_of("float32")
    served, stats = serve_shared("float32", snapshot, picked, temperature)
    full, full_stats = serve_shared("float32", None, picked, temperature)
    P = PREFIX + SUFFIX
    for got, want, i in zip(served, full, picked):
        first = SUFFIX - OWN[i]
        assert np.array_equal(got["tokens"], want["tokens"])
        np.testing.assert_allclose(got["logits"], want["logits"], rtol=0,
                                   atol=2e-5)
        assert got["prompt_choices"].shape == (P, 2, 4)
        assert np.array_equal(got["prompt_choices"][first:],
                              want["prompt_choices"][first:])
        assert np.array_equal(got["expert_choices"], want["expert_choices"])
        for name in ("router_scores", "expert_weights"):
            np.testing.assert_allclose(got[name], want[name], rtol=0,
                                       atol=5e-6)
        if temperature == 0:        # (a sampled id's margin is no reading)
            reading = compare(cfg, p, got)
            assert reading["correct"] and reading["flipped"] == 0, reading
    # what the program COMPUTED: the 12 positions behind the prefix, whose
    # queries (a row's own) attend to the prefix's 37 keys and their own
    # causal part in each of four attentions; the decode steps read what
    # they read without a snapshot
    B = len(picked)
    assert (stats["prefill_positions"], full_stats["prefill_positions"]) \
        == (B * SUFFIX, B * P)
    for b, i in enumerate(picked):
        n = OWN[i]
        assert stats["keys_attended_prefill"][b] == 4 * sum(
            PREFIX + j + 1 for j in range(n))
        assert full_stats["keys_attended_prefill"][b] \
            == 4 * (PREFIX + n) * (PREFIX + n + 1) // 2
        for kind in ("local", "zero"):
            name = f"expert_pairs_{kind}_prefill"
            assert stats[name][b] <= full_stats[name][b] <= P * 2 * 4
        assert stats["expert_pairs_local_prefill"][b] \
            + stats["expert_pairs_zero_prefill"][b] <= SUFFIX * 2 * 4
    for name in ("keys_attended", "expert_pairs_local", "expert_pairs_zero",
                 "expert_hits", "expert_pairs_dropped"):
        assert np.array_equal(stats[name], full_stats[name]), name


def test_bf16_rows_behind_a_snapshot_are_the_references():
    """In the configuration's precision the absorbed suffix and the
    expanded whole prompt round differently, so the two paths' ids may
    part; each is held to the reference under its OWN choices by the
    limits the whole-prompt path is held to."""
    cfg, p = of_dtype("bfloat16")
    served, _ = serve_shared("bfloat16", snapshot_of("bfloat16"))
    assert snapshot_of("bfloat16")["keys"].dtype == jnp.bfloat16
    for b, row in enumerate(served):
        got = compare(cfg, p, row)
        assert got["correct"] and got["mean_over_std"] > 1e-4, (b, got)


@pytest.mark.parametrize("b", range(4))
def test_a_row_behind_a_snapshot_is_its_single_row_run(b):
    """Four rows of unequal length from one snapshot give, each, what
    they give alone through the 1-row program (whose suffix buffer they do
    not fill either: the padding lies in front of prefix and suffix
    both), whatever the other rows hold."""
    snapshot = snapshot_of("float32")
    served, _ = serve_shared("float32", snapshot)
    (alone,), _ = serve_shared("float32", snapshot, (b,))
    assert np.array_equal(alone["tokens"], served[b]["tokens"])
    np.testing.assert_allclose(served[b]["logits"], alone["logits"], rtol=0,
                               atol=2e-5)
    assert np.array_equal(alone["expert_choices"],
                          served[b]["expert_choices"])
    first = SUFFIX - OWN[b]
    assert np.array_equal(alone["prompt_choices"][first:],
                          served[b]["prompt_choices"][first:])


def _prefilled(snapshot=None):
    """The cache behind the prefill of `shared_prompts`' four rows, the
    routers' choices over the buffer and ``first``: the whole prompts from
    an empty cache, or their suffixes behind ``snapshot``."""
    cfg, p = of_dtype("float32")
    K = 0 if snapshot is None else PREFIX
    ids, lens = buffers((0, 1, 2, 3), K)

    def run(params, ids, lens, snapshot):
        S = ids.shape[1]
        first = S - lens
        cache = mla_scmoe.empty_cache(cfg, 4, K + S + NEW)
        if snapshot is not None:
            cache = mla_scmoe.from_prefix(cache, snapshot, first)
        _, cache, routed, _ = mla_scmoe._stack(
            cfg, params,
            mla_scmoe._embed(params, jax.vmap(jnp.roll)(ids, first)),
            jnp.arange(K, K + S), first, cache, absorbed=K > 0, prefix=K)
        return cache, routed[1], first

    return jax.jit(run)(p, ids, lens, snapshot)


def test_the_snapshot_is_what_the_whole_prefill_leaves_behind_the_prefix():
    """At the cache's width, one row and no axis of rows: 37 latents and
    rotary keys in each of the ``2 L`` slots, the ones the whole prefill of
    a longer prompt writes at those positions of each row (whatever its
    padding: a rotary key is rotated by the row's position), with the
    record of what the 37 positions chose; behind the suffix's prefill all
    four slots are the whole prefill's over every real position, each row's
    prefix at its own offset and nothing in front of it."""
    snapshot = snapshot_of("float32")
    assert {k: (v.shape, v.dtype) for k, v in snapshot.items()} == {
        "keys": ((4, PREFIX, 24), TINY.dtype),
        "choices": ((PREFIX, 2, 4), jnp.int32)}
    assert sum(v.nbytes for v in snapshot.values()) \
        == mla_scmoe.prefix_bytes(TINY, PREFIX) \
        == 4 * PREFIX * (16 + 8) * 4 + PREFIX * 2 * 4 * 4
    whole, whole_chosen, first = _prefilled()
    behind, chosen, same = _prefilled(snapshot)
    assert list(first) == list(same) == [SUFFIX - n for n in OWN]
    P = PREFIX + SUFFIX
    for b, at in enumerate(first):
        np.testing.assert_allclose(whole[:, b, at:at + PREFIX],
                                   snapshot["keys"], rtol=0, atol=1e-5)
        np.testing.assert_array_equal(behind[:, b, at:at + PREFIX],
                                      snapshot["keys"])
        np.testing.assert_allclose(behind[:, b, at:P], whole[:, b, at:P],
                                   rtol=0, atol=1e-5)
        assert float(jnp.abs(behind[:, b, :at]).max(initial=0)) == 0
        assert float(jnp.abs(behind[:, b, P:]).max()) == 0
        np.testing.assert_array_equal(whole_chosen[b, at:at + PREFIX],
                                      snapshot["choices"])
        # (the suffix's call records its own S positions alone)
        np.testing.assert_array_equal(chosen[b, at:],
                                      whole_chosen[b, PREFIX + at:])
    # the published size: 9,216 B a position and 192 B of choices behind
    # the cell's 1,951 ids
    full = mla_scmoe.LONGCAT_FLASH_OMNI_SHARE
    assert mla_scmoe.kv_cache_bytes(full, 1, 1951) == 1951 * 9216 \
        == 17_980_416
    assert mla_scmoe.prefix_bytes(full, 1951) - 17_980_416 \
        == 1951 * 4 * 12 * 4 == 374_592


def test_the_maker_is_not_the_served_program_and_the_phases_stay():
    """The maker is ``lm_prefix_state``: the cells' pattern for the served
    program (``^jit_lm_generate$``) does not match it, so its seconds are
    no execution's.  The program that starts from a snapshot is still
    ``lm_generate``, with every class and both phases; the rows' start
    from the snapshot is the cache's and the choices' assembly the
    router's."""
    cfg, p = of_dtype("float32")
    maker = mla_scmoe.make_prefix_program(cfg).lower(
        p, jnp.zeros((PREFIX,), jnp.int32))
    assert "jit_lm_prefix_state" in maker.as_text()[:200]
    assert not re.match("^jit_lm_generate$", "jit_lm_prefix_state")
    ids, lens = buffers((0, 1, 2, 3), PREFIX)
    lowered = mla_scmoe.make_program(cfg, 3).lower(
        p, jnp.asarray(ids), lens, np.zeros(4, np.uint32),
        np.zeros(4, np.float32), snapshot_of("float32"))
    assert "jit_lm_generate" in lowered.as_text()[:200]
    names = [n for n in re.findall(r'op_name="([^"]+)"',
                                   lowered.compile().as_text())
             if "LongcatFlash" in n]
    assert {trace.classify(n) for n in names} == LM_CLASSES
    assert {trace.phase_of(n) for n in names} == {"prefill", "decode"}
    copies = [n for n in names
              if re.search(r"prefill/(kv_cache|router)/", n)]
    assert {(trace.classify(n), trace.phase_of(n)) for n in copies} == {
        ("lm_cache", "prefill"), ("lm_experts", "prefill")}


def _rotated_from_the_buffers_index(monkeypatch):
    """A snapshot whose rotary keys were rotated from where row 2's prefix
    stands in the BUFFER (its offset 7 on) instead of from the row's own
    positions 0 .. 36: no row's suffix meets the keys it left."""
    cfg, p = of_dtype("float32")
    off, ids = SUFFIX - OWN[2], jnp.asarray(shared_prompts()[0][:PREFIX])
    _, cache, routed, _ = mla_scmoe._stack(
        cfg, p, mla_scmoe._embed(p, ids[None]), jnp.arange(off, off + PREFIX),
        jnp.zeros((1,), jnp.int32),
        mla_scmoe.empty_cache(cfg, 1, off + PREFIX), absorbed=False)
    return {"keys": cache[:, 0, off:], "choices": routed[1][0]}, (0, 1, 2, 3)


def _padded_slots_written(monkeypatch):
    """A shorter row's padded slots written over the end of its prefix
    (`lm_decode.own_entries` lets every new entry through): the row that
    fills its buffer has no padded slot and cannot tell."""
    monkeypatch.setattr(mla_scmoe.lm_decode, "own_entries",
                        lambda own, new, cache, l, at: new)
    return snapshot_of("float32"), (1, 2, 3)


@pytest.mark.parametrize("breakage", [_rotated_from_the_buffers_index,
                                      _padded_slots_written])
def test_a_snapshot_path_with_the_mechanism_broken_is_refused(breakage,
                                                              monkeypatch):
    """Prefix keys rotated from the buffer's index, and a padded slot
    overwriting the prefix's last entries: every row the breakage reaches
    fails the comparison the served path passes, the others are what they
    were."""
    cfg, p = of_dtype("float32")
    snapshot, broken = breakage(monkeypatch)
    # (a program of its own: the jitted one was traced with the mechanism)
    served, _ = serve_shared("float32", snapshot,
                             program=mla_scmoe.make_program(cfg, NEW))
    good, _ = serve_shared("float32", snapshot_of("float32"))
    for b in range(4):
        if b in broken:
            got = compare(cfg, p, served[b])
            assert not got["correct"] and got["max_over_std"] > 0.01, (b, got)
        else:
            np.testing.assert_allclose(served[b]["logits"], good[b]["logits"],
                                       rtol=0, atol=2e-5)


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
        + got["lm.expert_pairs_zero_prefill"] <= 4 * (32 - 7) * 2 * 4
    assert got["lm.expert_pairs_local_prefill"] \
        <= got["lm.expert_rows_computed_prefill"]
    # every row of the program, the padded one too, behind the snapshot
    assert got["lm.prefill_positions"] == 4 * (32 - 7)
    # the three real rows, from the snapshot the first request made
    assert got["lm.prefix_hits"] == 3
    assert got["lm.prefix_positions_served"] == 3 * 7
    assert got.get("lm.prefix_misses", 0) == 0 and before[
        "lm.prefix_misses"] >= 1
    real = got["lm.prompt_tokens"]                      # of three rows
    assert got["lm.keys_attended"] == 4 * (5 * real + 3 * (1 + 2 + 3 + 4 + 5))
    # a row's n own queries see the 7 prefix keys and their causal part
    # (the padded row repeats the first)
    own = [len(i) - 7 for _, o in out for i in [o.prompt_ids]]
    assert got["lm.keys_attended_prefill"] == 4 * sum(
        7 * n + n * (n + 1) // 2 for n in own + own[:1])
    gauges = trace.GLOBAL_GAUGES.snapshot()
    assert gauges["lm.kv_cache_bytes"] == \
        mla_scmoe.kv_cache_bytes(TINY, 4, 37) == 4 * 4 * 37 * 24 * 4
    words, lm_out = out[2]
    assert lm_out.row == 2 and set(lm_out.aux) == {
        "router_scores", "expert_choices", "expert_weights",
        "prompt_choices"}
    # the record covers the WHOLE prompt buffer, the snapshot's positions too
    assert lm_out.aux["prompt_choices"].shape == (4, 32, 2, 4)
    assert len(words.split()) <= 5


def lm_delta(before):
    after = counters()
    return {k[3:]: after[k] - before.get(k, 0) for k in after
            if k.startswith("lm.") and after[k] != before.get(k, 0)}


def asked(model, rows, **kw):
    before = counters()
    out = model.generate_rows(rows, max_new_tokens=3, prompt_tokens=32, **kw)
    return [words for words, _ in out], lm_delta(before)


GUIDE = "style guide number 0 of many"          # 7 ids with the first


def test_the_rule_finds_this_familys_prefix_and_its_snapshot_is_made_once(
        model, monkeypatch):
    """`shared_prefix` finds the instructions' ids for this family (a
    rotary key is rotated from a row's first real id: the snapshot stands
    at any offset); two executions make the snapshot once and count a hit
    a row; the words are those of the whole prompt prefilled (the rule
    held off) and of each row alone."""
    model._prefixes.clear()
    rows = [registry.LMRow(f"a walled garden in june number {i}", i, 0.7 * i,
                           instructions=GUIDE) for i in range(3)]
    found = model.shared_prefix(rows, 32)
    assert list(found) == model.tokenizer.encode(GUIDE) and len(found) == 7
    words, got = asked(model, rows)
    assert (got["prefix_misses"], got["prefix_hits"],
            got["prefix_positions_served"]) == (1, 3, 3 * 7)
    assert got["prefill_positions"] == 4 * 25
    assert trace.GLOBAL_GAUGES.snapshot()["lm.prefix_bytes"] \
        == mla_scmoe.prefix_bytes(TINY, 7) == 7 * (4 * 24 * 4 + 2 * 4 * 4)
    again, got = asked(model, rows[:2])
    assert again == words[:2] and "prefix_misses" not in got
    assert got["prefix_hits"] == 2 and got["prefill_positions"] == 4 * 25
    for i, row in enumerate(rows):
        alone, got = asked(model, [row])
        assert alone == [words[i]] and got["prefill_positions"] == 25
    monkeypatch.setattr(registry.LanguageModel, "shared_prefix",
                        lambda self, *a: None)
    whole, got = asked(model, rows)
    assert whole == words and len(set(words)) == 3
    assert "prefix_hits" not in got and got["prefill_positions"] == 4 * 32


@pytest.mark.parametrize("what, rows", [
    ("no instructions", [("a cat", "")] * 2),
    ("instructions that differ between the rows",
     [("a cat", GUIDE), ("a dog", GUIDE.replace("0", "1"))]),
    ("one row without", [("a cat", GUIDE), ("a dog", "")]),
])
def test_rows_that_share_no_instructions_run_the_whole_prompt(what, rows,
                                                              model):
    """The rule reads its input: everything but the same non-empty
    instructions in every row is the execution it was, every position
    computed, no snapshot made, none counted."""
    rows = [registry.LMRow(text, i, instructions=instructions)
            for i, (text, instructions) in enumerate(rows)]
    assert model.shared_prefix(rows, 32) is None
    _, got = asked(model, rows)
    assert got["prefill_positions"] == 4 * 32
    assert got["keys_attended_prefill"] == 4 * sum(
        n * (n + 1) // 2 for n in [
            len(model.prompt_ids(r.text, 32, r.instructions))
            for r in rows + rows[:1] * 2])
    assert not [k for k in got if k.startswith("prefix_")]


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
