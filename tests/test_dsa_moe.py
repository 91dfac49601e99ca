"""The decoder with a learned key selection and routed experts
(models/dsa_moe.py: an indexer beside each attention, the ``topk`` best
keys a query, an index-key cache beside the key-value cache, the expert
layer of models/mla_moe.py with every expert held) against its plain
reference (benchmarks/chip/reference/dsa_moe.py) on seeded weights, at a
tiny size: d 64, 3 blocks, 4 query heads over 2 key-value heads of 16, 2
index heads of 8, the 8 best keys a query in chunks of 4 queries, 8
experts top-2, V 512.  Prompts of 5 to 24 ids behind a buffer of 24 and
6 decoded tokens: one prompt shorter than ``topk`` (nothing is selected
until its decode has grown past 8 positions), the others three times as
long (five of six chunks select, and every decode step).

The comparison is verify_lm_dsa_moe.py's, the one the chip run uses at
the published widths: the reference forced to the program's expert
choices AND key selections (logits, router scores, and the reference's
own selection beside the program's), then free against free.  Each
breakage the issue names has to fail it where the served path passes.
"""

import dataclasses
import functools
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import dsa_moe, layers, looplm, \
    mla_moe, registry
from comfyui_distributed_tpu.utils import trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sys.path.insert(0, BENCH)
ref = _load("dsa_moe_reference",
            os.path.join(BENCH, "reference", "dsa_moe.py"))
verify = _load("chipbench_verify_lm_dsa_moe",
               os.path.join(BENCH, "verify_lm_dsa_moe.py"))
verify_lm = _load("chipbench_verify_lm_for_dsa",
                  os.path.join(BENCH, "verify_lm.py"))

TINY = dsa_moe.TINY_DSA_MOE
NEW, PAD_TO = 6, 24
LENS = [19, 24, 5, 21]      # PAD_TO = 24: one row has no padding, one is
#                             shorter than topk = 8

# The limits for the tiny model in bf16 (the chip's, at width 2048, are
# verify_lm_dsa_moe.LIMITS).  Why the served path differs from the
# float32 reference at all: its matmul operands and all three caches are
# bf16, the head norms' gains of 2 make the scores N(0, 16), and three
# blocks of width 64 add their roundings up.  Measured here (weight seeds
# 7 and 11, rows alone and four together):
#
#                    mean_over_std   max_over_std  router scores  index margin
#   served           0.0051-0.0086   0.022-0.061   0.0028-0.0064  0-0.034
#   caches in 8 bits 0.033-0.056     0.20-0.44     0.019-0.037    0-0.75
#
# and the program's selection is the reference's own on 98.8-100% of the
# keys under forced upstream choices.  Each limit is the geometric mean of
# the two readings next to it.
BF16 = {"limits": {"max_over_std": 0.11, "mean_over_std": 0.017,
                   "margin_over_std": 0.22},
        "tolerance": 0.011, "margin": 0.1}
FP32 = {"limits": verify_lm.LIMITS_FP32,
        "tolerance": verify.ROUTER_TOLERANCE_FP32,
        "margin": verify.INDEX_MARGIN_FP32}


def of_dtype(dtype, seed=7, cfg=TINY):
    cfg = dataclasses.replace(cfg, dtype=jnp.dtype(dtype))
    return cfg, dsa_moe.seeded_params(cfg, np.uint32(seed))


def prompts(lens, vocab=TINY.vocab_size, seed=3):
    rng = np.random.RandomState(seed)
    ids = np.zeros((len(lens), PAD_TO), np.int32)
    for b, n in enumerate(lens):
        ids[b, :n] = rng.randint(1, vocab, n)
    return ids


def serve_rows(cfg, params, lens, new=NEW, temperature=None, empty=None,
               ids=None):
    """The program over rows of ``lens`` real ids; each row as the save
    node would write it.  ``empty`` wraps `dsa_moe.empty_cache`."""
    ids = prompts(lens) if ids is None else ids
    real = dsa_moe.empty_cache
    if empty is not None:
        dsa_moe.empty_cache = lambda *a: empty(real(*a))
    try:
        tokens, logits, aux, stats = dsa_moe.make_program(cfg, new)(
            params, ids, np.asarray(lens, np.int32),
            np.arange(len(lens), dtype=np.uint32) + 5,
            np.asarray(temperature or [0.0] * len(lens), np.float32))
    finally:
        dsa_moe.empty_cache = real
    return [{"prompt_ids": ids[b, :n], "tokens": np.asarray(tokens[b]),
             "logits": np.asarray(logits[b]),
             **{k: np.asarray(v[b]) for k, v in aux.items()}}
            for b, n in enumerate(lens)], jax.tree_util.tree_map(
                np.asarray, stats)


_REFERENCES = {}


def reference(cfg, **kw):
    key = (cfg.num_hidden_layers, cfg.experts_first, cfg.experts_held,
           tuple(sorted(kw.items())))
    if key not in _REFERENCES:
        _REFERENCES[key] = verify.Reference(verify.reference_config(cfg),
                                            **kw)
    return _REFERENCES[key]


def compare(cfg, params, served, free=True, sampled=False, **kw):
    """(A sampled row's token is not the largest logit's: its margin is
    not a reading.)"""
    held = BF16 if cfg.dtype == jnp.bfloat16 else FP32
    limits = {k: v for k, v in held["limits"].items()
              if not (sampled and k == "margin_over_std")}
    return verify.compare_request(reference(cfg, **kw), params, served,
                                  limits, held["tolerance"], held["margin"],
                                  free)


@pytest.fixture(scope="module")
def params():
    return of_dtype("float32")[1]


# --- against the reference -----------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows", [1, 4])
def test_prefill_then_decode_through_both_caches_match_the_reference(
        rows, dtype):
    """Prefill (chunks of 4 queries, five of six selecting) then decode
    (every cached index key scored, 8 keys gathered) = the reference's
    full forward pass with its sort, for rows of unequal length, a prompt
    longer and one shorter than ``topk``, greedy and sampled: logits, not
    tokens; and the program's selection IS the reference's."""
    cfg, p = of_dtype(dtype)
    lens = LENS[:rows]
    heat = [0.0, 0.0, 0.9, 0.7][:rows]
    served, stats = serve_rows(cfg, p, lens, temperature=heat)
    for row, t in zip(served, heat):
        got = compare(cfg, p, row, sampled=t > 0)
        assert got["correct"], got
        if dtype == "float32":
            assert got["selection_agree"] == 1.0 and got["flipped"] == 0
            assert got["free"]["selection_agree"] == 1.0
            assert got["free"]["expert_choices_agree"] == 1.0
            assert got["free"]["max_over_std"] < 1e-4
    assert stats["expert_pairs_dropped"] == 0
    # what a decode step read: its row's visible index keys scored, and
    # min(visible, topk) keys attended to, a block
    for b, n in enumerate(lens):
        sees = [n + i + 1 for i in range(NEW)]
        assert stats["keys_scored"][b] == 3 * sum(sees)
        assert stats["keys_attended"][b] == 3 * sum(min(s, 8) for s in sees)
        assert stats["keys_selected"][b] == 3 * sum(8 for s in sees if s > 8)
        # and the prefill: the triangle less the queries of the first two
        # chunks (they see no more than 8 positions of the buffer)
        first = PAD_TO - n
        seen = [t - first + 1 for t in range(first, PAD_TO)]
        assert stats["keys_attended_prefill"][b] == 3 * sum(
            min(s, 8) for s in seen)
        assert stats["keys_scored_prefill"][b] == 3 * sum(
            t - first + 1 for t in range(max(first, 8), PAD_TO))
        assert stats["keys_selected_prefill"][b] == 3 * sum(
            8 for s in seen if s > 8)
    assert stats["prefill_positions"] == rows * PAD_TO


def test_a_row_of_a_shared_execution_is_its_single_row_run(params):
    four, _ = serve_rows(TINY, params, LENS)
    for b, n in enumerate(LENS):
        alone, _ = serve_rows(TINY, params, [n], ids=prompts(LENS)[b:b + 1])
        assert np.array_equal(alone[0]["tokens"], four[b]["tokens"])
        np.testing.assert_allclose(alone[0]["logits"], four[b]["logits"],
                                   rtol=0, atol=2e-5)
        assert np.array_equal(alone[0]["key_selections"],
                              four[b]["key_selections"])
        assert np.array_equal(alone[0]["prompt_selected"],
                              four[b]["prompt_selected"])


def test_padding_is_never_selected(params):
    """The packed record of a row's prompt: no query selects a padded
    position, a padded query selects nothing, a real query selects
    min(visible, topk) keys."""
    served, _ = serve_rows(TINY, params, LENS)
    for row, n in zip(served, LENS):
        first = PAD_TO - n
        bits = np.unpackbits(row["prompt_selected"].view(np.uint8), axis=-1,
                             bitorder="little")[..., :PAD_TO].astype(bool)
        assert not bits[:, :, :first].any() and not bits[:first].any()
        want = np.minimum(np.arange(PAD_TO) - first + 1, 8)[first:]
        assert (bits[first:].sum(axis=-1) == want[:, None]).all()
        chosen = row["key_selections"]
        assert chosen[chosen >= 0].min() >= first


# --- a prefix shared between requests --------------------------------------------
#
# Every row's prompt is the same 37 ids (no multiple of 32 or of the chunk
# of 4 queries: the prefix's record ends inside a word and inside a chunk)
# and then its own: 40, 39, 9 and 1 ids behind them in a suffix buffer of
# 40, ten chunks (one row fills it, one is a single id; their offsets in
# the buffer are 0, 1, 31 and 39: no shift, one bit, a word less a bit, a
# word and seven bits).  The whole prompt is 77 positions.

PREFIX, SUFFIX = 37, 40
OWN = [40, 39, 9, 1]


def shared_prompts():
    """Per row the whole prompt: the same 37 ids, then the row's own."""
    rng = np.random.RandomState(12)
    head = rng.randint(1, TINY.vocab_size, PREFIX)
    return [np.concatenate([head, rng.randint(1, TINY.vocab_size, n)]
                           ).astype(np.int32) for n in OWN]


@functools.lru_cache(maxsize=None)
def shared_programs(cfg):
    """The maker and the served program, jitted once a configuration."""
    return dsa_moe.make_prefix_program(cfg), dsa_moe.make_program(cfg, NEW)


@pytest.fixture(scope="module")
def snapshot(params):
    return shared_programs(TINY)[0](
        params, jnp.asarray(shared_prompts()[0][:PREFIX]))


def buffers(picked, held):
    """The prompt buffer of the rows ``picked`` with their first ``held``
    ids left out (a snapshot stands for them), and the lengths."""
    whole = shared_prompts()
    ids = np.zeros((len(picked), PREFIX + SUFFIX - held), np.int32)
    for b, i in enumerate(picked):
        ids[b, :len(whole[i]) - held] = whole[i][held:]
    return ids, np.asarray([len(whole[i]) - held for i in picked], np.int32)


def serve_shared(cfg, params, snapshot, picked=(0, 1, 2, 3), temperature=0.0):
    """One execution over the rows ``picked`` of `shared_prompts`: from
    the ``snapshot`` of the 37 ids, or with None the whole prompts through
    the five-argument program."""
    ids, lens = buffers(picked, 0 if snapshot is None else PREFIX)
    tokens, logits, aux, stats = shared_programs(cfg)[1](
        params, ids, lens, np.asarray(picked, np.uint32) + 3,
        np.asarray([temperature] * len(picked), np.float32),
        *(() if snapshot is None else (snapshot,)))
    whole = shared_prompts()
    return [{"prompt_ids": whole[i], "tokens": np.asarray(tokens[b]),
             "logits": np.asarray(logits[b]),
             **{k: np.asarray(v[b]) for k, v in aux.items()}}
            for b, i in enumerate(picked)], jax.tree_util.tree_map(
                np.asarray, stats)


@pytest.mark.parametrize("picked", [(0,), (3,), (0, 1, 2, 3), (2, 1, 1, 1)])
@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_rows_started_from_a_snapshot_are_the_full_paths_and_the_references(
        picked, temperature, params, snapshot):
    """Alone or four of unequal length (a row that fills the suffix
    buffer, a single id, a padded execution whose last rows repeat one),
    greedy or sampled with seeds: the ids of the program over the whole
    prompt, its logits to float32's rounding, and over each row's REAL
    positions the same record of the experts chosen and of the keys every
    query selected, by buffer index; and the reference forced to that
    record agrees (verify_lm_dsa_moe's comparison, which reads all of
    it)."""
    served, stats = serve_shared(TINY, params, snapshot, picked, temperature)
    full, full_stats = serve_shared(TINY, params, None, picked, temperature)
    P = PREFIX + SUFFIX
    for got, want, i in zip(served, full, picked):
        first = SUFFIX - OWN[i]
        assert np.array_equal(got["tokens"], want["tokens"])
        np.testing.assert_allclose(got["logits"], want["logits"], rtol=0,
                                   atol=2e-5)
        assert got["prompt_choices"].shape == (P, 3, 2)
        assert got["prompt_selected"].shape == (P, 3, 3)
        for name in ("prompt_choices", "prompt_selected"):
            assert np.array_equal(got[name][first:], want[name][first:]), name
        for name in ("key_selections", "expert_choices"):
            assert np.array_equal(got[name], want[name]), name
        np.testing.assert_allclose(got["router_scores"],
                                   want["router_scores"], rtol=0, atol=1e-6)
        reading = compare(TINY, params, got, free=False,
                          sampled=temperature > 0)
        assert reading["correct"] and reading["selection_agree"] == 1.0, \
            reading
    # what the program COMPUTED: the 40 positions behind the prefix, whose
    # queries (a row's own) score the prefix's index keys too and attend to
    # 8 keys each; the decode steps read what they read without a snapshot
    B = len(picked)
    assert (stats["prefill_positions"], full_stats["prefill_positions"]) \
        == (B * SUFFIX, B * P)
    for b, i in enumerate(picked):
        n = OWN[i]
        assert stats["keys_scored_prefill"][b] == 3 * sum(
            PREFIX + j + 1 for j in range(n))
        assert stats["keys_selected_prefill"][b] \
            == stats["keys_attended_prefill"][b] == 3 * 8 * n
        assert stats["expert_pairs_local_prefill"][b] == SUFFIX * 3 * 2
        assert full_stats["expert_pairs_local_prefill"][b] == P * 3 * 2
        assert full_stats["keys_attended_prefill"][b] == 3 * sum(
            min(t + 1, 8) for t in range(PREFIX + n))
    for name in ("keys_scored", "keys_selected", "keys_attended",
                 "expert_pairs_local", "expert_hits",
                 "expert_pairs_dropped"):
        assert np.array_equal(stats[name], full_stats[name]), name


def test_a_row_behind_a_snapshot_is_its_single_row_run(params, snapshot):
    """Four rows of unequal length from one snapshot give, each, what
    they give alone through the 1-row program (whose suffix buffer they do
    not fill either: the padding lies in front of prefix and suffix
    both)."""
    served, _ = serve_shared(TINY, params, snapshot)
    for b in range(4):
        (alone,), _ = serve_shared(TINY, params, snapshot, (b,))
        assert np.array_equal(alone["tokens"], served[b]["tokens"]), b
        np.testing.assert_allclose(served[b]["logits"], alone["logits"],
                                   rtol=0, atol=2e-5)
        for name in ("key_selections", "prompt_selected", "prompt_choices"):
            assert np.array_equal(alone[name], served[b][name]), (b, name)


def _prefilled(cfg, params, snapshot=None):
    """The three caches behind the prefill of `shared_prompts`' four rows
    and ``first``: the whole prompts from empty caches, or their suffixes
    behind ``snapshot``."""
    K = 0 if snapshot is None else PREFIX
    ids, lens = buffers((0, 1, 2, 3), K)

    def run(params, ids, lens, snapshot):
        S = ids.shape[1]
        first = S - lens
        index = jnp.arange(K, K + S)
        caches = dsa_moe.empty_cache(cfg, 4, K + S + NEW)
        if snapshot is not None:
            caches = dsa_moe.from_prefix(caches, snapshot, first)
        _, caches, _ = dsa_moe._stack(
            cfg, params, dsa_moe._embed(params, jax.vmap(jnp.roll)(ids, first)),
            dsa_moe.text_positions(index, first), index, first, caches,
            decode=False, prefix=K)
        return caches, first

    return jax.jit(run)(params, ids, lens, snapshot)


def test_the_snapshot_is_what_the_full_prefill_leaves_behind_the_prefix(
        params, snapshot):
    """At the caches' widths, one row and no axis of rows: 37 keys, values
    and index keys a block, the ones the full prefill of a longer prompt
    writes at those positions of each row, with the record of what the 37
    positions chose; behind the suffix's prefill all three caches are the
    full prefill's over every real position, each row's prefix at its own
    offset and nothing in front of it."""
    words = -(-PREFIX // 32)
    assert {k: (v.shape, v.dtype) for k, v in snapshot.items()} == {
        "keys": ((3, PREFIX, 2, 16), TINY.dtype),
        "values": ((3, PREFIX, 2, 16), TINY.dtype),
        "index_keys": ((3, PREFIX, 8), TINY.dtype),
        "choices": ((PREFIX, 3, 2), jnp.int32),
        "selected": ((PREFIX, 3, words), jnp.uint32)}
    assert sum(v.nbytes for v in snapshot.values()) \
        == dsa_moe.prefix_bytes(TINY, PREFIX) \
        == dsa_moe.kv_cache_bytes(TINY, 1, PREFIX) \
        + PREFIX * 3 * 4 * (2 + words)
    whole, first = _prefilled(TINY, params)
    behind, same = _prefilled(TINY, params, snapshot)
    assert list(first) == list(same) == [SUFFIX - n for n in OWN]
    for name, full, got in zip(dsa_moe.PREFIX_CACHES, whole, behind):
        for b, at in enumerate(first):
            np.testing.assert_allclose(
                full[:, b, at:at + PREFIX], snapshot[name], rtol=0, atol=1e-5)
            np.testing.assert_array_equal(got[:, b, at:at + PREFIX],
                                          snapshot[name])
            np.testing.assert_allclose(
                got[:, b, at:PREFIX + SUFFIX], full[:, b, at:PREFIX + SUFFIX],
                rtol=0, atol=1e-5)
            assert float(jnp.abs(got[:, b, :at]).max(initial=0)) == 0
    # the published size: 106 MB of caches and 51 MB of records behind the
    # cell's 8,101 ids
    full = dsa_moe.KEYE_VL2_STAGE
    assert dsa_moe.kv_cache_bytes(full, 1, 8101) == 13_056 * 8101
    assert dsa_moe.prefix_bytes(full, 8101) - 13_056 * 8101 \
        == 8101 * 6 * 4 * (8 + 254) == 50_939_088


@pytest.mark.parametrize("positions", [37, 64, 5])
@pytest.mark.parametrize("by", [0, 1, 31, 32, 33, 39, 95])
def test_the_packed_record_moves_up_by_a_rows_offset(by, positions):
    """A funnel shift over uint32 words: bit ``s`` of a snapshot's record
    (a key by its position in the row) is bit ``s + first`` of the buffer's
    (``first % 32`` is rarely 0, and 37 keys end inside a word); no bit is
    lost within the buffer's words and none appears."""
    rng = np.random.RandomState(by)
    mask = rng.rand(4, 3, positions) < 0.5
    mask[0, 0, :] = True
    width = -(-(positions + 96) // 32)
    packed = dsa_moe._pack(jnp.asarray(mask), -(-positions // 32))
    moved = np.asarray(jax.jit(dsa_moe._shift_up, static_argnums=2)(
        packed, jnp.int32(by), width))
    assert moved.shape == (4, 3, width) and moved.dtype == np.uint32
    bits = np.unpackbits(moved.view(np.uint8), axis=-1, bitorder="little")
    want = np.zeros((4, 3, 32 * width), bool)
    want[..., by:by + positions] = mask
    np.testing.assert_array_equal(bits.astype(bool), want)


def test_the_maker_is_not_the_served_program_and_the_phases_stay(
        params, snapshot):
    """The maker is ``lm_prefix_state``: the cells' pattern for the served
    program (``^jit_lm_generate$``) does not match it, so its seconds are
    no execution's.  The program that starts from a snapshot is still
    ``lm_generate``, with every class and both phases; the rows' start
    from the snapshot is the caches' and the records' assembly is the
    index's and the router's."""
    maker = dsa_moe.make_prefix_program(TINY).lower(
        params, jnp.zeros((PREFIX,), jnp.int32))
    assert "jit_lm_prefix_state" in maker.as_text()[:200]
    assert not re.match("^jit_lm_generate$", "jit_lm_prefix_state")
    ids, lens = buffers((0, 1, 2, 3), PREFIX)
    lowered = dsa_moe.make_program(TINY, 3).lower(
        params, jnp.asarray(ids), lens, np.zeros(4, np.uint32),
        np.zeros(4, np.float32), snapshot)
    assert "jit_lm_generate" in lowered.as_text()[:200]
    names = [n for n in re.findall(r'op_name="([^"]+)"',
                                   lowered.compile().as_text())
             if "KeyeVL2" in n]
    assert {trace.classify(n) for n in names} == {
        "lm_proj", "lm_attn", "lm_cache", "lm_index", "lm_experts",
        "lm_mlp", "lm_norm", "lm_head", "embed"}
    assert {trace.phase_of(n) for n in names} == {"prefill", "decode"}
    copies = [n for n in names if re.search(
        r"prefill/(kv_cache|selection_record|gate)/", n)]
    assert {(trace.classify(n), trace.phase_of(n)) for n in copies} == {
        ("lm_cache", "prefill"), ("lm_index", "prefill"),
        ("lm_experts", "prefill")}


# --- the rotation over position triples ------------------------------------------

def test_equal_components_are_the_one_dimensional_rotation():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 7, 4, 16))
    at = jnp.asarray([[0, 1, 2, 3, 4, 5, 6], [3, 4, 5, 6, 7, 8, 9]])
    triple = jnp.broadcast_to(at[None], (3, 2, 7))
    assert np.array_equal(
        dsa_moe._mrope(x, triple, 1e7, (2, 3, 3)), looplm._rope(x, at, 1e7))
    np.testing.assert_array_equal(
        dsa_moe.text_positions(jnp.arange(4, 9), jnp.asarray([2, 4])),
        np.broadcast_to(np.asarray([[2, 3, 4, 5, 6], [0, 1, 2, 3, 4]]),
                        (3, 2, 5)))


@pytest.mark.parametrize("width, sections", [(16, (2, 3, 3)),
                                             (128, (16, 24, 24)),
                                             (64, (16, 24, 24))])
def test_unequal_components_are_the_references(width, sections):
    """An image token's triple: the frequency pairs of a section turn by
    that section's component (the index head's 32 pairs take the
    sections halved)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 9, 2, width))
    triple = jnp.asarray(np.random.RandomState(2).randint(0, 50, (3, 1, 9)))
    got = dsa_moe._mrope(x, triple, 1e7, sections)
    want = ref.rope3(x[0], triple[:, 0], 1e7, sections)
    np.testing.assert_allclose(got[0], want, rtol=0, atol=1e-5)
    one = jnp.broadcast_to(triple[:1], triple.shape)
    assert not np.allclose(got, dsa_moe._mrope(x, one, 1e7, sections),
                           atol=1e-3)


# --- breakages ---------------------------------------------------------------------

BREAKAGES = ref.BREAKAGES + verify.ROUTER_BREAKAGES


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("what", BREAKAGES)
def test_a_reference_with_the_mechanism_broken_is_refused(what, dtype):
    """No selection, the last ``topk`` keys, the ReLU dropped, the heads'
    weights dropped, the top-1 of 2 experts (the published model's top-7
    of 8), a softmax router without renormalisation: the served path
    passes against the reference and fails against each."""
    cfg, p = of_dtype(dtype)
    served, _ = serve_rows(cfg, p, LENS[:1])
    row = served[0]
    assert compare(cfg, p, row, free=False)["correct"]
    held = BF16 if dtype == "bfloat16" else FP32
    ids, rows = verify_lm.rows_of(row)
    logits, _, _, _ = reference(cfg, breakage=what).forward(
        p, ids, rows, verify.program_selections(row),
        verify.program_choices(row), force=what in verify.ROUTER_BREAKAGES)
    got = verify_lm.compare_logits(row["logits"], logits, row["tokens"],
                                   held["limits"])
    assert not got["correct"] and got["mean_over_std"] > 0.05, got


def _fp8(caches):
    return tuple(c.astype(jnp.float8_e4m3fn) for c in caches)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_caches_in_eight_bits_are_refused(dtype):
    cfg, p = of_dtype(dtype)
    for row in serve_rows(cfg, p, LENS, empty=_fp8)[0]:
        got = compare(cfg, p, row, free=False)
        assert not got["correct"], got
        assert not got["logits_correct"] and not got["routing_correct"]


def test_weights_in_eight_bits_are_refused():
    cfg, p = of_dtype("bfloat16")
    row = serve_rows(cfg, p, LENS[:1])[0][0]
    ids, rows = verify_lm.rows_of(row)
    logits, scores, _, _ = reference(
        cfg, weights_dtype=jnp.float8_e4m3fn).forward(
        p, ids, rows, verify.program_selections(row),
        verify.program_choices(row), force=True)
    got = compare(cfg, p, {**row, "logits": logits, "router_scores": scores},
                  free=False)
    assert not got["logits_correct"], got


# --- the share ---------------------------------------------------------------------

def test_the_stage_is_the_uncut_models_first_blocks():
    """Depth is the one cut: a stage of 3 blocks of an uncut tiny model of
    5 holds the SAME leaves (its layers' first three slices, embedding,
    norm and head whole) and its logits are the uncut reference's at that
    depth."""
    uncut = dataclasses.replace(TINY, num_hidden_layers=5)
    whole = dsa_moe.seeded_params(uncut, np.uint32(9))
    stage = {**whole, "layers": jax.tree_util.tree_map(
        lambda w: w[:3], whole["layers"])}
    assert jax.tree_util.tree_map(lambda w: w.shape, stage) == \
        jax.tree_util.tree_map(lambda w: w.shape, of_dtype("float32")[1])
    served, _ = serve_rows(TINY, stage, LENS[:1])
    row = served[0]
    ids, rows = verify_lm.rows_of(row)
    at_depth, _, _, _ = ref.forward(verify.reference_config(uncut), whole,
                                    ids, layers=3)
    through, _, _, _ = ref.forward(verify.reference_config(uncut), whole,
                                   ids)
    np.testing.assert_allclose(row["logits"], np.asarray(at_depth)[rows],
                               rtol=0, atol=5e-5)
    assert np.abs(np.asarray(through)[rows] - row["logits"]).max() > 0.1


def test_the_experts_parts_over_two_halves_add_up_to_the_whole_layer(params):
    """Expert parallelism is not this configuration's cut, and the layer
    can still be told which experts it holds: experts 0..3 and 4..7 of
    the 8 give parts that add up to the whole layer's, in the program and
    in the reference, and the whole layer is the reference's."""
    lp = {k: v[1] for k, v in params["layers"].items()
          if not isinstance(v, dict)}
    n = jax.random.normal(jax.random.PRNGKey(4), (2, 6, 64))
    whole, ((scores, chosen), counts) = mla_moe._moe(
        TINY, lp, params["layers"]["experts"], jnp.int32(1), n)
    assert int(counts[0].sum()) == 2 * 6 * 2 and int(counts[2]) == 0
    parts = []
    for first in (0, 4):
        cfg = dataclasses.replace(TINY, experts_first=first, experts_held=4)
        experts = {k: v[:, first:first + 4]
                   for k, v in params["layers"]["experts"].items()}
        parts.append(mla_moe._moe(cfg, lp, experts, jnp.int32(1), n)[0])
    np.testing.assert_allclose(parts[0] + parts[1], whole, atol=1e-5)
    config = verify.reference_config(TINY)
    x = n.reshape(12, 64)
    own = {k: ref.f32(v[1]) for k, v in params["layers"]["experts"].items()}
    s, c = ref.router(config, ref.f32(lp["gate"]), x)
    np.testing.assert_allclose(scores.reshape(12, -1), s, atol=1e-6)
    want = ref.routed(config, own, range(8), x, s, c)
    np.testing.assert_allclose(whole.reshape(12, 64), want, atol=1e-5)
    halves = [ref.routed(config, {k: v[a:a + 4] for k, v in own.items()},
                         range(a, a + 4), x, s, c) for a in (0, 4)]
    np.testing.assert_allclose(halves[0] + halves[1], want, atol=1e-5)


def test_the_router_scores_by_the_configuration():
    """``softmax`` over all experts, the chosen renormalised, no scaling
    factor (this family); ``sigmoid`` times 2.5 (the other two), which
    the new branch leaves as it was."""
    n = jax.random.normal(jax.random.PRNGKey(5), (7, 64))
    gate = jax.random.normal(jax.random.PRNGKey(6), (64, 8)) / 8
    scores, chosen, weights = mla_moe.route(TINY, gate, n)
    np.testing.assert_allclose(scores, jax.nn.softmax(n @ gate), atol=1e-6)
    np.testing.assert_allclose(scores.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-6)
    assert chosen.shape == (7, 2)
    free = dataclasses.replace(TINY, norm_topk_prob=False)
    assert float(mla_moe.route(free, gate, n)[2].sum(-1).max()) < 0.9
    other = mla_moe.TINY_MLA_MOE
    assert other.scoring_func == "sigmoid"
    gate16 = jax.random.normal(jax.random.PRNGKey(7), (64, 16)) / 8
    s, _, w = mla_moe.route(other, gate16, n)
    np.testing.assert_allclose(s, jax.nn.sigmoid(n @ gate16), atol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 2.5, atol=1e-5)


# --- the selection, alone ------------------------------------------------------------

def _sorted_selection(scores, valid, k):
    """The oracle: a stable sort, descending, the first ``k`` valid."""
    keyed = np.where(valid, scores, -np.inf)
    order = np.argsort(-keyed, axis=-1, kind="stable")
    rank = np.argsort(order, axis=-1, kind="stable")
    return valid & (rank < k)


@pytest.mark.parametrize("case", ["random", "ties", "few_valid", "none_valid",
                                  "negative", "all_equal"])
def test_the_threshold_search_selects_what_a_stable_sort_selects(case):
    rng = np.random.RandomState(0)
    scores = rng.randn(3, 5, 40).astype(np.float32)
    valid = rng.rand(3, 5, 40) < 0.8
    if case == "ties":
        scores = np.round(scores * 2) / 2   # many equal scores, -0. and 0.
        assert np.signbit(scores[scores == 0]).any()
    elif case == "few_valid":
        valid = rng.rand(3, 5, 40) < 0.15           # fewer than k valid
    elif case == "none_valid":
        valid[1] = False
    elif case == "negative":
        scores = -np.abs(scores) - 1.0
    elif case == "all_equal":
        scores[:] = 0.25
    got = np.asarray(dsa_moe.select_keys(jnp.asarray(scores),
                                         jnp.asarray(valid), 8))
    np.testing.assert_array_equal(got, _sorted_selection(scores, valid, 8))
    assert (got.sum(-1) == np.minimum(valid.sum(-1), 8)).all()


def test_the_selection_record_packs_and_unpacks():
    rng = np.random.RandomState(1)
    mask = rng.rand(2, 3, 45) < 0.5
    words = np.asarray(dsa_moe._pack(jnp.asarray(mask), 2))
    assert words.shape == (2, 3, 2) and words.dtype == np.uint32
    bits = np.unpackbits(words.view(np.uint8), axis=-1, bitorder="little")
    np.testing.assert_array_equal(bits[..., :45].astype(bool), mask)
    assert not bits[..., 45:].any()
    at = np.asarray(dsa_moe._indices_of(jnp.asarray(mask[0]), 48))
    for row, got in zip(mask[0], at):
        assert sorted(got[got >= 0]) == list(np.nonzero(row)[0])
        assert (got[row.sum():] == -1).all()


@pytest.mark.parametrize("selected, want", [
    (None, [[[1, 1, 0, 0], [1, 1, 1, 0]], [[0, 1, 0, 0], [0, 1, 1, 0]]]),
    ([[[1, 0, 1, 1]], [[0, 0, 1, 1]]],
     [[[1, 0, 0, 0], [1, 0, 1, 0]], [[0, 0, 0, 0], [0, 0, 1, 0]]]),
])
def test_the_mask_of_a_selecting_call(selected, want):
    """`visible_keys` with a selection: of the keys a query may see, the
    ones selected for it (a selection of one row a position broadcasts
    over the position's queries)."""
    sel = None if selected is None else jnp.asarray(selected, bool)
    got = layers.visible_keys(4, jnp.asarray([1, 2]), jnp.asarray([0, 1]),
                              selected=sel)
    np.testing.assert_array_equal(got, np.asarray(want, bool))


def test_attention_under_a_selection_is_attention_over_the_gathered_keys():
    """The prefill's masked call and the decode's gathered call are one
    mathematics; a chunked walk over the queries carries the selection
    with them."""
    key = jax.random.PRNGKey(8)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), s)
               for i, s in enumerate([(2, 256, 2, 8), (2, 256, 2, 8),
                                      (2, 256, 2, 8)]))
    at = jnp.arange(256)
    seen = np.asarray(layers.visible_keys(256, at, jnp.asarray([0, 3])))
    rng = np.random.RandomState(9)
    sel = jnp.asarray(seen & (rng.rand(2, 256, 256) < 0.3)
                      | np.eye(256, dtype=bool)[None] & seen)
    whole = layers.xla_attention(q, k, v, 0.35, at, jnp.asarray([0, 3]),
                                 selected=sel)
    import unittest.mock as mock
    with mock.patch.object(layers, "_query_chunk", lambda *a: 64):
        walked = layers.xla_attention(q, k, v, 0.35, at, jnp.asarray([0, 3]),
                                      selected=sel)
    np.testing.assert_allclose(whole, walked, atol=1e-6)
    # one query, its keys gathered
    t, b = 200, 1
    idx = np.nonzero(np.asarray(sel)[b, t])[0]
    live = jnp.ones((1, 1, len(idx)), bool)
    gathered = layers.xla_attention(q[b:b + 1, t:t + 1], k[b:b + 1, idx],
                                    v[b:b + 1, idx], 0.35, selected=live)
    np.testing.assert_allclose(gathered[0, 0], whole[b, t], atol=1e-5)


def test_the_attention_rule_names_the_selected_and_the_gathered_calls():
    for platform in ("tpu", "cpu"):
        assert layers.attention_path(platform, 4, 512, 8192, 32, masked=True,
                                     selected=True) == "xla_selected"
        assert layers.attention_path(platform, 4, 1, 2048, 32, masked=True,
                                     selected=True) == "xla_gathered"
        # what it said before, it says
        assert layers.attention_path(platform, 4, 1, 128, 64, masked=True,
                                     banded=True) == "xla_ring"
        assert layers.attention_path(platform, 4, 512, 512, 64, masked=True,
                                     banded=True) == "xla_banded"
        assert layers.attention_path(platform, 4, 1, 2112, 32,
                                     masked=True) == "xla_decode"
        assert layers.attention_path(platform, 4, 2048, 2048, 32,
                                     masked=True) == "xla_causal"
    assert layers.attention_path("tpu", 2, 4096, 4096, 10) == "fused"
    assert layers.attention_path("cpu", 2, 4096, 4096, 10) == "xla_chunked"
    assert layers.attention_path("tpu", 2, 4096, 77, 10) == "xla_whole"


def test_a_trace_counts_the_two_new_paths(params):
    before = trace.ATTENTION_PATHS.snapshot()
    dsa_moe.make_program(TINY, 2).trace(
        params, jnp.zeros((1, 12), jnp.int32), np.zeros(1, np.int32) + 9,
        np.zeros(1, np.uint32), np.zeros(1, np.float32))
    after = trace.ATTENTION_PATHS.snapshot()
    got = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    # a block's prefill: chunks of 4 over 12 positions, two within topk = 8
    # and one selecting; its decode step gathers
    assert (got["xla_causal"], got["xla_selected"], got["xla_gathered"]) \
        == (2, 1, 1)


# --- the published stage ---------------------------------------------------------------

def test_the_published_stage_is_the_issues_arithmetic():
    full = dsa_moe.KEYE_VL2_STAGE
    d = 2048
    attention = d * 4096 + 2 * d * 512 + 4096 * d
    indexer = d * 1024 + d * 64 + d * 16
    experts = 128 * 3 * d * 768
    assert (attention, indexer, experts) == (18_874_368, 2_260_992,
                                             603_979_776)
    block = attention + 256 + indexer + 128 + d * 128 + 2 * d + experts
    assert block == 625_381_760
    assert dsa_moe.param_count(full) == 6 * block + 2 * 151_936 * d + d \
        == 4_374_622_464
    assert dsa_moe.param_count(full) * 2 / 1e9 == pytest.approx(8.749, abs=1e-3)
    # the whole model: 30 B parameters, 61 GB, on no chip
    assert (48 * block + 2 * 151_936 * d + d) / 1e9 == pytest.approx(
        30.64, abs=0.01)
    # one more block would be 10.0 GB
    assert (dsa_moe.param_count(full) + block) * 2 / 1e9 == pytest.approx(
        10.0, abs=0.01)
    assert (full.experts_first, full.experts_held, full.moe_layers) == \
        (0, 128, 6)
    assert full.scoring_func == "softmax" and full.routed_scaling_factor == 1
    by_kind = dsa_moe.kv_cache_bytes_by_kind(full, 1, 8256)
    assert by_kind == {"keys_values": 6 * 8256 * 2048,
                       "index_keys": 6 * 8256 * 128}
    assert dsa_moe.kv_cache_bytes(full, 1, 8256) == 13_056 * 8256 \
        == 107_790_336
    assert dsa_moe.kv_cache_bytes(full, 4, 8256) / 1e9 == pytest.approx(
        0.431, abs=1e-3)
    shapes = dsa_moe.param_shapes(full)
    assert shapes["layers"]["experts"]["down_proj"] == (6, 128, 768, 2048)
    assert shapes["layers"]["indexer"]["wk"] == (6, 2048, 64)
    assert "shared_experts" not in shapes["layers"]
    with pytest.raises(ValueError, match="frequency pairs"):
        dataclasses.replace(full, mrope_section=(16, 24, 20))
    with pytest.raises(ValueError, match="router"):
        dataclasses.replace(full, experts_first=120, experts_held=16)


def test_the_seeded_head_norms_make_the_selection_matter(params):
    """The gains of q_norm and k_norm are drawn at 2 (every other norm's
    at 1): at the tiny size, replacing S_t by every key or by the last
    ``topk`` moves the logits by tenths of a standard deviation (the
    breakage tests), and with unit gains it would not."""
    for name in dsa_moe.HEAD_NORMS:
        gains = np.asarray(params["layers"][name])
        assert 1.5 < gains.mean() < 2.5
    for name in dsa_moe.BLOCK_NORMS:
        assert 0.8 < np.asarray(params["layers"][name]).mean() < 1.2
    assert np.abs(np.asarray(
        params["layers"]["indexer"]["k_layernorm_bias"])).mean() < 0.2


# --- names in a device trace -------------------------------------------------------------

@pytest.fixture(scope="module")
def compiled_text(params):
    return dsa_moe.make_program(TINY, 3).lower(
        params, jnp.zeros((4, 16), jnp.int32), np.zeros(4, np.int32) + 13,
        np.zeros(4, np.uint32), np.zeros(4, np.float32)).compile().as_text()


def test_every_class_and_both_phases_are_in_the_compiled_program(
        compiled_text):
    names = [n for n in re.findall(r'op_name="([^"]+)"', compiled_text)
             if "KeyeVL2" in n]
    assert len(names) > 200
    assert {trace.classify(n) for n in names} == {
        "lm_proj", "lm_attn", "lm_cache", "lm_index", "lm_experts",
        "lm_mlp", "lm_norm", "lm_head", "embed"}    # lm_mlp: `mlp`'s glue
    assert {trace.phase_of(n) for n in names} == {"prefill", "decode"}
    for n in names:
        segments = n.split("/")
        at = segments.index("KeyeVL2")
        assert segments[at + 1] in trace.PHASES, n


def test_a_decode_step_copies_no_cache(compiled_text):
    """Three caches go through the decode scan's carry, and a step writes
    one position of each in place: no instruction under ``decode`` has a
    whole cache (``[3, 4, 19, 2, 16]`` or ``[3, 4, 19, 8]``) as its
    result unless it is the in-place ``dynamic-update-slice``."""
    shapes = {"f32[3,4,19,2,16]", "f32[3,4,19,8]"}
    copies = []
    for line in compiled_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.-]+) = (\S+) (\w[\w-]*)\(", line)
        if not m or "KeyeVL2/decode" not in line:
            continue
        name, result, op = m.groups()
        if result.split("{")[0] in shapes and op not in (
                "dynamic-update-slice", "get-tuple-element", "parameter",
                "bitcast") and "dynamic_update_slice" not in line \
                and "dynamic-update-slice" not in name:
            copies.append(line.strip()[:160])
    assert not copies, copies


@pytest.mark.parametrize("path, want, phase", [
    ("prefill/layers/while/body/self_attn/q_proj/dot_general", "lm_proj",
     "prefill"),
    ("decode/while/body/layers/while/body/closed_call/self_attn/k_proj/"
     "fewrow_dense_k_proj_v_proj/pallas_call", "lm_proj", "decode"),
    ("decode/while/body/layers/while/body/self_attn/k_norm/mul", "lm_norm",
     "decode"),
    ("prefill/layers/while/body/self_attn/rotary/cos", "lm_attn", "prefill"),
    ("prefill/layers/while/body/self_attn/bnhd,bmhd->bhnm/dot_general",
     "lm_attn", "prefill"),
    ("prefill/layers/while/body/self_attn/indexer/wq/dot_general",
     "lm_index", "prefill"),
    ("prefill/layers/while/body/self_attn/indexer/k_layernorm/rsqrt",
     "lm_index", "prefill"),
    ("prefill/layers/while/body/self_attn/indexer/index_rotary/sin",
     "lm_index", "prefill"),
    ("decode/while/body/layers/while/body/self_attn/indexer/weights_proj/"
     "dot_general", "lm_index", "decode"),
    ("prefill/layers/while/body/self_attn/indexer/index_scores/while/body/"
     "njd,md->njm/dot_general", "lm_index", "prefill"),
    ("prefill/layers/while/body/self_attn/indexer/topk/while/body/"
     "reduce_sum", "lm_index", "prefill"),
    ("decode/while/body/layers/while/body/self_attn/indexer/topk/top_k",
     "lm_index", "decode"),
    ("decode/while/body/layers/while/body/self_attn/indexer/gather/gather",
     "lm_index", "decode"),
    ("prefill/layers/while/body/self_attn/selection_record/shift_left",
     "lm_index", "prefill"),
    ("decode/while/body/layers/while/body/self_attn/kv_cache/"
     "dynamic_update_slice", "lm_cache", "decode"),
    ("prefill/layers/while/body/mlp/gate/top_k", "lm_experts", "prefill"),
    ("decode/while/body/layers/while/body/mlp/experts/while/body/cond/"
     "branch_1_fun/dot_general", "lm_experts", "decode"),
    ("prefill/layers/while/body/mlp/dispatch/sort", "lm_experts", "prefill"),
    ("prefill/layers/while/body/post_attention_layernorm/rsqrt", "lm_norm",
     "prefill"),
    ("decode/while/body/final_norm/mul", "lm_norm", "decode"),
    ("decode/while/body/lm_head/dot_general", "lm_head", "decode"),
    ("decode/while/body/sample/argmax", "lm_head", "decode"),
    ("prefill/embed_tokens/gather", "embed", "prefill"),
    ("prefill/layers/while/body/add", "lm_proj", "prefill"),
])
def test_the_scopes_fall_in_their_classes_and_phases(path, want, phase):
    name = "jit(lm_generate)/KeyeVL2/" + path
    assert trace.classify(name) == want
    assert trace.phase_of(name) == phase


# --- through the registry: counters, gauges, names ------------------------------------------

def counters():
    return dict(trace.GLOBAL_COUNTERS.snapshot())


@pytest.fixture
def model(monkeypatch):
    monkeypatch.setenv("DTPU_DEFAULT_FAMILY", "tiny")
    return registry.load_language_model("keye-vl-2.0-30b-a3b.safetensors")


def test_the_registry_serves_it_and_counts_what_a_step_reads(
        model, assert_nothing_compiled):
    """`load_language_model` by name -> `generate_rows`: the ``lm.*``
    counters of PR 28-32 keep their meaning, what the index scored and
    what attention read come over in the same read (8 keys a row a block
    a step, not the cache's length), the gauge says both caches; a second
    execution of the shape compiles nothing.  The rows carry the same
    instructions (7 ids with the first), so each starts from their
    snapshot and the program computes the 25 positions behind it."""
    assert model.family == "keye" and model.cfg == TINY
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
    assert got["lm.layer_applications"] == 15 * 3          # 3 blocks held
    assert got["lm.expert_pairs"] == 3 * 5 * 3 * 2         # rows x steps x L x k
    # every expert is held: every pair is local, none dropped
    assert got["lm.expert_pairs_local"] == got["lm.expert_pairs"]
    assert got["lm.expert_pairs_local_prefill"] == 4 * (32 - 7) * 3 * 2
    assert got["lm.expert_pairs_dropped"] == 0
    assert 0 < got["lm.expert_hits"] <= 5 * 3 * 8
    # every row of the program, the padded one too
    assert got["lm.prefill_positions"] == 4 * (32 - 7)
    # the three real rows, from the snapshot the first request made
    assert got["lm.prefix_hits"] == 3
    assert got["lm.prefix_positions_served"] == 3 * 7
    assert got.get("lm.prefix_misses", 0) == 0 and before[
        "lm.prefix_misses"] >= 1
    real = got["lm.prompt_tokens"]                          # of three rows
    assert real > 3 * 8
    # a step a row a block: every visible index key scored, 8 attended to
    assert got["lm.keys_scored_decode"] == 3 * (5 * real
                                                + 3 * (1 + 2 + 3 + 4 + 5))
    assert got["lm.keys_attended"] == got["lm.keys_selected"] \
        == 3 * 5 * 3 * 8
    assert got["lm.keys_attended_prefill"] > got["lm.keys_selected_prefill"] \
        > 0 < got["lm.keys_scored_prefill"]
    assert not any(got.get(k) for k in ("lm.state_steps",
                                        "lm.keys_attended_window",
                                        "lm.keys_attended_full"))
    gauges = trace.GLOBAL_GAUGES.snapshot()
    assert gauges["lm.kv_cache_bytes"] == \
        dsa_moe.kv_cache_bytes(TINY, 4, 37) == \
        gauges["lm.kv_cache_bytes_keys_values"] \
        + gauges["lm.kv_cache_bytes_index_keys"]
    assert gauges["lm.kv_cache_bytes_keys_values"] == 3 * 4 * 37 * 2 * 2 * 16 * 4
    assert gauges["lm.kv_cache_bytes_index_keys"] == 3 * 4 * 37 * 8 * 4
    words, lm_out = out[2]
    assert lm_out.row == 2 and set(lm_out.aux) == {
        "router_scores", "expert_choices", "prompt_choices",
        "key_selections", "prompt_selected"}
    # the records cover the WHOLE prompt buffer, the snapshot's positions too
    assert lm_out.aux["prompt_choices"].shape == (4, 32, 3, 2)
    assert lm_out.aux["prompt_selected"].shape == (4, 32, 3, 1)
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
    """`shared_prefix` finds the instructions' ids for this family (its
    rotation counts from a row's first real id: the snapshot stands at
    any offset); two executions make the snapshot once and count a hit a
    row; the words are those of the whole prompt scanned (the rule held
    off) and of each row alone."""
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
        == dsa_moe.prefix_bytes(TINY, 7)
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
    assert got["expert_pairs_local_prefill"] == 4 * 32 * 3 * 2
    assert not [k for k in got if k.startswith("prefix_")]


@pytest.mark.parametrize("name, want", [
    ("keye-vl-2.0-30b-a3b.safetensors", ("keye", "full")),
    ("Keye-VL-2.0-tiny.safetensors", ("keye", "tiny")),
])
def test_a_model_name_names_the_fifth_family(name, want, monkeypatch):
    monkeypatch.delenv("DTPU_DEFAULT_FAMILY", raising=False)
    assert registry.detect_lm_family(name) == want
    with pytest.raises(ValueError) as e:
        registry.detect_lm_family("a-decoder-of-no-family-7b.safetensors")
    assert "keye" in str(e.value) and "2,048 keys a query" in str(e.value)
    assert list(registry.LM_FAMILIES)[:5] == ["ouro", "pangu", "exaone",
                                              "granite", "keye"]


def test_a_second_language_model_that_cannot_fit_is_refused_by_name(
        monkeypatch):
    monkeypatch.delenv("DTPU_DEFAULT_FAMILY", raising=False)
    monkeypatch.setattr(registry, "_device_free_bytes",
                        lambda: int(15.7e9 - 6.38e9 - 2.6e9))
    name = "keye-vl-2.0-30b-a3b-of-another-graph.safetensors"  # not cached
    with pytest.raises(ValueError) as e:
        registry.load_language_model(name)
    assert name in str(e.value)
    assert "8.75 GB" in str(e.value) and "serve one language model a chip" \
        in str(e.value)


def test_no_file_of_this_family_is_read(tmp_path):
    with pytest.raises(NotImplementedError, match="KeyeVL2"):
        dsa_moe.load_checkpoint(str(tmp_path / "x.safetensors"), TINY)
