"""The decoder-hybrid-decoder (models/sambay.py: Mamba-1 and window
differential attention in front, ONE key-value cache and ONE state-space
memory shared by the layers behind; the back half computed for a prompt's
last position only) against its plain reference
(benchmarks/chip/reference/sambay.py: every layer at every position, the
recurrence as the recurrence, the full masked square) on seeded weights,
at a tiny size: d 64, eight layers (mamba, swa, mamba, swa, memory, full,
gmu, cross), 4 query heads over 2 key-value heads of 16 (two differential
heads over one pair), window 8, state 4, V 512, float32.  Prompts of 5 to
29 ids behind a buffer of 29 walked in chunks of 6 (five chunks, the first
one a padded front; more than three windows) and 14 decoded tokens: the
ring wraps over DECODED keys.

The comparison is verify_lm.py's (logits, not tokens), the one the chip
run uses at the published widths.  Each breakage the issue names has to
fail it where the served path passes.
"""

import dataclasses
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import lm_decode, registry, sambay
from comfyui_distributed_tpu.utils import trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sys.path.insert(0, BENCH)
ref = _load("sambay_reference", os.path.join(BENCH, "reference", "sambay.py"))
verify = _load("chipbench_verify_lm_for_sambay",
               os.path.join(BENCH, "verify_lm.py"))

TINY = sambay.TINY_SAMBAY
FULL = sambay.PHI_4_MINI_FLASH
NEW, PAD_TO = 14, 29
LENS = [29, 5, 17, 23]              # PAD_TO = 29: one row has no padding
# The tiny model is float32 on both sides: what is left is the order of
# the sums (chunks against one pass, the grouped call against two maps a
# head) and the CPU's transcendentals.  verify_lm.LIMITS_FP32 (1e-4 /
# 1e-5 of a logit's standard deviation) stand seven times over what the
# served path reads here (8e-6 to 1.5e-5 / 1.2e-6 to 1.5e-6) and over a
# hundred times under a bfloat16 state's reading (1.7e-2 / 1.2e-3:
# test_a_bfloat16_state_is_refused).  (The rows are
# SAMPLED, so that the ids vary: the margin of a greedy choice is not
# read.)
LIMITS = {k: v for k, v in verify.LIMITS_FP32.items()
          if k != "margin_over_std"}


def hf(cfg):
    """The config as the reference reads it (the configuration file's
    ``lm`` block)."""
    return {k: v for k, v in dataclasses.asdict(cfg).items()
            if k not in ("dtype", "state_dtype", "prefill_chunk")}


@pytest.fixture(scope="module")
def params():
    return sambay.seeded_params(TINY, np.uint32(11))


def prompt(seed=0, n=LENS[0], pad_to=PAD_TO):
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :n] = np.random.default_rng(seed).integers(3, TINY.vocab_size, n)
    return ids


def serve_rows(cfg, params, lens, new=NEW, temperature=0.7, pad_to=PAD_TO):
    """One execution over rows of the real lengths ``lens`` (row ``b``'s
    prompt is ``prompt(b, lens[b])``), SAMPLED: per row what the save node
    would write, and the execution's ``stats``."""
    ids = np.concatenate([prompt(b, n, pad_to) for b, n in enumerate(lens)])
    tokens, logits, aux, stats = sambay.make_program(cfg, new)(
        params, jnp.asarray(ids), np.asarray(lens, np.int32),
        np.arange(len(lens), dtype=np.uint32) + 3,
        np.asarray([temperature] * len(lens), np.float32))
    assert aux == {}
    rows = [{"prompt_ids": ids[b, :n], "tokens": np.asarray(tokens[b]),
             "logits": np.asarray(logits[b])} for b, n in enumerate(lens)]
    return rows, {k: np.asarray(v) for k, v in stats.items()}


def compare(cfg, params, served, limits=LIMITS):
    """The served row against the reference's full forward pass (every
    layer at every position), teacher-forced over the prompt and the
    served ids: the last prompt position and every decoded one."""
    ids, rows = verify.rows_of(served)
    logits = ref.forward(hf(cfg), params, ids)
    return verify.compare_logits(served["logits"], np.asarray(logits)[rows],
                                 served["tokens"], limits)


# --- the served path against the reference ------------------------------------

@pytest.mark.parametrize("rows", [1, 4])
def test_prefill_then_decode_through_the_shared_state_match_the_reference(
        rows, params):
    """The front over the prompt in chunks, the back half for each row's
    last position alone, then every decode step through the states, the
    rings and the one cache, give the logits of the reference's full
    forward pass over the same ids: every row of the execution, alone or
    as one of four of unequal length.  14 steps behind a window of 8: the
    ring is overwritten with decoded keys, twice for some slots."""
    served, stats = serve_rows(TINY, params, LENS[:rows])
    assert len({tuple(r["tokens"]) for r in served}) == rows
    for row in served:
        reading = compare(TINY, params, row)
        assert reading["correct"], reading
    Lm, Ls, Lc = 3, 2, 1
    assert (TINY.layers_of(sambay.MAMBA) + 1, TINY.layers_of(sambay.SWA),
            TINY.layers_of(sambay.CROSS)) == (Lm, Ls, Lc)
    chunks = 5                                          # ceil(29 / 6)
    assert stats["prefill_positions"] == rows * chunks * 6
    # the back half ran on ONE position a row
    assert stats["cross_positions"] == rows
    assert stats["scan_chunks"] == rows * Lm * chunks
    assert stats["state_steps"] == rows * Lm * NEW
    # step i's query sees the row's real ids and the i + 1 written: in
    # the one cache all of them, once for each of its 1 + Lc readers
    assert list(stats["keys_attended_full"]) == [
        (1 + Lc) * (NEW * n + NEW * (NEW + 1) // 2) for n in LENS[:rows]]
    # ... in a ring the last 8 of them
    assert list(stats["keys_attended_ring"]) == [
        Ls * sum(min(8, n + i + 1) for i in range(NEW)) for n in LENS[:rows]]


def _alone(cfg, params, b, n):
    tokens, logits, _, _ = sambay.make_program(cfg, NEW)(
        params, jnp.asarray(prompt(b, n)), np.asarray([n], np.int32),
        np.asarray([b + 3], np.uint32), np.asarray([0.7], np.float32))
    return np.asarray(tokens[0]), np.asarray(logits[0])


@pytest.mark.parametrize("pad", ["as seeded", "a large pad embedding"])
def test_a_row_of_a_shared_execution_is_its_single_row_run(pad, params):
    """The padding trap: a recurrence, a convolution and a LayerNorm's
    BIAS carry whatever stands in front of a row.  Each row of a 4-row
    execution gives the ids and logits of its own 1-row run, also where
    the pad id's embedding is large."""
    if pad != "as seeded":
        table = np.asarray(params["embed_tokens"]).copy()
        # (not a constant row: a LayerNorm of one would be all rounding)
        table[0] = 50.0 * np.random.default_rng(1).normal(size=table.shape[1])
        params = {**params, "embed_tokens": jnp.asarray(table)}
    served, _ = serve_rows(TINY, params, LENS)
    for b, (n, row) in enumerate(zip(LENS, served)):
        tokens, logits = _alone(TINY, params, b, n)
        np.testing.assert_array_equal(row["tokens"], tokens)
        # (rtol: the pad id's own logit is hundreds where the rest are 0.1)
        np.testing.assert_allclose(row["logits"], logits, atol=2e-5,
                                   rtol=1e-5)


def test_a_long_prompt_walks_several_chunks_and_windows(params):
    """61 real ids behind a buffer of 64 (eleven chunks of 6 with two
    padded positions added in front, eight windows of 8) and two rows of
    it: the state, the tail and the last 8 keys pass from chunk to chunk
    ten times."""
    served, stats = serve_rows(TINY, params, [61, 40], new=3, pad_to=64)
    assert stats["prefill_positions"] == 2 * 66
    for row in served:
        reading = compare(TINY, params, row)
        assert reading["correct"], reading


def test_the_chunk_does_not_change_the_numbers(params):
    """A chunk of 29 (one: the whole buffer) and of 6 give the same
    logits: the band under `_window`'s mask is the band whatever the
    chunk."""
    one, _ = serve_rows(dataclasses.replace(TINY, prefill_chunk=29), params,
                        LENS)
    many, _ = serve_rows(TINY, params, LENS)
    for a, b in zip(one, many):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        np.testing.assert_allclose(a["logits"], b["logits"], atol=2e-5)


# --- what has to be refused -----------------------------------------------------

def _refused(cfg, params, limits=LIMITS):
    served, _ = serve_rows(cfg, params, LENS[:1])
    reading = compare(TINY, params, served[0], limits)
    assert not reading["correct"], reading
    return reading


def test_a_gmu_fed_the_gated_output_is_refused(params, monkeypatch):
    """The memory is the scan's output BEFORE the gate: a memory layer
    that hands on ``y * silu(z)`` (what its own ``out_proj`` reads) fails
    the comparison."""
    real = sambay._mamba

    def gated(cfg, lp, v, is_real, s, tail):
        out, y, s, tail = real(cfg, lp, v, is_real, s, tail)
        if is_real is not None:
            v = jnp.where(is_real[..., None], v, 0.0)
        z = sambay._dense(v, lp["in_proj"], cfg)[..., cfg.d_inner:]
        return out, y * jax.nn.silu(z), s, tail

    monkeypatch.setattr(sambay, "_mamba", gated)
    _refused(TINY, params)


def test_a_cross_layer_fed_a_windows_ring_is_refused(params, monkeypatch):
    """The cross layers read layer ``n/2 + 1``'s cache: one that reads
    the last window layer's keys and values (tiled to the cache's length)
    fails the comparison."""
    real = sambay._cross

    def ring_fed(cfg, lp, u, l, index, first, kc, vc):
        tiles = -(-kc.shape[1] // cfg.sliding_window)
        kc, vc = (jnp.tile(rings[n][-1], (1, tiles, 1, 1))[:, :kc.shape[1]]
                  for n in ("ring_keys", "ring_values"))
        return real(cfg, lp, u, l, index, first, kc, vc)

    rings = {}
    real_back = sambay._back

    def back(cfg, params, x, memory, index, first, state, held):
        rings.update(state)
        return real_back(cfg, params, x, memory, index, first, state, held)

    monkeypatch.setattr(sambay, "_cross", ring_fed)
    monkeypatch.setattr(sambay, "_back", back)
    _refused(TINY, params)


def test_a_bfloat16_state_is_refused(params):
    """The nearest precision below the stated float32 for the recurrent
    state: 1.7e-2 / 1.2e-3 of a logit's standard deviation, over a hundred
    times the limits."""
    reading = _refused(dataclasses.replace(TINY, state_dtype=jnp.bfloat16),
                       params)
    assert reading["max_over_std"] > 10 * LIMITS["max_over_std"]


def test_a_window_off_by_one_is_refused(params):
    """A query at ``p`` sees keys ``p - 7 .. p``: a window of 9 in the
    program against the reference's 8 fails."""
    _refused(dataclasses.replace(TINY, sliding_window=9), params)


def test_the_d_skip_is_part_of_the_memory(params):
    """``m`` includes the ``D`` skip: with ``D`` = 0 in the program's
    weights the comparison with the reference of the seeded ones fails."""
    dropped = {**params, "mamba_layers": {
        **params["mamba_layers"],
        "D": jnp.zeros_like(params["mamba_layers"]["D"])}}
    served, _ = serve_rows(TINY, dropped, LENS[:1])
    assert not compare(TINY, params, served[0])["correct"]


# --- sizes ----------------------------------------------------------------------

def test_the_published_size_counts_to_the_published_3_8_b():
    """9 Mamba layers x 119,895,040 + 9 self-attention layers x
    98,322,304 + 7 GMU layers x 104,867,840 + 7 cross-attention layers x
    91,766,144 + the tied embedding 512,163,840 + a final norm 5,120."""
    assert FULL.layer_kinds == (
        ("mamba", "swa") * 8 + ("memory", "full") + ("gmu", "cross") * 7)
    shapes = sambay.param_shapes(FULL)

    def a_layer(stack):
        return sambay.count_values(
            {k: v[1:] for k, v in shapes[stack].items()})

    assert a_layer("mamba_layers") == 119_895_040
    assert a_layer("swa_layers") == a_layer("full_layers") == 98_322_304
    assert a_layer("gmu_layers") == 104_867_840
    assert a_layer("cross_layers") == 91_766_144
    assert [shapes[s]["fc1"][0] for s in (
        "mamba_layers", "swa_layers", "full_layers", "gmu_layers",
        "cross_layers")] == [9, 8, 1, 7, 7]
    assert sambay.param_count(FULL) == 3_852_562_944


def test_the_tree_carries_the_published_modules_names(params):
    assert set(params) == {
        "embed_tokens", "mamba_layers", "swa_layers", "full_layers",
        "gmu_layers", "cross_layers", "final_layernorm",
        "final_layernorm_bias"}
    every = {"input_layernorm", "input_layernorm_bias",
             "post_attention_layernorm", "post_attention_layernorm_bias",
             "fc1", "fc2"}
    attention = {"Wqkv", "Wqkv_bias", "out_proj", "out_proj_bias", "subln",
                 "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"}
    assert set(params["mamba_layers"]) == every | {
        "in_proj", "conv1d_weight", "conv1d_bias", "x_proj", "dt_proj",
        "dt_proj_bias", "A_log", "D", "out_proj"}
    assert set(params["swa_layers"]) == set(params["full_layers"]) \
        == set(params["cross_layers"]) == every | attention
    assert set(params["gmu_layers"]) == every | {"in_proj", "out_proj"}
    # a cross layer projects queries alone
    assert params["cross_layers"]["Wqkv"].shape == (1, 64, 64)
    assert params["swa_layers"]["Wqkv"].shape == (2, 64, 128)
    assert "lm_head" not in params              # tied


def test_the_seeded_scan_is_no_trivial_recurrence(params):
    """Mamba's initialisation: ``A`` = 1..N a channel and ``dt`` in
    [1e-3, 1e-1], so that ``exp(dt A)`` is neither 0 nor 1 and the
    logits say something about the state."""
    mamba = params["mamba_layers"]
    np.testing.assert_allclose(
        np.exp(np.asarray(mamba["A_log"][0, 5])), [1, 2, 3, 4], rtol=1e-6)
    dt = np.asarray(jax.nn.softplus(mamba["dt_proj_bias"]))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    assert np.all(np.asarray(mamba["D"]) == 1.0)
    assert 0.015 < float(np.asarray(params["embed_tokens"]).std()) < 0.025


def test_state_bytes_by_kind_at_the_published_size():
    """A row's state at 8,256 positions: ONE cache of 5,120 B a position,
    eight rings of 512 slots, nine float32 states and tails: 66.5 MB
    where 32 layers of the same heads would hold 1.35 GB."""
    by_kind = sambay.kv_cache_bytes_by_kind(FULL, 1, 8256)
    assert by_kind == {"recurrent": 9 * (5120 * 16 * 4 + 3 * 5120 * 2),
                       "ring": 8 * 512 * 5120, "full": 8256 * 5120}
    assert sambay.state_bytes(FULL, 4) == 4 * by_kind["recurrent"]
    assert sambay.kv_cache_bytes(FULL, 1, 8256) \
        == by_kind["ring"] + by_kind["full"]
    assert 66.4e6 < sum(by_kind.values()) < 66.6e6
    assert 32 * 8256 * 5120 > 1.35e9
    state = jax.eval_shape(lambda: sambay.empty_state(FULL, 4, 8256))
    held = {"recurrent": ("ssm", "conv"),
            "ring": ("ring_keys", "ring_values"), "full": ("keys", "values")}
    for kind, names in held.items():
        assert sum(state[n].size * state[n].dtype.itemsize
                   for n in names) == 4 * by_kind[kind]
    assert state["ssm"].dtype == jnp.float32


def test_a_config_that_is_no_decoder_hybrid_decoder_is_refused():
    for wrong in ({"num_hidden_layers": 6}, {"mb_per_layer": 3},
                  {"num_key_value_heads": 4}, {"tie_word_embeddings": False}):
        with pytest.raises(ValueError):
            dataclasses.replace(TINY, **wrong)


# --- the registry and the served model ---------------------------------------------

@pytest.mark.parametrize("name, want", [
    ("phi-4-mini-flash-reasoning.safetensors", ("phi4flash", "full")),
    ("Phi-4-Mini-Flash-Reasoning-tiny.safetensors", ("phi4flash", "tiny")),
    ("phi4flash-test.safetensors", ("phi4flash", "tiny")),
])
def test_a_model_name_names_the_sixth_family(name, want, monkeypatch):
    monkeypatch.delenv("DTPU_DEFAULT_FAMILY", raising=False)
    assert registry.detect_lm_family(name) == want
    with pytest.raises(ValueError) as e:
        registry.detect_lm_family("a-decoder-of-no-family-7b.safetensors")
    assert "phi-4-mini-flash" in str(e.value) \
        and "ONE key-value cache" in str(e.value)
    assert list(registry.LM_FAMILIES)[:6] == ["ouro", "pangu", "exaone",
                                              "granite", "keye", "phi4flash"]


def test_the_family_offers_what_the_serving_path_reads():
    arch = registry.LM_FAMILIES["phi4flash"].load()
    assert arch is sambay
    for name in ("CONFIGS", "param_count", "seeded_params",
                 "load_checkpoint", "make_program", "kv_cache_bytes",
                 "kv_cache_bytes_by_kind", "state_bytes", "window_counters",
                 "few_rows_here", "make_prefix_program", "prefix_bytes"):
        assert hasattr(arch, name), name
    assert FULL.layer_applications == 32 and FULL.vocab_size == 200064


def test_no_file_of_this_family_is_read(tmp_path):
    with pytest.raises(NotImplementedError, match="phi4flash"):
        sambay.load_checkpoint(str(tmp_path / "x.safetensors"), TINY)


def test_a_second_language_model_that_cannot_fit_is_refused_by_name(
        monkeypatch):
    monkeypatch.delenv("DTPU_DEFAULT_FAMILY", raising=False)
    monkeypatch.setattr(registry, "_device_free_bytes",
                        lambda: int(15.7e9 - 6.38e9 - 2.6e9))
    name = "phi-4-mini-flash-reasoning-of-another-graph.safetensors"
    with pytest.raises(ValueError) as e:
        registry.load_language_model(name)
    assert name in str(e.value) and "7.71 GB" in str(e.value)


def test_the_served_model_counts_what_the_program_computed(monkeypatch):
    """Through `LanguageModel.generate_rows`: the window counters of the
    new kinds and the gauges of the three geometries."""
    monkeypatch.setenv("DTPU_DEFAULT_FAMILY", "tiny")
    model = registry.load_language_model(
        "phi-4-mini-flash-reasoning.safetensors")
    assert model.family == "phi4flash" and model.cfg is TINY
    before = dict(trace.GLOBAL_COUNTERS.snapshot())
    rows = [registry.LMRow("a red fox on fresh snow", 1),
            registry.LMRow("a lighthouse", 2), registry.LMRow("rain", 3)]
    out = model.generate_rows(rows, max_new_tokens=4, prompt_tokens=32)
    assert len(out) == 3 and all(isinstance(t, str) for t, _ in out)
    after = trace.GLOBAL_COUNTERS.snapshot()

    def stepped(name):
        return after.get(name, 0) - before.get(name, 0)

    # 3 real rows in the 4-row program, 32 positions = 6 chunks of 6
    assert stepped("lm.prefill_positions") == 4 * 36
    assert stepped("lm.cross_positions") == 4
    assert stepped("lm.scan_chunks") == 4 * 3 * 6
    assert stepped("lm.state_steps") == 4 * 3 * 4
    assert stepped("lm.keys_attended_full") > 0
    assert stepped("lm.keys_attended_ring") > 0
    gauges = trace.GLOBAL_GAUGES.snapshot()
    by_kind = sambay.kv_cache_bytes_by_kind(TINY, 4, 36)
    assert gauges["lm.kv_cache_bytes_ring"] == by_kind["ring"]
    assert gauges["lm.kv_cache_bytes_full"] == by_kind["full"]
    assert gauges["lm.kv_cache_bytes_recurrent"] == by_kind["recurrent"]
    assert gauges["lm.state_bytes"] == sambay.state_bytes(TINY, 4)


# --- a prefix shared between requests -------------------------------------------
#
# Every row's prompt is the same K ids and then its own, in a suffix buffer
# the longest row fills.  Window 8, chunks of 6:

@dataclasses.dataclass(frozen=True)
class Shared:
    prefix: int         # K, the ids every row starts with
    own: tuple          # each row's own ids behind them
    new: int = 6

    @property
    def buffer(self):
        return max(self.own)

    @property
    def first(self):
        return [self.buffer - n for n in self.own]


SHARED = {
    # 13 ids: longer than the window, no multiple of the chunk; a suffix
    # buffer of 8 (two chunks behind 4 padded positions) that one row
    # fills, one of a single id, one shorter than the convolution's tail;
    # the rows' offsets 0, 7, 6, 3 differ mod 8
    "13 ids, a window and more": Shared(13, (8, 1, 2, 5)),
    # 5 ids: the rings hold zeros in front of them
    "5 ids, less than a window": Shared(5, (8, 1, 2, 5)),
    # 24 ids, four chunks and three windows; a suffix of 16 (three chunks
    # behind 2 padded positions), offsets 0, 15, 9, 5: a row's first own
    # position lies in the first, the last and the middle chunk
    "24 ids, a suffix of three chunks": Shared(24, (16, 1, 7, 11)),
    # a suffix buffer of ONE id: a chunk of one position
    "a suffix of one id": Shared(13, (1, 1)),
}
A = SHARED["13 ids, a window and more"]


def shared_prompts(case):
    """Per row the whole prompt: `prompt(9)`'s first K ids, then the
    row's own."""
    head = prompt(9, case.prefix, case.prefix)[0]
    return [np.concatenate([head, prompt(b + 20, n, n)[0]])
            for b, n in enumerate(case.own)]


def snapshot_of(cfg, params, case):
    return sambay.make_prefix_program(cfg)(
        params, jnp.asarray(shared_prompts(case)[0][:case.prefix]))


def buffers(case, picked, held):
    """The prompt buffer of the rows ``picked`` with their first ``held``
    ids left out (a snapshot stands for them), and the lengths."""
    whole = shared_prompts(case)
    ids = np.zeros((len(picked), case.prefix + case.buffer - held), np.int32)
    for b, i in enumerate(picked):
        ids[b, :len(whole[i]) - held] = whole[i][held:]
    return ids, np.asarray([len(whole[i]) - held for i in picked], np.int32)


def serve_shared(cfg, params, case, picked=None, snapshot="made",
                 temperature=0.7, program=None):
    """One execution over the rows ``picked`` of `shared_prompts`: from
    the snapshot of the K ids (made here where "made"), or with None the
    whole prompts through the five-argument program.  (A ``program`` kept
    by the caller compiles a shape once; a fresh one traces whatever a
    test has patched.)"""
    picked = tuple(range(len(case.own))) if picked is None else picked
    if isinstance(snapshot, str):
        snapshot = snapshot_of(cfg, params, case)
    ids, lens = buffers(case, picked, 0 if snapshot is None else case.prefix)
    tokens, logits, _, stats = (program or sambay.make_program(
        cfg, case.new))(
        params, jnp.asarray(ids), lens, np.asarray(picked, np.uint32) + 3,
        np.asarray([temperature] * len(picked), np.float32),
        *(() if snapshot is None else (snapshot,)))
    whole = shared_prompts(case)
    rows = [{"prompt_ids": whole[i], "tokens": np.asarray(tokens[b]),
             "logits": np.asarray(logits[b])} for b, i in enumerate(picked)]
    return rows, {k: np.asarray(v) for k, v in stats.items()}


def close(got, want, scale=2e-6):
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=scale * float(np.abs(want).max()))


@pytest.mark.parametrize("case", SHARED)
def test_rows_started_from_a_snapshot_are_the_full_paths_and_the_references(
        case, params):
    """Rows of unequal length from one snapshot, sampled with seeds: the
    ids of the five-argument program over the whole prompt, its logits to
    float32's rounding, and the reference's (every layer at every one of
    the whole prompt's positions, no snapshot, no chunk) inside the
    limits every served path is held to."""
    case = SHARED[case]
    served, stats = serve_shared(TINY, params, case)
    full, full_stats = serve_shared(TINY, params, case, snapshot=None)
    for b, (got, want) in enumerate(zip(served, full)):
        assert np.array_equal(got["tokens"], want["tokens"])
        close(got["logits"], want["logits"])
        # (the reference walks every position in Python: of the later
        # cases the row of ONE own id, which reads most of the snapshot)
        if case is A or b == case.own.index(1):
            reading = compare(TINY, params, got)
            assert reading["correct"], reading
    # what the program COMPUTED: the suffix buffer, in chunks of 6 or in
    # one of its own length where it is shorter, where the full path
    # walked the whole buffer in chunks of 6
    B, Lm, S = len(case.own), 3, case.buffer
    walked = -(-S // min(6, S)) * min(6, S)
    whole = -(-(case.prefix + S) // 6) * 6
    assert (stats["prefill_positions"], full_stats["prefill_positions"]) \
        == (B * walked, B * whole)
    assert (stats["scan_chunks"], full_stats["scan_chunks"]) \
        == (B * Lm * walked // min(6, S), B * Lm * whole // 6)
    assert stats["cross_positions"] == full_stats["cross_positions"] == B
    assert stats["state_steps"] == full_stats["state_steps"] \
        == B * Lm * case.new
    # the prefix's keys are READ by every decode step all the same
    for kind in ("ring", "full"):
        assert list(stats[f"keys_attended_{kind}"]) \
            == list(full_stats[f"keys_attended_{kind}"])
    assert list(stats["keys_attended_full"]) == [
        2 * (case.new * (case.prefix + n) + case.new * (case.new + 1) // 2)
        for n in case.own]


@pytest.mark.parametrize("pad", ["as seeded", "a large pad embedding"])
def test_a_row_behind_a_snapshot_is_its_single_row_run(pad, params):
    """Four rows of unequal length from one snapshot give, each, what
    they give alone through the 1-row program (whose suffix buffer they
    do not fill either: the padding lies in front of prefix and suffix
    both), whatever the other rows hold; also where the pad id's
    embedding is large."""
    if pad != "as seeded":
        table = np.asarray(params["embed_tokens"]).copy()
        table[0] = 50.0 * np.random.default_rng(1).normal(size=table.shape[1])
        params = {**params, "embed_tokens": jnp.asarray(table)}
    snapshot = snapshot_of(TINY, params, A)
    program = sambay.make_program(TINY, A.new)
    served, _ = serve_shared(TINY, params, A, snapshot=snapshot)
    for b in range(4):
        (alone,), _ = serve_shared(TINY, params, A, (b,), snapshot,
                                   program=program)
        assert np.array_equal(alone["tokens"], served[b]["tokens"]), b
        np.testing.assert_allclose(served[b]["logits"], alone["logits"],
                                   atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("case", SHARED)
def test_the_snapshot_is_what_the_full_prefill_leaves_behind_the_prefix(
        case, params):
    """At the stored widths, one row and no axis of rows.  Against the
    five-argument prefill of the PREFIX alone, each row behind its own
    padding: the nine... here three states and tails; each ring SLOT FOR
    SLOT once a row's offset has rotated the snapshot's position order
    (prefix position ``j`` of row ``b`` in slot ``(first[b] + j) mod
    W``); the cache's part at ``first[b]``.  Then the state behind the
    suffix's prefill against the five-argument prefill of the WHOLE
    prompts: what a decode step reads is the same."""
    case = SHARED[case]
    K, W, B = case.prefix, TINY.sliding_window, len(case.own)
    snapshot = snapshot_of(TINY, params, case)
    Lm, Ls, pair = 3, 2, (1, 32)
    assert {k: (v.shape, v.dtype) for k, v in snapshot.items()} == {
        "ssm": ((Lm, 4, 128), jnp.float32),
        "conv": ((Lm, 3, 128), TINY.dtype),
        "ring_keys": ((Ls, W, *pair), TINY.dtype),
        "ring_values": ((Ls, W, *pair), TINY.dtype),
        "keys": ((1, K, *pair), TINY.dtype),
        "values": ((1, K, *pair), TINY.dtype)}
    assert lm_decode.prefix_length(snapshot) == K
    assert sum(v.nbytes for v in snapshot.values()) \
        == sambay.prefix_bytes(TINY, K)
    if K < W:
        assert float(jnp.abs(snapshot["ring_keys"][:, :W - K]).max()) == 0
    # the prefix alone, every row behind the padding its suffix gives it
    first = np.asarray(case.first)
    head = shared_prompts(case)[0][:K]
    ids = np.zeros((B, K + case.buffer), np.int32)
    ids[:, :K] = head
    _, state = jax.jit(lambda p, i, f: sambay.prefill(
        TINY, p, i, f, K + case.buffer))(params, jnp.asarray(ids),
                                         case.buffer + 0 * first)
    for b in range(B):
        for name in ("ssm", "conv"):
            close(snapshot[name], state[name][:, b], 1e-5)
    # (that prefill put row b's prefix at ``buffer``, not at ``first[b]``:
    # one offset for every row; the rotation is the offset's)
    at = case.buffer
    held = range(max(0, K - W), K)
    for name, ring in (("keys", "ring_keys"), ("values", "ring_values")):
        want = np.asarray(state[ring][:, 0])
        for j in held:
            close(snapshot[ring][:, j - (K - W)], want[:, (at + j) % W], 1e-5)
        close(snapshot[name][0], state[name][0, at:at + K], 1e-5)
    # behind the suffix: every row at its OWN offset, against the whole
    # prompts through the five-argument prefill
    P = K + case.buffer
    run = jax.jit(lambda p, i, f, *s: sambay.prefill(
        TINY, p, i, f, P + case.new, *s)[1])
    got = run(params, jnp.asarray(buffers(case, range(B), K)[0]), first,
              snapshot)
    want = run(params, jnp.asarray(buffers(case, range(B), 0)[0]), first)
    for b, n in enumerate(case.own):
        for name in ("ssm", "conv"):
            close(got[name][:, b], want[name][:, b], 1e-5)
        # the slots a decode step can see: the last W real positions
        seen = [i % W for i in range(max(first[b], P - W), P)]
        for name in sambay.RINGS:
            close(got[name][:, b, seen], want[name][:, b, seen], 1e-5)
            # prefix position j of row b stands in slot (first[b] + j) mod W
            for j in range(max(0, K - W, K + n - W), K):
                np.testing.assert_array_equal(
                    got[name][:, b, (first[b] + j) % W],
                    snapshot[name][:, j - (K - W)])
        # the cache: padding | prefix | own ids
        for name in ("keys", "values"):
            np.testing.assert_array_equal(
                got[name][b, first[b]:first[b] + K], snapshot[name][0])
            close(got[name][b, first[b]:P], want[name][b, first[b]:P], 1e-5)
            assert float(jnp.abs(got[name][b, :first[b]]).max(initial=0)) == 0
    # the row of a single id: two inputs of its new tail are the prefix's
    one = case.own.index(1)
    np.testing.assert_array_equal(got["conv"][:, one, :2],
                                  snapshot["conv"][:, 1:])


def test_the_maker_is_not_the_served_program_and_the_phases_stay(params):
    """The maker is ``lm_prefix_state``: the cells' pattern for the
    served program (``^jit_lm_generate$``) does not match it, so its
    seconds are no execution's.  It runs the FRONT and the cache's
    projection, nothing behind.  The program that starts from a snapshot
    is still ``lm_generate``, with every class and both phases."""
    maker = sambay.make_prefix_program(TINY).lower(
        params, jnp.zeros((A.prefix,), jnp.int32))
    assert "jit_lm_prefix_state" in maker.as_text()[:200]
    assert not re.match("^jit_lm_generate$", "jit_lm_prefix_state")
    made = set(re.findall(r'op_name="([^"]+)"', maker.compile().as_text()))
    assert any("/Phi4Flash/prefill/layers/" in n for n in made)
    assert not [n for n in made if re.search(
        r"/(gmu|cross)/|lm_head|final_layernorm|/decode/", n)]
    ids, lens = buffers(A, range(4), A.prefix)
    lowered = sambay.make_program(TINY, 3).lower(
        params, jnp.asarray(ids), lens, np.zeros(4, np.uint32),
        np.zeros(4, np.float32), snapshot_of(TINY, params, A))
    assert "jit_lm_generate" in lowered.as_text()[:200]
    names = [n for n in re.findall(r'op_name="([^"]+)"',
                                   lowered.compile().as_text())
             if "/Phi4Flash/" in n]
    classes = {"lm_proj", "lm_attn", "lm_cross", "lm_gmu", "lm_ssm",
               "lm_state", "lm_cache", "lm_mlp", "lm_norm", "lm_head",
               "embed"}
    assert {trace.classify(n) for n in names} == classes
    assert {trace.phase_of(n) for n in names} == {"prefill", "decode"}
    # the rows' start from the snapshot is the state's and the cache's
    copies = {trace.classify(f"jit(lm_generate)/Phi4Flash/prefill/{scope}/x")
              for scope in ("ssm_state", "conv_state", "kv_cache")}
    assert copies == {"lm_state", "lm_cache"}


def _a_ring_left_unrotated(monkeypatch):
    """The prefix's last keys where row 0's offset puts them, in every
    row."""
    real = sambay._behind_prefix
    monkeypatch.setattr(
        sambay, "_behind_prefix",
        lambda held, last, slot: real(held, last, slot[:1] + 0 * slot))


def _a_tail_taken_from_the_padding(monkeypatch):
    """The snapshot's tail in front of the CHUNK, where a row's padding
    stands, not in front of its first own id."""
    real = sambay.causal_conv
    monkeypatch.setattr(
        sambay, "causal_conv",
        lambda u, weight, bias, tail=None, at=None: real(u, weight, bias,
                                                         tail))


def _padding_over_the_prefixs_end(monkeypatch):
    """Every position of the suffix buffer writes its key into the one
    cache, a row's padded ones too: over the last keys of its prefix."""
    monkeypatch.setattr(lm_decode, "own_entries",
                        lambda own, new, cache, l, at: new)


def _no_keys_behind_the_prefix(monkeypatch):
    """The window layers start the suffix from empty rings."""
    monkeypatch.setattr(sambay, "_behind_prefix",
                        lambda held, last, slot: held)


SHARED_BREAKAGES = {
    "a ring left unrotated": _a_ring_left_unrotated,
    "a tail taken from the padding": _a_tail_taken_from_the_padding,
    "padding written over the prefix's end": _padding_over_the_prefixs_end,
    "rings that start empty": _no_keys_behind_the_prefix}
# 9 ids (a window and one more) and a suffix buffer of one chunk, which row
# 0 fills: it has no padding for a trap of the padding to go wrong in
TRAPS = Shared(9, (6, 1, 4), new=3)


@pytest.mark.parametrize("what", SHARED_BREAKAGES)
def test_each_breakage_behind_a_snapshot_fails_the_comparison(
        what, params, monkeypatch):
    """The traps of a prefix in front of right-aligned rows whose state
    is of three geometries: the comparison with the reference of the
    WHOLE prompt refuses each, in a padded row; the row that fills its
    buffer passes the three that are the padding's."""
    SHARED_BREAKAGES[what](monkeypatch)
    served, _ = serve_shared(TINY, params, TRAPS)
    monkeypatch.undo()
    readings = [compare(TINY, params, row) for row in served]
    assert not any(r["correct"] for r in readings[1:]), readings
    assert readings[0]["correct"] == (what != "rings that start empty"), \
        readings[0]


def test_rows_that_share_no_instructions_run_the_program_as_it_was(
        params, monkeypatch):
    """The five-argument program does not know of the sixth: nothing of
    the snapshot's path is on its way (each piece would raise here), its
    chunk is ``prefill_chunk`` whatever the buffer (behind a snapshot a
    shorter buffer is one chunk of its own length), and its ids and
    logits are the unpatched program's bit for bit.  (Against a ``git
    archive`` of the parent's tree they are the parent's bit for bit, by
    hand: PERF.md section 6, PR 47.)"""
    ids, lens = buffers(A, range(4), 0)
    args = (params, jnp.asarray(ids), lens, np.arange(4, dtype=np.uint32),
            np.full(4, 0.7, np.float32))
    want_tokens, want_logits, _, _ = sambay.make_program(TINY, 4)(*args)

    def never(*a, **kw):
        raise AssertionError("the snapshot's path without a snapshot")

    for module, name in ((sambay, "_behind_prefix"), (sambay, "from_prefix"),
                         (lm_decode, "own_entries"),
                         (lm_decode, "write_at_offsets")):
        monkeypatch.setattr(module, name, never)
    tokens, logits, _, _ = sambay.make_program(TINY, 4)(*args)
    np.testing.assert_array_equal(tokens, want_tokens)
    np.testing.assert_array_equal(logits, want_logits)
    assert sambay.chunk_of(TINY, 3) == TINY.prefill_chunk == 6
    assert (sambay.chunk_of(TINY, 3, {}), sambay.chunk_of(TINY, 8, {})) \
        == (3, 6)


# --- through the registry -------------------------------------------------------

def lm_delta(before):
    after = trace.GLOBAL_COUNTERS.snapshot()
    return {k[3:]: after[k] - before.get(k, 0) for k in after
            if k.startswith("lm.") and after[k] != before.get(k, 0)}


def asked(model, rows, **kw):
    before = dict(trace.GLOBAL_COUNTERS.snapshot())
    out = model.generate_rows(rows, max_new_tokens=3, prompt_tokens=32, **kw)
    return [words for words, _ in out], lm_delta(before)


def test_rows_from_a_snapshot_get_the_words_of_the_whole_prompt(monkeypatch):
    """Through `LanguageModel.generate_rows`, three rows of one set of
    instructions (7 ids with the first): each starts from their snapshot,
    the program walks the 25 positions behind it (5 chunks of 6 behind 5
    padded positions), and the words are those of the same rows with the
    whole prompt scanned (the rule held off), and of each row alone."""
    monkeypatch.setenv("DTPU_DEFAULT_FAMILY", "tiny")
    model = registry.load_language_model(
        "phi-4-mini-flash-reasoning.safetensors")
    guide = "style guide number 0 of many"
    rows = [registry.LMRow(f"a walled garden in june number {i}", i, 0.7 * i,
                           instructions=guide) for i in range(3)]
    words, got = asked(model, rows)
    assert got["prefix_hits"] == 3 and got["prefix_positions_served"] == 21
    assert got["prefill_positions"] == 4 * 30 and got["cross_positions"] == 4
    assert got["scan_chunks"] == 4 * 3 * 5
    assert trace.GLOBAL_GAUGES.snapshot()["lm.prefix_bytes"] \
        == sambay.prefix_bytes(TINY, 7) * len(model._prefixes)
    for i, row in enumerate(rows):
        assert asked(model, [row])[0] == [words[i]]
    # rows with DIFFERENT instructions: the whole prompt, nothing counted
    other = [*rows[:2], registry.LMRow("a cat", 2, instructions="draw it")]
    assert model.shared_prefix(other, 32) is None
    got = asked(model, other)[1]
    assert got["prefill_positions"] == 4 * 36
    assert not [k for k in got if k.startswith("prefix_")]
    monkeypatch.setattr(registry.LanguageModel, "shared_prefix",
                        lambda self, *a: None)
    whole, got = asked(model, rows)
    assert whole == words and len(set(words)) == 3
    assert "prefix_hits" not in got and got["prefill_positions"] == 4 * 36


# --- names in a compiled program ----------------------------------------------------

@pytest.fixture(scope="module")
def op_names(params):
    text = sambay.make_program(TINY, 2).lower(
        params, jnp.zeros((2, 13), jnp.int32), jnp.asarray([13, 7]),
        jnp.zeros(2, jnp.uint32), jnp.zeros(2, jnp.float32)
    ).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', text))


def test_every_class_and_both_phases_are_in_the_program(op_names):
    """The HLO of the tiny program, classified by what
    `trace.KERNEL_CLASSES` reads: every class of this family in both
    phases, nothing of ours in ``other``."""
    ours = [n for n in op_names if "/Phi4Flash/" in n]
    seen = {(trace.classify(n), trace.phase_of(n)) for n in ours}
    classes = {"lm_proj", "lm_attn", "lm_cross", "lm_gmu", "lm_ssm",
               "lm_state", "lm_cache", "lm_mlp", "lm_norm", "lm_head",
               "embed"}
    assert {c for c, _ in seen} == classes
    for cls in classes - {"embed"}:
        assert {(cls, "prefill"), (cls, "decode")} <= seen, cls


@pytest.mark.parametrize("path, cls, phase", [
    ("Phi4Flash/prefill/layers/while/body/closed_call/mamba/while/body/"
     "closed_call/attn/selective_scan/while/body/closed_call/exp",
     "lm_ssm", "prefill"),
    ("Phi4Flash/decode/while/body/closed_call/layers/memory/attn/conv1d/"
     "mul", "lm_ssm", "decode"),
    ("Phi4Flash/decode/while/body/closed_call/layers/memory/ssm_state/"
     "dynamic_update_slice", "lm_state", "decode"),
    ("Phi4Flash/decode/while/body/closed_call/layers/while/body/"
     "closed_call/gmu/attn/gate/mul", "lm_gmu", "decode"),
    ("Phi4Flash/decode/while/body/closed_call/layers/while/body/"
     "closed_call/gmu/attn/in_proj/dot_general", "lm_proj", "decode"),
    ("Phi4Flash/prefill/layers/while/body/closed_call/cross/attn/"
     "inner_cross_attn/bhnm,bmhd->bnhd/dot_general", "lm_cross", "prefill"),
    ("Phi4Flash/decode/while/body/closed_call/layers/full/attn/inner_attn/"
     "bnhd,bmhd->bhnm/dot_general", "lm_attn", "decode"),
    ("Phi4Flash/decode/while/body/closed_call/layers/while/body/"
     "closed_call/swa/attn/kv_cache/dynamic_update_slice", "lm_cache",
     "decode"),
    ("Phi4Flash/prefill/layers/full/attn/Wqkv/dot_general", "lm_proj",
     "prefill"),
    ("Phi4Flash/decode/while/body/closed_call/layers/full/attn/subln/mul",
     "lm_norm", "decode"),
    ("Phi4Flash/decode/while/body/closed_call/layers/while/body/"
     "closed_call/cross/mlp/fc1/dot_general", "lm_mlp", "decode"),
    ("Phi4Flash/decode/while/body/closed_call/layers/while/body/"
     "closed_call/swa/add", "lm_proj", "decode"),
    ("Phi4Flash/decode/while/body/closed_call/final_layernorm/mul",
     "lm_norm", "decode"),
    ("Phi4Flash/decode/while/body/closed_call/lm_head/dot_general",
     "lm_head", "decode"),
])
def test_a_path_of_this_family_falls_in_its_class(path, cls, phase):
    path = "jit(lm_generate)/" + path
    assert (trace.classify(path), trace.phase_of(path)) == (cls, phase)
