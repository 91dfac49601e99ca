"""The decoder of state-space (Mamba-2) and attention layers
(models/ssm_hybrid.py: a recurrent state overwritten in place beside a
key-value cache, a chunked scan for the prefill and one step of the
recurrence for a decode step, a tied embedding) against its plain
reference (benchmarks/chip/reference/ssm_hybrid.py, which writes the
recurrence as the recurrence) on seeded weights, at a tiny size: d 64,
six blocks (mamba, mamba, attention, mamba, mamba, attention), 4 Mamba
heads of 32 over a state of 16, chunks of 8, 4 query heads over 2
key-value heads of 16, V 512, float32.  Prompts of 3 to 21 ids behind a
buffer of 21 and 6 decoded tokens: three chunks, the last one partly
filled, so the state passes between chunks and the padding lies inside
one.

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

from comfyui_distributed_tpu.models import lm_decode, looplm, registry, \
    ssm_hybrid
from comfyui_distributed_tpu.models.ssm_hybrid import ATTENTION, MAMBA
from comfyui_distributed_tpu.utils import trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sys.path.insert(0, BENCH)
ref = _load("ssm_hybrid_reference",
            os.path.join(BENCH, "reference", "ssm_hybrid.py"))
verify = _load("chipbench_verify_lm_for_ssm",
               os.path.join(BENCH, "verify_lm.py"))

TINY = ssm_hybrid.TINY_SSM_HYBRID
FULL = ssm_hybrid.GRANITE_4_0_H_MICRO
NEW, PAD_TO = 6, 21
LENS = [21, 3, 13, 18]              # PAD_TO = 21: one row has no padding
# (the rows are SAMPLED, so that the ids vary: the margin of a greedy
# choice is not read)
LIMITS = {k: v for k, v in verify.LIMITS_FP32.items()
          if k != "margin_over_std"}


def hf(cfg):
    """The config as the reference reads it (the configuration file's
    ``lm`` block)."""
    return {k: v for k, v in dataclasses.asdict(cfg).items()
            if k not in ("dtype", "state_dtype")}


@pytest.fixture(scope="module")
def params():
    return ssm_hybrid.seeded_params(TINY, np.uint32(11))


def prompt(seed=0, n=LENS[0]):
    ids = np.zeros((1, PAD_TO), np.int32)
    ids[0, :n] = np.random.default_rng(seed).integers(3, TINY.vocab_size, n)
    return ids


def serve_rows(cfg, params, lens, new=NEW, temperature=0.7):
    """One execution over rows of the real lengths ``lens`` (row ``b``'s
    prompt is ``prompt(b, lens[b])``), SAMPLED (a seeded model's greedy
    ids hardly vary; the logits are compared whatever was drawn): per row
    what the save node would write, and the execution's ``stats``."""
    ids = np.concatenate([prompt(b, n) for b, n in enumerate(lens)])
    tokens, logits, aux, stats = ssm_hybrid.make_program(cfg, new)(
        params, jnp.asarray(ids), np.asarray(lens, np.int32),
        np.arange(len(lens), dtype=np.uint32) + 3,
        np.asarray([temperature] * len(lens), np.float32))
    assert aux == {}
    rows = [{"prompt_ids": ids[b, :n], "tokens": np.asarray(tokens[b]),
             "logits": np.asarray(logits[b])} for b, n in enumerate(lens)]
    return rows, {k: np.asarray(v) for k, v in stats.items()}


def compare(cfg, params, served, limits=LIMITS):
    """The served row against the reference's full forward pass,
    teacher-forced over the prompt and the served ids."""
    ids, rows = verify.rows_of(served)
    logits, _ = ref.forward(hf(cfg), params, ids)
    return verify.compare_logits(served["logits"], np.asarray(logits)[rows],
                                 served["tokens"], limits)


# --- the served path against the reference ------------------------------------

@pytest.mark.parametrize("rows", [1, 4])
def test_prefill_then_decode_through_state_and_cache_match_the_reference(
        rows, params):
    """The chunked scan over the prompt, then every decode step through
    the resident state, the tail and the cache, give the logits of the
    reference's full forward pass (the recurrence position by position,
    no cache) over the same ids: every row of the execution, alone or as
    one of four of unequal length."""
    served, stats = serve_rows(TINY, params, LENS[:rows])
    assert len({tuple(r["tokens"]) for r in served}) == rows
    for row in served:
        reading = compare(TINY, params, row)
        assert reading["correct"], reading
    Lm, La = TINY.layers_of(MAMBA), TINY.layers_of(ATTENTION)
    assert (Lm, La) == (4, 2)
    assert stats["prefill_positions"] == rows * PAD_TO
    assert stats["scan_chunks"] == rows * Lm * 3          # ceil(21 / 8)
    assert stats["state_steps"] == rows * Lm * NEW
    # step i's query sees the row's real ids and the i + 1 written
    assert list(stats["keys_attended_full"]) == [
        La * (NEW * n + NEW * (NEW + 1) // 2) for n in LENS[:rows]]


def _alone(cfg, params, b, n):
    ids = np.concatenate([prompt(b, n)])
    tokens, logits, _, _ = ssm_hybrid.make_program(cfg, NEW)(
        params, jnp.asarray(ids), np.asarray([n], np.int32),
        np.asarray([b + 3], np.uint32), np.asarray([0.7], np.float32))
    return np.asarray(tokens[0]), np.asarray(logits[0])


@pytest.mark.parametrize("pad", ["as seeded", "a large pad embedding"])
def test_a_row_of_a_shared_execution_is_its_single_row_run(pad, params):
    """The padding trap: a recurrence and a causal convolution carry
    whatever lies in front of a row's first real id forward, where an
    attention mask would hide it.  With the mixer's input zeroed and
    ``dt`` forced to 0 there, four rows of unequal length give, each,
    what they give alone; also where the pad id's embedding is large."""
    if pad != "as seeded":
        table = params["embed_tokens"]
        params = {**params, "embed_tokens": table.at[0].set(
            50.0 * jnp.sign(table[0]))}
    served, _ = serve_rows(TINY, params, LENS)
    for b, n in enumerate(LENS):
        tokens, logits = _alone(TINY, params, b, n)
        assert np.array_equal(tokens, served[b]["tokens"]), b
        np.testing.assert_allclose(served[b]["logits"], logits, rtol=0,
                                   atol=2e-6 * np.abs(logits).max())


def test_the_state_after_the_prefill_is_the_references_at_the_last_real_id(
        params):
    """Rows of unequal length: each row's recurrent state behind the
    prefill is the reference's behind its last REAL id, and its tail the
    last three inputs of the reference's convolution."""
    ids = np.concatenate([prompt(b, n) for b, n in enumerate(LENS)])
    _, state, first = jax.jit(
        lambda p, i, n: ssm_hybrid.prefill(TINY, p, i, n, PAD_TO + NEW))(
        params, jnp.asarray(ids), np.asarray(LENS, np.int32))
    assert list(first) == [PAD_TO - n for n in LENS]
    assert state["ssm"].dtype == jnp.float32
    assert state["ssm"].shape == (4, 4, 4, 32, 16)
    assert state["conv"].shape == (4, 4, 3, 32 * 4 + 2 * 16)
    for b, n in enumerate(LENS):
        _, want = ref.forward(hf(TINY), params, ids[b, :n])
        np.testing.assert_allclose(state["ssm"][:, b], want, rtol=0,
                                   atol=1e-5 * float(np.abs(want).max()))
    # the shortest row (3 ids) leaves a tail whose three inputs are all
    # real; one id shorter and its first would be the zeros in front
    assert float(jnp.abs(state["conv"][:, 1]).min(axis=-1).max()) > 0
    short = np.asarray([2], np.int32)
    _, state2, _ = ssm_hybrid.prefill(TINY, params, jnp.asarray(ids[1:2]),
                                      short, PAD_TO + NEW)
    assert float(jnp.abs(state2["conv"][:, 0, 0]).max()) == 0.0
    assert float(jnp.abs(state2["conv"][:, 0, 1]).max()) > 0


@pytest.mark.parametrize("T", [1, 5, 8, 13, 24])
def test_the_chunked_scan_is_the_sequential_recurrence(T):
    """Chunks of 8: shorter than one, exactly one, a partly filled last
    chunk, three whole ones.  ``dt`` of Mamba-2's range, some of it 0
    (padding in front)."""
    rng = np.random.default_rng(T)
    B, h, p, n = 2, 3, 4, 5
    x = rng.standard_normal((B, T, h, p)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (B, T, h))
                ).astype(np.float32)
    dt[1, :T // 3] = 0.0
    A = -rng.uniform(1, 16, h).astype(np.float32)
    Bm, Cm = (rng.standard_normal((B, T, n)).astype(np.float32)
              for _ in range(2))
    y, last = ssm_hybrid.chunked_scan(*map(jnp.asarray, (x, dt, A, Bm, Cm)),
                                      8, jnp.float32)
    for b in range(B):
        want_y, want_last = ref.recurrence(*map(
            jnp.asarray, (x[b], dt[b], A, Bm[b], Cm[b])))
        np.testing.assert_allclose(y[b], want_y, rtol=0, atol=2e-5)
        np.testing.assert_allclose(last[b], want_last, rtol=0, atol=2e-5)
    # and one step of the recurrence from that state is the next position
    y1, S1 = ssm_hybrid.state_step(last, *map(jnp.asarray, (
        x[:, 0], dt[:, 0] + 0.01, A, Bm[:, 0], Cm[:, 0])))
    for b in range(B):
        longer = [np.concatenate([a[b], a[b, :1]]) for a in (x, dt, Bm, Cm)]
        longer[1][-1] += 0.01
        want_y, want_last = ref.recurrence(*map(jnp.asarray, (
            longer[0], longer[1], A, longer[2], longer[3])))
        np.testing.assert_allclose(y1[b], want_y[-1], rtol=0, atol=2e-5)
        np.testing.assert_allclose(S1[b], want_last, rtol=0, atol=2e-5)


@pytest.mark.parametrize("cut", [1, 5, 8, 13, 23])
def test_the_chunked_scan_from_a_state_is_the_scan_of_the_whole(cut):
    """24 positions in chunks of 8, cut behind ``cut`` of them (inside a
    chunk, at a chunk's end, one position from either end): the scan of
    what follows FROM the state behind the cut gives the outputs and the
    last state of the scan of the whole, and of the recurrence; so the
    first chunk's outputs read the state they start from."""
    rng = np.random.default_rng(cut)
    B, T, h, p, n = 2, 24, 3, 4, 5
    x = rng.standard_normal((B, T, h, p)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (B, T, h))
                ).astype(np.float32)
    A = -rng.uniform(1, 16, h).astype(np.float32)
    Bm, Cm = (rng.standard_normal((B, T, n)).astype(np.float32)
              for _ in range(2))

    def scan(lo, hi, start=None):
        return ssm_hybrid.chunked_scan(
            *(jnp.asarray(a[:, lo:hi]) for a in (x, dt)), jnp.asarray(A),
            *(jnp.asarray(a[:, lo:hi]) for a in (Bm, Cm)), 8, jnp.float32,
            start)

    whole_y, whole_last = scan(0, T)
    _, behind = scan(0, cut)
    y, last = scan(cut, T, behind)
    np.testing.assert_allclose(y, whole_y[:, cut:], rtol=0, atol=2e-5)
    np.testing.assert_allclose(last, whole_last, rtol=0, atol=2e-5)
    assert float(jnp.abs(y - scan(cut, T)[0]).max()) > 1e-2
    for b in range(B):
        want_y, want_last = ref.recurrence(*map(
            jnp.asarray, (x[b], dt[b], A, Bm[b], Cm[b])))
        np.testing.assert_allclose(y[b], want_y[cut:], rtol=0, atol=2e-5)
        np.testing.assert_allclose(last[b], want_last, rtol=0, atol=2e-5)


@pytest.mark.parametrize("own", [1, 2, 3, 7])
def test_the_tail_stands_in_front_of_a_rows_own_first_position(own):
    """A buffer of 7 positions whose last ``own`` are the row's (zeros in
    front, as the mixer's input is at padding) behind a tail of 3: the
    taps at the row's first positions see the TAIL, not the padding, and
    a row shorter than the tail leaves a new tail that spans both."""
    rng = np.random.default_rng(own)
    T, C, taps = 7, 6, 4
    w = rng.standard_normal((taps, C)).astype(np.float32)
    bias = rng.standard_normal(C).astype(np.float32)
    before = rng.standard_normal((2, taps - 1, C)).astype(np.float32)
    rows = rng.standard_normal((2, own, C)).astype(np.float32)
    xbc = np.zeros((2, T, C), np.float32)
    xbc[:, T - own:] = rows
    out, tail = ssm_hybrid.causal_conv(
        jnp.asarray(xbc), jnp.asarray(w), jnp.asarray(bias),
        jnp.asarray(before), jnp.asarray([T - own] * 2))
    for b in range(2):
        whole = np.concatenate([before[b], rows[b]])
        want = ref.convolution(jnp.asarray(w), jnp.asarray(bias),
                               jnp.asarray(whole))
        np.testing.assert_allclose(out[b, T - own:], want[taps - 1:],
                                   rtol=0, atol=1e-6)
        np.testing.assert_array_equal(tail[b], whole[-(taps - 1):])
    # without the offset the tail lies in front of the padding
    if own < T:
        off, _ = ssm_hybrid.causal_conv(
            jnp.asarray(xbc), jnp.asarray(w), jnp.asarray(bias),
            jnp.asarray(before))
        assert float(jnp.abs(off - out)[:, T - own].max()) > 1e-2


# --- a prefix shared between requests -------------------------------------------
#
# Every row's prompt is the same 13 ids and then its own: 8, 1, 2 and 5 ids
# behind them in a suffix buffer of 8 (one row fills it, one is a single
# id, one is shorter than the convolution's 3 taps of tail).  The whole
# prompt is 21 positions at most, the buffer the tests above use.

PREFIX = 13
OWN = [8, 1, 2, 5]


def shared_prompts():
    """Per row the whole prompt: `prompt(9)`'s first 13 ids, then the
    row's own."""
    head = prompt(9)[0, :PREFIX]
    return [np.concatenate([head, prompt(b + 20, n)[0, :n]])
            for b, n in enumerate(OWN)]


def snapshot_of(cfg, params):
    return ssm_hybrid.make_prefix_program(cfg)(
        params, jnp.asarray(shared_prompts()[0][:PREFIX]))


def buffers(picked, held):
    """The prompt buffer of the rows ``picked`` with their first ``held``
    ids left out (a snapshot stands for them), and the lengths."""
    whole = shared_prompts()
    ids = np.zeros((len(picked), PAD_TO - held), np.int32)
    for b, i in enumerate(picked):
        ids[b, :len(whole[i]) - held] = whole[i][held:]
    return ids, np.asarray([len(whole[i]) - held for i in picked], np.int32)


def serve_shared(cfg, params, picked=(0, 1, 2, 3), snapshot="made",
                 temperature=0.7):
    """One execution over the rows ``picked`` of `shared_prompts`: from
    the snapshot of the 13 ids (made here where "made"), or with None
    the whole prompts through the full path."""
    if isinstance(snapshot, str):
        snapshot = snapshot_of(cfg, params)
    ids, lens = buffers(picked, 0 if snapshot is None else PREFIX)
    tokens, logits, _, stats = ssm_hybrid.make_program(cfg, NEW)(
        params, jnp.asarray(ids), lens,
        np.asarray(picked, np.uint32) + 3,
        np.asarray([temperature] * len(picked), np.float32),
        *(() if snapshot is None else (snapshot,)))
    whole = shared_prompts()
    rows = [{"prompt_ids": whole[i], "tokens": np.asarray(tokens[b]),
             "logits": np.asarray(logits[b])} for b, i in enumerate(picked)]
    return rows, {k: np.asarray(v) for k, v in stats.items()}


@pytest.mark.parametrize("picked", [(0,), (1,), (0, 1, 2, 3), (3, 2, 2, 2)])
@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_rows_started_from_a_snapshot_are_the_full_paths_and_the_references(
        picked, temperature, params):
    """Alone or four of unequal length (a row that fills the suffix
    buffer, a single id, two ids where the tail holds three, a padded
    execution whose last rows repeat one), greedy or sampled with seeds:
    the ids of the full path over the whole prompt, its logits to
    float32's rounding, and the reference's (the recurrence over ALL 21
    positions, no snapshot, no chunk) inside the limits every served
    path is held to."""
    served, stats = serve_shared(TINY, params, picked, temperature=temperature)
    full, full_stats = serve_shared(TINY, params, picked, None,
                                    temperature=temperature)
    for got, want in zip(served, full):
        assert np.array_equal(got["tokens"], want["tokens"])
        np.testing.assert_allclose(
            got["logits"], want["logits"], rtol=0,
            atol=2e-6 * np.abs(want["logits"]).max())
        limits = LIMITS if temperature else verify.LIMITS_FP32
        reading = compare(TINY, params, got, limits)
        assert reading["correct"], reading
    # what the program COMPUTED: the 8 positions behind the prefix (one
    # chunk), where the full path computed 21 (three)
    B, Lm, La = len(picked), 4, 2
    assert (stats["prefill_positions"], full_stats["prefill_positions"]) \
        == (B * (PAD_TO - PREFIX), B * PAD_TO)
    assert (stats["scan_chunks"], full_stats["scan_chunks"]) \
        == (B * Lm * 1, B * Lm * 3)
    assert stats["state_steps"] == full_stats["state_steps"] == B * Lm * NEW
    # the prefix's keys are READ by every decode step all the same
    assert list(stats["keys_attended_full"]) \
        == list(full_stats["keys_attended_full"]) == [
        La * (NEW * (PREFIX + OWN[i]) + NEW * (NEW + 1) // 2)
        for i in picked]


@pytest.mark.parametrize("pad", ["as seeded", "a large pad embedding"])
def test_a_row_behind_a_snapshot_is_its_single_row_run(pad, params):
    """Four rows of unequal length from one snapshot give, each, what
    they give alone through the 1-row program (whose suffix buffer they
    do not fill either: the padding lies BETWEEN nothing, in front of
    prefix and suffix both); also where the pad id's embedding is
    large."""
    if pad != "as seeded":
        table = params["embed_tokens"]
        params = {**params, "embed_tokens": table.at[0].set(
            50.0 * jnp.sign(table[0]))}
    snapshot = snapshot_of(TINY, params)
    served, _ = serve_shared(TINY, params, snapshot=snapshot)
    for b in range(4):
        (alone,), _ = serve_shared(TINY, params, (b,), snapshot)
        assert np.array_equal(alone["tokens"], served[b]["tokens"]), b
        np.testing.assert_allclose(
            served[b]["logits"], alone["logits"], rtol=0,
            atol=2e-6 * np.abs(alone["logits"]).max())


def test_the_snapshot_is_what_the_full_prefill_leaves_behind_the_prefix(
        params):
    """At the stored widths (a float32 state, the tail and the keys in
    the model's type), one row and no axis of rows: the reference's state
    behind id 13, the last three inputs of every Mamba layer's
    convolution and 13 keys and values an attention layer, the ones the
    full prefill of a longer prompt writes at those positions."""
    snapshot = snapshot_of(TINY, params)
    assert {k: (v.shape, v.dtype) for k, v in snapshot.items()} == {
        "ssm": ((4, 4, 32, 16), jnp.float32),
        "conv": ((4, 3, 160), TINY.dtype),
        "keys": ((2, PREFIX, 2, 16), TINY.dtype),
        "values": ((2, PREFIX, 2, 16), TINY.dtype)}
    assert sum(v.nbytes for v in snapshot.values()) \
        == ssm_hybrid.prefix_bytes(TINY, PREFIX)
    whole = shared_prompts()[0]
    _, want = ref.forward(hf(TINY), params, whole[:PREFIX])
    np.testing.assert_allclose(snapshot["ssm"], want, rtol=0,
                               atol=1e-5 * float(np.abs(want).max()))
    ids, lens = buffers((0,), 0)
    _, state, _ = ssm_hybrid.prefill(TINY, params, jnp.asarray(ids), lens,
                                     PAD_TO + NEW)
    for name in ("keys", "values"):
        want = np.asarray(state[name][:, 0, :PREFIX])
        np.testing.assert_allclose(snapshot[name], want, rtol=0,
                                   atol=1e-5 * float(np.abs(want).max()))
    # and behind the suffix prefill: the state behind each row's LAST id
    ids, lens = buffers((0, 1, 2, 3), PREFIX)
    _, state, first = jax.jit(
        lambda p, i, n, s: ssm_hybrid.prefill(TINY, p, i, n, PAD_TO + NEW, s))(
        params, jnp.asarray(ids), lens, snapshot)
    assert list(first) == [8 - n for n in OWN]
    for b, row in enumerate(shared_prompts()):
        _, want = ref.forward(hf(TINY), params, row)
        np.testing.assert_allclose(state["ssm"][:, b], want, rtol=0,
                                   atol=1e-5 * float(np.abs(want).max()))
    # the row of a single id: two inputs of its new tail are the prefix's
    np.testing.assert_array_equal(state["conv"][:, 1, :2],
                                  snapshot["conv"][:, 1:])
    # the cache: padding | prefix | own ids, each row at its own offset
    for b, n in enumerate(OWN):
        at = 8 - n
        np.testing.assert_array_equal(
            state["keys"][:, b, at:at + PREFIX], snapshot["keys"])
        assert float(jnp.abs(state["keys"][:, b, :at]).max(initial=0)) == 0


def test_the_maker_is_not_the_served_program_and_the_phases_stay(params):
    """The maker is ``lm_prefix_state``: the cells' pattern for the
    served program (``^jit_lm_generate$``) does not match it, so its
    seconds are no execution's.  The program that starts from a snapshot
    is still ``lm_generate``, with every class and both phases."""
    maker = ssm_hybrid.make_prefix_program(TINY).lower(
        params, jnp.zeros((PREFIX,), jnp.int32))
    assert "jit_lm_prefix_state" in maker.as_text()[:200]
    assert not re.match("^jit_lm_generate$", "jit_lm_prefix_state")
    ids, lens = buffers((0, 1, 2, 3), PREFIX)
    lowered = ssm_hybrid.make_program(TINY, 3).lower(
        params, jnp.asarray(ids), lens, np.zeros(4, np.uint32),
        np.zeros(4, np.float32), snapshot_of(TINY, params))
    assert "jit_lm_generate" in lowered.as_text()[:200]
    names = [n for n in re.findall(r'op_name="([^"]+)"',
                                   lowered.compile().as_text())
             if "GraniteMoeHybrid" in n]
    assert {trace.classify(n) for n in names} == LM_CLASSES
    assert {trace.phase_of(n) for n in names} == {"prefill", "decode"}
    # the rows' start from the snapshot is the state's and the cache's
    copies = {trace.classify(f"jit(lm_generate)/GraniteMoeHybrid/prefill/"
                             f"{scope}/x")
              for scope in ("ssm_state", "conv_state", "kv_cache")}
    assert copies == {"lm_state", "lm_cache"}


# --- what the comparison has to see -------------------------------------------

def _bf16_state(monkeypatch, params):
    return dataclasses.replace(TINY, state_dtype=jnp.bfloat16), params


def _dropped_skip(monkeypatch, params):
    mamba = params["mamba_layers"]
    return TINY, {**params, "mamba_layers": {
        **mamba, "D": jnp.zeros_like(mamba["D"])}}


def _tail_off_by_one(monkeypatch, params):
    real = ssm_hybrid.causal_conv

    def shifted(xbc, weight, bias, tail=None, at=None):
        out, new = real(xbc, weight, bias, tail, at)
        # keeps the three inputs in front of the last: a step too old
        return out, jnp.concatenate([jnp.zeros_like(new[:, :1]),
                                     new[:, :-1]], axis=1)
    monkeypatch.setattr(ssm_hybrid, "causal_conv", shifted)
    return TINY, params


def _pad_leaks(monkeypatch, params):
    """The padded positions take part: what `looplm.generate`'s contract
    alone (a mask for attention) does to a recurrence."""
    monkeypatch.setattr(ssm_hybrid, "_real_only", lambda real, a: a)
    return TINY, params


def _bf16_decay(monkeypatch, params):
    real = ssm_hybrid.state_step

    def rounded(S, x, dt, A, Bm, Cm):
        return real(S, x, dt.astype(jnp.bfloat16).astype(jnp.float32), A,
                    Bm, Cm)
    monkeypatch.setattr(ssm_hybrid, "state_step", rounded)
    return TINY, params


def _tail_in_front_of_the_padding(monkeypatch, params):
    real = ssm_hybrid.causal_conv
    monkeypatch.setattr(
        ssm_hybrid, "causal_conv",
        lambda xbc, weight, bias, tail=None, at=None: real(xbc, weight, bias,
                                                           tail))
    return TINY, params


def _keys_at_the_buffers_front(monkeypatch, params):
    real = ssm_hybrid.from_prefix
    monkeypatch.setattr(ssm_hybrid, "from_prefix",
                        lambda state, prefix, first: real(state, prefix,
                                                          0 * first))
    return TINY, params


def _padding_over_the_prefixs_end(monkeypatch, params):
    """Every position of the suffix buffer writes its keys, a row's
    padded ones too: over the last keys of its prefix."""
    monkeypatch.setattr(lm_decode, "own_entries",
                        lambda own, new, cache, l, at: new)
    return TINY, params


def _a_snapshot_in_bf16(monkeypatch, params):
    real = ssm_hybrid.make_prefix_program

    def rounded(cfg):
        made = real(cfg)
        return lambda p, ids: {
            k: v.astype(jnp.bfloat16).astype(v.dtype) if k == "ssm" else v
            for k, v in made(p, ids).items()}
    monkeypatch.setattr(ssm_hybrid, "make_prefix_program", rounded)
    return TINY, params


SHARED_BREAKAGES = {
    "the tail in front of the padding": _tail_in_front_of_the_padding,
    "the prefix's keys at the buffer's front": _keys_at_the_buffers_front,
    "padding written over the prefix's end": _padding_over_the_prefixs_end,
    "a snapshot whose state is bf16": _a_snapshot_in_bf16}


@pytest.mark.parametrize("what", SHARED_BREAKAGES)
def test_each_breakage_behind_a_snapshot_fails_the_comparison(
        what, params, monkeypatch):
    """The traps of a prefix in front of right-aligned rows, and a
    snapshot stored narrower than the state: the comparison with the
    reference of the WHOLE prompt refuses each, in a padded row; the row
    that fills its buffer has no padding for the first three to go wrong
    in."""
    cfg, broken = SHARED_BREAKAGES[what](monkeypatch, params)
    served, _ = serve_shared(cfg, broken)
    monkeypatch.undo()
    readings = [compare(TINY, params, row) for row in served]
    assert not all(r["correct"] for r in readings[1:]), readings
    if "bf16" not in what:
        assert readings[0]["correct"], readings[0]


BREAKAGES = {"a bf16 state": (_bf16_state, 1),
             "a dropped D skip": (_dropped_skip, 1),
             "a convolution tail off by one": (_tail_off_by_one, 1),
             "a pad that leaked into the state": (_pad_leaks, 4),
             "a bf16 dt in the decode step": (_bf16_decay, 1)}


@pytest.mark.parametrize("what", BREAKAGES)
def test_each_breakage_fails_the_comparison(what, params, monkeypatch):
    """Each of these leaves the shapes, the ids' range and most logits
    nearly alone; the comparison that passes the served path (the first
    test of this file) refuses every one, by at least one limit, in at
    least one row."""
    breakage, rows = BREAKAGES[what]
    cfg, broken = breakage(monkeypatch, params)
    served, _ = serve_rows(cfg, broken, LENS[:rows])
    monkeypatch.undo()
    readings = [compare(TINY, params, row) for row in served]
    assert not all(r["correct"] for r in readings), readings
    if rows == 4:
        # the row with no padding has nothing to leak
        assert readings[0]["correct"], readings[0]


# --- sizes, bytes, layout -----------------------------------------------------

def test_the_published_model_is_the_issues_arithmetic():
    """3,191,396,096 values: 36 Mamba blocks, 4 attention blocks, the
    embedding ONCE (tied: the tree has no ``lm_head``), a final norm."""
    shapes = ssm_hybrid.param_shapes(FULL)
    assert set(shapes) == {"embed_tokens", "mamba_layers",
                           "attention_layers", "norm"}
    count = lambda tree: ssm_hybrid.count_values(tree)      # noqa: E731
    mamba, attention = shapes["mamba_layers"], shapes["attention_layers"]
    mlp = 2048 * 16384 + 8192 * 2048
    assert count(mamba) == 36 * 76_182_976
    assert count(attention) == 4 * 60_821_504
    mixer = {k: mamba[k] for k in (
        "in_proj_zx", "in_proj_dt", "conv1d_weight", "conv1d_bias",
        "dt_bias", "A_log", "D", "norm", "out_proj")}
    assert count(mixer) == 36 * 25_847_232
    # the published in_proj's 8512 columns, in two leaves
    assert mamba["in_proj_zx"][-1] + mamba["in_proj_dt"][-1] == 8512 \
        == 2 * 4096 + 2 * 128 + 64
    assert mamba["in_proj_zx"][-1] % 128 == 0 and 8512 % 128 == 64
    assert count({k: attention[k] for k in
                  ("q_proj", "k_proj", "v_proj", "o_proj")}) == 4 * 10_485_760
    assert count({k: mamba[k] for k in ("input_linear", "output_linear")}) \
        == 36 * mlp == 36 * 50_331_648
    assert shapes["embed_tokens"] == (100352, 2048)
    assert ssm_hybrid.param_count(FULL) == 3_191_396_096 \
        == count(mamba) + count(attention) + 205_520_896 + 2048
    assert FULL.layer_types == (("mamba",) * 5 + ("attention",)
                                + ("mamba",) * 4) * 4
    assert [(r.kind, r.start, r.count) for r in FULL.runs] == [
        (MAMBA, 0, 5), (ATTENTION, 0, 1), (MAMBA, 5, 9), (ATTENTION, 1, 1),
        (MAMBA, 14, 9), (ATTENTION, 2, 1), (MAMBA, 23, 9), (ATTENTION, 3, 1),
        (MAMBA, 32, 4)]
    assert FULL.layer_applications == 40 and FULL.head_dim == 64


def test_the_states_bytes_by_kind():
    """A row's recurrent state is no function of the positions; its keys
    and values are 8 KiB a position."""
    ssm = 64 * 64 * 128 * 4
    tail = 3 * 4352 * 2
    assert ssm_hybrid.state_bytes(FULL, 1) == 36 * (ssm + tail) == 76_437_504
    assert ssm_hybrid.kv_cache_bytes(FULL, 1, 1) == 4 * 2 * 8 * 64 * 2 == 8192
    by_kind = ssm_hybrid.kv_cache_bytes_by_kind(FULL, 4, 2112)
    assert by_kind == {"recurrent": 4 * 76_437_504,
                       "positional": 4 * 2112 * 8192}
    assert ssm_hybrid.state_bytes(FULL, 4) == by_kind["recurrent"]
    state = jax.eval_shape(lambda: ssm_hybrid.empty_state(FULL, 4, 2112))
    nbytes = {k: int(np.prod(v.shape)) * v.dtype.itemsize
              for k, v in state.items()}
    assert nbytes["ssm"] + nbytes["conv"] == by_kind["recurrent"]
    assert nbytes["keys"] + nbytes["values"] == by_kind["positional"]


@pytest.mark.parametrize("name, k, n, four_rows", [
    ("in_proj_zx", 2048, 8448, "fewrow"),
    ("the published in_proj, undivided", 2048, 8512, "xla"),
    ("in_proj_dt", 2048, 64, "xla"),
    ("out_proj", 4096, 2048, "fewrow"),
    ("input_linear", 2048, 16384, "fewrow"),
    ("output_linear", 8192, 2048, "fewrow"),
    ("q_proj, o_proj", 2048, 2048, "fewrow"),
    ("k_proj+v_proj", 2048, 512, "fewrow"),
    ("the tied head", 2048, 100352, "fewrow"),
])
def test_the_lowering_of_each_of_the_familys_products(name, k, n, four_rows):
    """At 4 rows on a TPU every large product streams its leaf through
    the few-row kernel, which is why ``in_proj`` is stored in two leaves
    (8512 columns are 66.5 blocks of 128); one row, the prefill's 4 x 2048
    and every other backend keep ``jnp.dot``."""
    assert looplm.dense_path("tpu", 4, k, n) == four_rows
    for rows in (1, 2048, 8192):
        assert looplm.dense_path("tpu", rows, k, n) == "xla"
    assert looplm.dense_path("cpu", 4, k, n) == "xla"


def test_the_call_sites_say_which_lowering_each_took(params, monkeypatch):
    """``dense_paths`` counts a program's products by lowering and rows
    while it is traced; with the platform read as a TPU's the 4-row
    program of the published size streams every large leaf (the tied head
    among them), and its attention call sites are the masked ones."""
    def traced(rows, where):
        monkeypatch.setattr(looplm, "_where", lambda: where)
        spec = jax.ShapeDtypeStruct
        shapes = jax.tree_util.tree_map(
            lambda s: spec(s, FULL.dtype), ssm_hybrid.param_shapes(FULL),
            is_leaf=lambda x: isinstance(x, tuple))
        before = (trace.DENSE_PATHS.snapshot(),
                  trace.ATTENTION_PATHS.snapshot())
        text = str(ssm_hybrid.make_program(FULL, 2).trace(
            shapes, spec((rows, 512), np.int32), spec((rows,), np.int32),
            spec((rows,), np.uint32), spec((rows,), np.float32)).jaxpr)
        return text, [{k: v - b.get(k, 0) for k, v in now.items()
                       if v != b.get(k, 0)} for now, b in zip(
            (trace.DENSE_PATHS.snapshot(), trace.ATTENTION_PATHS.snapshot()),
            before)]

    text, (dense, attention) = traced(4, ("tpu", None))
    # a run is a call site: 5 Mamba runs x (zx, out, MLP in, MLP out) and
    # 4 attention runs x (q, k, v, o, MLP in, MLP out), and the head
    assert dense == {"fewrow_few": 5 * 4 + 4 * 6, "fewrow_tied_few": 2,
                     "xla_few": 5, "xla_many": 5 * 5 + 4 * 6}
    assert attention == {"xla_causal": 4, "xla_decode": 4}
    assert "fewrow_dense_t" in text and "fewrow_dense_k_proj_v_proj" in text
    text, (dense, _) = traced(1, ("tpu", None))
    assert dense == {"xla_one": 5 * 5 + 4 * 6, "xla_tied_one": 2,
                     "xla_many": 5 * 5 + 4 * 6}
    assert "pallas_call" not in text
    assert trace.counters_snapshot()["dense_paths"] == \
        trace.DENSE_PATHS.snapshot()


# --- the compiled program -----------------------------------------------------

@pytest.fixture(scope="module")
def compiled_text(params):
    return ssm_hybrid.make_program(TINY, 3).lower(
        params, jnp.zeros((4, 16), jnp.int32), np.zeros(4, np.int32) + 9,
        np.zeros(4, np.uint32), np.zeros(4, np.float32)).compile().as_text()


LM_CLASSES = {"lm_proj", "lm_attn", "lm_cache", "lm_mlp", "lm_ssm",
              "lm_state", "lm_norm", "lm_head", "embed"}


def test_every_class_and_both_phases_are_in_the_compiled_program(
        compiled_text):
    names = [n for n in re.findall(r'op_name="([^"]+)"', compiled_text)
             if "GraniteMoeHybrid" in n]
    assert len(names) > 200
    assert {trace.classify(n) for n in names} == LM_CLASSES
    assert {trace.phase_of(n) for n in names} == {"prefill", "decode"}


def test_a_decode_step_copies_no_cache(compiled_text):
    """The state of both kinds goes through the decode scan's carry.  A
    step writes one position of a layer's keys and values in place: no
    instruction under ``decode`` but the loops' own tuples has a whole
    cache as its RESULT unless it is the in-place
    ``dynamic-update-slice`` (or a fusion rooted in one).  (XLA's CPU
    backend unrolls the tiny model's runs of two Mamba blocks and then
    copies the recurrent state once a step; that the chip's compiler
    overwrites it in place at the published size is
    tests/test_fewrow_dense.py's to hold, on the program compiled for a
    described v5e.)"""
    shapes = {"f32[2,4,19,2,16]"}
    copies = []
    for line in compiled_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.-]+) = (\S+) (\w[\w-]*)\(", line)
        if not m or "GraniteMoeHybrid/decode" not in line:
            continue
        name, result, op = m.groups()
        if result.split("{")[0] in shapes and op not in (
                "dynamic-update-slice", "get-tuple-element", "parameter",
                "bitcast") and "dynamic_update_slice" not in line \
                and "dynamic-update-slice" not in name:
            copies.append(line.strip()[:160])
    assert not copies, copies


@pytest.mark.parametrize("path, want, phase", [
    ("prefill/mamba_layers/while/body/mamba/in_proj/dot_general", "lm_proj",
     "prefill"),
    ("decode/while/body/mamba_layers/while/body/mamba/in_proj/fewrow_dense/"
     "pallas_call", "lm_proj", "decode"),
    ("decode/while/body/mamba_layers/while/body/mamba/out_proj/dot_general",
     "lm_proj", "decode"),
    ("prefill/mamba_layers/while/body/mamba/conv1d/mul", "lm_ssm",
     "prefill"),
    ("prefill/mamba_layers/while/body/mamba/ssm/bchqs,bcshp->bcqhp/"
     "dot_general", "lm_ssm", "prefill"),
    ("prefill/mamba_layers/while/body/mamba/ssm/while/body/mul", "lm_ssm",
     "prefill"),
    ("decode/while/body/mamba_layers/while/body/mamba/ssm/exp", "lm_ssm",
     "decode"),
    # the gated norm is ``mamba/norm``: not the image models' ``norm``
    ("decode/while/body/mamba_layers/while/body/mamba/norm/rsqrt", "lm_ssm",
     "decode"),
    ("prefill/mamba_layers/while/body/mamba/select_n", "lm_ssm", "prefill"),
    ("decode/while/body/mamba_layers/while/body/mamba/ssm_state/"
     "dynamic_update_slice", "lm_state", "decode"),
    ("decode/while/body/mamba_layers/while/body/mamba/conv_state/"
     "dynamic_slice", "lm_state", "decode"),
    ("prefill/mamba_layers/while/body/mamba/ssm_state/dynamic_update_slice",
     "lm_state", "prefill"),
    ("prefill/attention_layers/while/body/self_attn/q_proj/dot_general",
     "lm_proj", "prefill"),
    ("decode/while/body/attention_layers/while/body/self_attn/kv_cache/"
     "dynamic_update_slice", "lm_cache", "decode"),
    ("prefill/attention_layers/while/body/self_attn/while/body/"
     "bnhd,bmhd->bhnm/dot_general", "lm_attn", "prefill"),
    ("decode/while/body/mamba_layers/while/body/shared_mlp/input_linear/"
     "dot_general", "lm_mlp", "decode"),
    ("prefill/attention_layers/while/body/shared_mlp/mul", "lm_mlp",
     "prefill"),
    ("prefill/mamba_layers/while/body/input_layernorm/rsqrt", "lm_norm",
     "prefill"),
    ("decode/while/body/attention_layers/while/body/"
     "post_attention_layernorm/mul", "lm_norm", "decode"),
    ("decode/while/body/final_norm/mul", "lm_norm", "decode"),
    ("decode/while/body/lm_head/fewrow_dense_t/pallas_call", "lm_head",
     "decode"),
    ("decode/while/body/sample/argmax", "lm_head", "decode"),
    ("prefill/embed_tokens/gather", "embed", "prefill"),
    ("prefill/mamba_layers/while/body/add", "lm_proj", "prefill"),
])
def test_the_scopes_fall_in_their_classes_and_phases(path, want, phase):
    name = "jit(lm_generate)/GraniteMoeHybrid/" + path
    assert trace.classify(name) == want
    assert trace.phase_of(name) == phase
    # and the image models' norms are still theirs
    assert trace.classify("jit(core)/UNet/mid_attn/norm/add") == "norm"


# --- through the registry: counters, gauges, the name --------------------------

def counters():
    return dict(trace.GLOBAL_COUNTERS.snapshot())


@pytest.fixture
def model(monkeypatch):
    monkeypatch.setenv("DTPU_DEFAULT_FAMILY", "tiny")
    trace.install_jax_monitoring()      # the guards below count compiles
    return registry.load_language_model("granite-4.0-h-micro.safetensors")


def test_the_registry_serves_it_and_counts_positions_chunks_and_steps(
        model, assert_nothing_compiled):
    """`load_language_model` by name -> `generate_rows`: the ``lm.*``
    counters of PR 28-34 keep their meaning, what the program computed
    comes over in the same read, and two gauges say the state of each
    kind; a second execution of the shape compiles nothing.  The rows
    carry the same instructions (7 ids with the first), so each starts
    from their snapshot and the program computes the 25 positions behind
    it."""
    assert model.family == "granite" and model.cfg == TINY
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
    assert got["lm.layer_applications"] == 15 * 6
    # every row of the program, the padded one too
    assert got["lm.prefill_positions"] == 4 * (32 - 7)
    assert got["lm.scan_chunks"] == 4 * 4 * 4              # rows x Lm x 25/8
    assert got["lm.state_steps"] == 4 * 4 * 5              # rows x Lm x steps
    real = got["lm.prompt_tokens"]                          # of three rows
    assert real > 3 * 7
    # the prefix's keys are read in every step
    assert got["lm.keys_attended_full"] == 2 * (
        5 * real + 3 * (1 + 2 + 3 + 4 + 5))
    assert not got.get("lm.expert_pairs")   # (0 where a worker ran an expert family first)
    # the three real rows, from the snapshot the first request made
    assert got["lm.prefix_hits"] == 3
    assert got["lm.prefix_positions_served"] == 3 * 7
    assert got.get("lm.prefix_misses", 0) == 0 and before[
        "lm.prefix_misses"] >= 1
    gauges = trace.GLOBAL_GAUGES.snapshot()
    assert gauges["lm.state_bytes"] == ssm_hybrid.state_bytes(TINY, 4) \
        == gauges["lm.kv_cache_bytes_recurrent"] \
        == 4 * 4 * (4 * 32 * 16 * 4 + 3 * 160 * 4)
    assert gauges["lm.kv_cache_bytes"] == \
        ssm_hybrid.kv_cache_bytes(TINY, 4, 37) == \
        gauges["lm.kv_cache_bytes_positional"] == 2 * 2 * 4 * 37 * 2 * 16 * 4
    words, lm_out = out[2]
    assert lm_out.row == 2 and lm_out.aux == {}
    assert len(words.split()) <= 5


def lm_delta(before):
    after = counters()
    return {k[3:]: after[k] - before.get(k, 0) for k in after
            if k.startswith("lm.") and after[k] != before.get(k, 0)}


def asked(model, rows, **kw):
    before = counters()
    out = model.generate_rows(rows, max_new_tokens=3, prompt_tokens=32, **kw)
    return [words for words, _ in out], lm_delta(before)


def guide(i, words=6):
    """Instructions of ``words`` words (``words`` + 1 ids)."""
    return " ".join(["style", "guide", "number", str(i), "of", "many",
                     "more", "words"][:words])


def test_rows_from_a_snapshot_get_the_words_of_the_whole_prompt(
        model, monkeypatch):
    """Through the registry, three rows of one set of instructions: the
    words of the same rows with the whole prompt scanned (the rule held
    off), and of each row alone."""
    rows = [registry.LMRow(f"a walled garden in june number {i}", i, 0.7 * i,
                           instructions=guide(0)) for i in range(3)]
    words, got = asked(model, rows)
    assert got["prefix_hits"] == 3 and got["prefill_positions"] == 4 * 25
    for i, row in enumerate(rows):
        assert asked(model, [row])[0] == [words[i]]
    monkeypatch.setattr(registry.LanguageModel, "shared_prefix",
                        lambda self, *a: None)
    whole, got = asked(model, rows)
    assert whole == words and len(set(words)) == 3
    assert "prefix_hits" not in got and got["prefill_positions"] == 4 * 32


def test_snapshots_are_made_once_kept_and_the_least_recently_used_let_go(
        model, assert_nothing_compiled):
    """As many snapshots as an execution has rows: a fifth set of
    instructions lets the least recently USED go, which is made again at
    its next request; other instructions of a known length compile
    nothing, nor does anything already seen."""
    model._prefixes.clear()
    row = lambda i, words=6: registry.LMRow(            # noqa: E731
        "a harbour at night", 5, instructions=guide(i, words))
    assert asked(model, [row(0)])[1]["prefix_misses"] == 1
    mark = trace.GLOBAL_RETRACES.mark()
    for i in (1, 2, 3):
        got = asked(model, [row(i)])[1]
        assert (got["prefix_misses"], got["prefix_hits"],
                got["prefix_positions_served"]) == (1, 1, 7)
        assert "prefix_evictions" not in got
    one = ssm_hybrid.prefix_bytes(TINY, 7)
    assert one == 4 * (4 * 32 * 16 * 4 + 3 * 160 * 4) \
        + 2 * 2 * 7 * 2 * 16 * 4
    assert trace.GLOBAL_GAUGES.snapshot()["lm.prefix_bytes"] == 4 * one
    # the oldest is used again, so the second oldest is the one to go
    got = asked(model, [row(0)])[1]
    assert "prefix_misses" not in got and got["prefix_hits"] == 1
    got = asked(model, [row(4)])[1]
    assert (got["prefix_misses"], got["prefix_evictions"]) == (1, 1)
    assert [np.frombuffer(k, np.int32)[4] for k in model._prefixes] == [
        model.tokenizer.encode(str(i))[1] for i in (2, 3, 0, 4)]
    assert trace.GLOBAL_GAUGES.snapshot()["lm.prefix_bytes"] == 4 * one
    got = asked(model, [row(1)])[1]
    assert (got["prefix_misses"], got["prefix_evictions"]) == (1, 1)
    assert_nothing_compiled(trace.GLOBAL_RETRACES.since(mark))
    # a new LENGTH is a maker and a program a row count
    mark = trace.GLOBAL_RETRACES.mark()
    got = asked(model, [row(0, 8)])[1]
    assert got["prefix_positions_served"] == 9
    assert got["prefill_positions"] == 32 - 9
    assert trace.GLOBAL_RETRACES.since(mark)["compiles"] == 3
    assert sorted(model._prefix_makers) == [7, 9]
    assert {k for k in model._programs if k[:2] == (3, 32)} >= {
        (3, 32, 7), (3, 32, 9)}


@pytest.mark.parametrize("what, rows, tokens", [
    ("no instructions", [("a cat", "")] * 2, 32),
    ("instructions that differ between the rows",
     [("a cat", guide(0)), ("a dog", guide(1))], 32),
    ("one row without", [("a cat", guide(0)), ("a dog", "")], 32),
    ("a prompt cut inside the instructions", [("a cat", guide(0, 8))], 6),
    ("instructions that fill the buffer", [("a cat", guide(0))] * 2, 7),
])
def test_what_the_rule_does_not_find_runs_the_whole_prompt(what, rows,
                                                           tokens, model):
    """The rule reads its input: the same non-empty instructions in every
    row, their ids in front of at least one id of each row.  Everything
    else is the execution it was: every position computed, no snapshot
    made, none counted."""
    rows = [registry.LMRow(text, i, instructions=instructions)
            for i, (text, instructions) in enumerate(rows)]
    assert model.shared_prefix(rows, tokens) is None
    before = counters()
    model.generate_rows(rows, max_new_tokens=3, prompt_tokens=tokens)
    got = lm_delta(before)
    count = 1 if len(rows) == 1 else 4
    assert got["prefill_positions"] == count * tokens
    assert not [k for k in got if k.startswith("prefix_")]
    # one more position, or the same instructions in both: it finds one
    if what.startswith(("a prompt cut", "instructions that fill")):
        assert len(model.shared_prefix(rows, 32)) in (7, 9)


def test_a_family_that_offers_no_snapshot_runs_as_it_did(monkeypatch):
    """K-EXAONE's rows share instructions too; its keys are rotated by
    their position, the family offers no maker, and nothing of this is
    counted for it."""
    monkeypatch.setenv("DTPU_DEFAULT_FAMILY", "tiny")
    other = registry.load_language_model("k-exaone-236b-a23b.safetensors")
    rows = [registry.LMRow(f"a cat number {i}", i, instructions=guide(0))
            for i in range(2)]
    assert other.shared_prefix(rows, 32) is None
    before = counters()
    other.generate_rows(rows, max_new_tokens=3, prompt_tokens=32)
    got = lm_delta(before)
    assert got["executions"] == 1 and got["rows"] == 2
    assert not [k for k in got if k.startswith("prefix_")]
    assert "prefill_positions" not in got
    assert not any(k[2] for k in other._programs)


@pytest.mark.parametrize("name, want", [
    ("granite-4.0-h-micro.safetensors", ("granite", "full")),
    ("Granite-4.0-H-tiny-test.safetensors", ("granite", "tiny")),
])
def test_a_model_name_names_the_fourth_family(name, want, monkeypatch):
    monkeypatch.delenv("DTPU_DEFAULT_FAMILY", raising=False)
    assert registry.detect_lm_family(name) == want
    with pytest.raises(ValueError) as e:
        registry.detect_lm_family("some-other-decoder-7b.safetensors")
    assert "granite" in str(e.value) and "exaone" in str(e.value)
    assert list(registry.LM_FAMILIES)[:4] == ["ouro", "pangu", "exaone",
                                              "granite"]


def test_a_second_language_model_that_cannot_fit_is_refused_by_name(
        monkeypatch):
    monkeypatch.delenv("DTPU_DEFAULT_FAMILY", raising=False)
    monkeypatch.setattr(registry, "_device_free_bytes",
                        lambda: int(15.7e9 - 7.42e9 - 2.6e9))
    name = "granite-4.0-h-micro-of-another-graph.safetensors"   # not cached
    with pytest.raises(ValueError) as e:
        registry.load_language_model(name)
    assert name in str(e.value) and "6.38 GB" in str(e.value)


def test_a_published_state_dict_loads_as_the_tree_the_program_serves(
        params, tmp_path):
    """The family's Hugging Face names and layouts (linear weights ``[out,
    in]``, ``mamba.in_proj`` whole with its columns z | xBC | dt, the
    convolution ``[channels, 1, taps]``) written from the seeded tree and
    read back: the same tree, so the same logits."""
    from safetensors.numpy import save_file
    sd = {"model.embed_tokens.weight": np.asarray(params["embed_tokens"]),
          "model.norm.weight": np.asarray(params["norm"])}
    at = {MAMBA: 0, ATTENTION: 0}
    for l, kind in enumerate(TINY.layer_types):
        lp = {k: np.asarray(v[at[kind]])
              for k, v in params[ssm_hybrid.STACKS[kind]].items()}
        at[kind] += 1
        pre = f"model.layers.{l}."
        for name in ("input_layernorm", "post_attention_layernorm"):
            sd[pre + name + ".weight"] = lp[name]
        for name in ("input_linear", "output_linear"):
            sd[pre + f"shared_mlp.{name}.weight"] = lp[name].T
        if kind == ATTENTION:
            for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
                sd[pre + f"self_attn.{name}.weight"] = lp[name].T
            continue
        sd[pre + "mamba.in_proj.weight"] = np.concatenate(
            [lp["in_proj_zx"], lp["in_proj_dt"]], axis=1).T
        sd[pre + "mamba.conv1d.weight"] = lp["conv1d_weight"].T[:, None, :]
        sd[pre + "mamba.conv1d.bias"] = lp["conv1d_bias"]
        sd[pre + "mamba.norm.weight"] = lp["norm"]
        sd[pre + "mamba.out_proj.weight"] = lp["out_proj"].T
        for name in ("dt_bias", "A_log", "D"):
            sd[pre + f"mamba.{name}"] = lp[name]
    path = str(tmp_path / "granite-tiny.safetensors")
    save_file({k: np.ascontiguousarray(v) for k, v in sd.items()}, path)
    loaded = ssm_hybrid.load_checkpoint(path, TINY)
    assert jax.tree_util.tree_structure(loaded) == \
        jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(loaded),
                    jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_the_seeded_recurrence_is_not_trivial(params):
    """``A`` in 1..16, ``dt`` log-uniform in 1e-3..1e-1, ``D`` 1, as
    Mamba-2 initialises them: the decay of a step lies strictly between 0
    and 1 for every head, so a wrong state is a different logit."""
    mamba = params["mamba_layers"]
    A = np.exp(np.asarray(mamba["A_log"], np.float64))
    dt = np.log1p(np.exp(np.asarray(mamba["dt_bias"], np.float64)))
    assert 1.0 <= A.min() and A.max() <= 16.0 and A.std() > 2
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    decay = np.exp(-dt * A)
    assert 0.15 < decay.min() and decay.max() < 0.9999
    assert np.all(np.asarray(mamba["D"]) == 1.0)
    assert abs(float(np.asarray(params["embed_tokens"]).std())
               - ssm_hybrid.EMBED_STD) < 1e-3
