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

from comfyui_distributed_tpu.models import looplm, registry, ssm_hybrid
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


# --- what the comparison has to see -------------------------------------------

def _bf16_state(monkeypatch, params):
    return dataclasses.replace(TINY, state_dtype=jnp.bfloat16), params


def _dropped_skip(monkeypatch, params):
    mamba = params["mamba_layers"]
    return TINY, {**params, "mamba_layers": {
        **mamba, "D": jnp.zeros_like(mamba["D"])}}


def _tail_off_by_one(monkeypatch, params):
    real = ssm_hybrid.causal_conv

    def shifted(xbc, weight, bias, tail=None):
        out, new = real(xbc, weight, bias, tail)
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
    return registry.load_language_model("granite-4.0-h-micro.safetensors")


def test_the_registry_serves_it_and_counts_positions_chunks_and_steps(
        model, assert_nothing_compiled):
    """`load_language_model` by name -> `generate_rows`: the ``lm.*``
    counters of PR 28-34 keep their meaning, what the program computed
    comes over in the same read, and two gauges say the state of each
    kind; a second execution of the shape compiles nothing."""
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
    assert got["lm.prefill_positions"] == 4 * 32
    assert got["lm.scan_chunks"] == 4 * 4 * 4              # rows x Lm x 32/8
    assert got["lm.state_steps"] == 4 * 4 * 5              # rows x Lm x steps
    real = got["lm.prompt_tokens"]                          # of three rows
    assert got["lm.keys_attended_full"] == 2 * (
        5 * real + 3 * (1 + 2 + 3 + 4 + 5))
    assert "lm.expert_pairs" not in got
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
    assert list(registry.LM_FAMILIES) == ["ouro", "pangu", "exaone",
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
