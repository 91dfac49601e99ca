"""The looped language model (models/looplm.py) against its plain
reference (benchmarks/chip/reference/looplm.py) on seeded weights, at a
tiny size: d 64, L 3, R 4, 4 heads of 16, FFN 176, V 512.  Logits, not
tokens: with random weights the largest logit changes on rounding.

The comparison and its limits are verify_lm.py's, the ones the chip run
uses at the published widths; each breakage the issue names (a loop
dropped, two loops sharing a slot, a sandwich norm left out, the cache or
the weights held in 8 bits) has to fail them.
"""

import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import looplm
from comfyui_distributed_tpu.models.layers import (
    attention_path, scaled_dot_product_attention, xla_attention)
from comfyui_distributed_tpu.utils import trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("looplm_reference", os.path.join(BENCH, "reference", "looplm.py"))
sys.path.insert(0, BENCH)
verify = _load("chipbench_verify_lm", os.path.join(BENCH, "verify_lm.py"))

TINY = looplm.TINY_LOOPLM
PROMPT, NEW, PAD_TO = 9, 6, 16

# The comparison's limits for the tiny model in bf16 (the chip's, for 48
# layers of width 2048, are verify_lm.LIMITS).  Why the served path
# differs from the float32 reference at all: its matmul operands and its
# cache are bf16 (8 bits of mantissa) and 12 layer applications add their
# roundings up.  Measured here: mean 0.0017-0.0018, max 0.008-0.011 of a
# logit's standard deviation.  With the cache in 8 bits (3 bits of
# mantissa): mean 0.0069-0.0076, max 0.033-0.044.  The limits lie a
# factor of two from either reading.
TINY_BF16_LIMITS = {"max_over_std": 0.02, "mean_over_std": 0.0036,
                    "margin_over_std": 0.04}


def hf(cfg):
    return {k: v for k, v in dataclasses.asdict(cfg).items() if k != "dtype"}


def make_params(cfg, seed=7):
    return jax.jit(lambda s: looplm.init_params(cfg, s))(np.uint32(seed))


def prompt(seed=0, n=PROMPT):
    ids = np.zeros((1, PAD_TO), np.int32)
    ids[0, :n] = np.random.default_rng(seed).integers(3, TINY.vocab_size, n)
    return ids


def serve(cfg, params, ids, n=PROMPT, new=NEW, temperature=0.0, seed=3):
    """The served path: prefill, then decode through the cache."""
    tokens, logits, exits = looplm.make_generate(cfg, new)(
        params, jnp.asarray(ids), np.int32(n), np.uint32(seed),
        np.float32(temperature))
    return np.asarray(tokens[0]), np.asarray(logits[0]), np.asarray(exits[0])


def serve_rows(cfg, params, lens, new=NEW, temperatures=None, seeds=None,
               ids=None):
    """One execution over rows of the real lengths ``lens`` (row ``b``'s
    prompt is ``prompt(b, lens[b])``), each with its own seed and
    temperature: ``tokens [B, N]``, ``logits [B, N, V]``, ``exits``."""
    if ids is None:
        ids = np.concatenate([prompt(b, n) for b, n in enumerate(lens)])
    out = looplm.make_generate(cfg, new)(
        params, jnp.asarray(ids), np.asarray(lens, np.int32),
        np.asarray(seeds or [3] * len(lens), np.uint32),
        np.asarray(temperatures or [0.0] * len(lens), np.float32))
    return tuple(np.asarray(x) for x in out)


def reference_rows(cfg, params, ids, tokens, n=PROMPT):
    """The reference's full forward pass, teacher-forced over the prompt
    and the served ids: the rows each served token was drawn from."""
    full = np.concatenate([ids[0, :n], tokens])
    logits, exits = ref.forward(hf(cfg), params, full)
    rows = slice(n - 1, n - 1 + len(tokens))
    return np.asarray(logits)[rows], np.asarray(exits)[rows]


@pytest.fixture(scope="module")
def params():
    return make_params(TINY)


@pytest.fixture(scope="module")
def served(params):
    return serve(TINY, params, prompt())


# --- the served path against the reference ---------------------------------

@pytest.mark.parametrize("loops", [1, 2, 3, 4])
def test_prefill_and_cached_decode_match_the_reference_fp32(loops):
    """Tolerance 1e-4 of a standard deviation (measured 3e-6): in float32
    the two differ only in the order of their additions."""
    cfg = dataclasses.replace(TINY, total_ut_steps=loops)
    p = make_params(cfg)
    tokens, logits, exits = serve(cfg, p, prompt(loops))
    want, want_exits = reference_rows(cfg, p, prompt(loops), tokens)
    got = verify.compare_logits(logits, want, tokens, verify.LIMITS_FP32)
    assert got["correct"], got
    assert exits.shape == (NEW, loops)
    np.testing.assert_allclose(exits, want_exits, atol=1e-5)


def test_bf16_weights_and_cache_stay_inside_their_limits():
    """bf16 operands and cache against the float32 reference over the
    same (bf16) weights, within TINY_BF16_LIMITS (their reasons stand
    beside them), and so within the chip's, which are for a model
    sixteen times as deep."""
    cfg = dataclasses.replace(TINY, dtype=jnp.bfloat16)
    p = make_params(cfg)
    assert {x.dtype for x in jax.tree_util.tree_leaves(p)} == {jnp.dtype(jnp.bfloat16)}
    tokens, logits, _ = serve(cfg, p, prompt())
    assert logits.dtype == np.float32
    want, _ = reference_rows(cfg, p, prompt(), tokens)
    got = verify.compare_logits(logits, want, tokens, TINY_BF16_LIMITS)
    assert got["correct"], got
    assert verify.compare_logits(logits, want, tokens)["correct"]
    assert got["mean_over_std"] > 1e-4      # and bf16 is what ran


def test_one_loop_is_the_plain_stack_run_once(params):
    cfg = dataclasses.replace(TINY, total_ut_steps=1)
    tokens, logits, _ = serve(cfg, params, prompt())
    full = np.concatenate([prompt()[0, :PROMPT], tokens])
    x = ref.f32(params["embed_tokens"])[full]
    for l in range(cfg.num_hidden_layers):
        x = ref.layer(hf(cfg), ref.layer_params(params, l), x)
    x, _ = ref.end_of_loop(hf(cfg), params, x)
    want = np.asarray(ref.head(params, x))[PROMPT - 1:-1]
    np.testing.assert_allclose(logits, want, atol=1e-4)


def test_a_loop_adds_no_weight():
    counts = {looplm.param_count(dataclasses.replace(TINY, total_ut_steps=r))
              for r in (1, 2, 4, 8)}
    assert counts == {216961}
    # the published model: 48 x 51,388,416 + 2 x 49152 x 2048 + 2048, and
    # the exit gate's 2049
    assert looplm.param_count(looplm.OURO_2_6B) == \
        48 * 51_388_416 + 2 * 49152 * 2048 + 2048 + 2049
    assert looplm.LoopLMConfig.from_hf(
        hf(looplm.OURO_2_6B) | {"model_type": "ouro"}) == looplm.OURO_2_6B


def test_the_cache_has_a_slot_per_loop_and_layer_and_they_differ(params):
    kc, vc = looplm.empty_cache(TINY, 1, 12)
    assert kc.shape == (4, 3, 1, 12, 4, 16) and TINY.cache_slots == 12
    assert looplm.kv_cache_bytes(TINY, 1, 12) == kc.nbytes + vc.nbytes
    # Ouro-2.6B: 192 slots, 1.5 MiB a position
    assert looplm.OURO_2_6B.cache_slots == 192
    assert looplm.kv_cache_bytes(looplm.OURO_2_6B, 1, 1) == 1.5 * 2 ** 20

    def decode_after_prefill(swap):
        x = looplm._embed(params, jnp.asarray(prompt()[:, :PROMPT]))
        first = jnp.zeros(1, jnp.int32)
        _, _, (kc, vc) = looplm._stack(
            TINY, params, x, jnp.arange(PROMPT), first,
            looplm.empty_cache(TINY, 1, 12), use_cache=False)
        # every slot was written, and no two loops hold the same keys
        assert float(jnp.abs(kc[:, :, :, :PROMPT]).min(axis=(2, 3, 4, 5)
                                                       ).min()) >= 0
        assert all(float(jnp.abs(kc[a] - kc[b]).max()) > 1e-3
                   for a in range(4) for b in range(a))
        if swap:
            order = jnp.asarray([1, 0, 2, 3])
            kc, vc = kc[order], vc[order]
        tok = looplm._embed(params, jnp.asarray([[5]]))
        x, _, _ = looplm._stack(TINY, params, tok, jnp.asarray([PROMPT]),
                                first, (kc, vc), use_cache=True)
        return np.asarray(looplm._head(TINY, params, x))

    assert np.abs(decode_after_prefill(False)
                  - decode_after_prefill(True)).max() > 1e-2


def test_padding_is_never_attended_to(params):
    """The same prompt in a longer padded buffer, and with other ids in
    the padding, gives the same logits."""
    tokens, logits, _ = serve(TINY, params, prompt())
    noisy = prompt()
    noisy[0, PROMPT:] = 77
    assert np.array_equal(serve(TINY, params, noisy)[0], tokens)
    np.testing.assert_allclose(serve(TINY, params, noisy)[1], logits,
                               atol=1e-5)


def test_sampling_follows_the_seed_and_greedy_ignores_it(params):
    greedy = serve(TINY, params, prompt(), seed=1)[0]
    assert np.array_equal(greedy, serve(TINY, params, prompt(), seed=2)[0])
    a = serve(TINY, params, prompt(), temperature=1.0, seed=1)[0]
    b = serve(TINY, params, prompt(), temperature=1.0, seed=2)[0]
    assert np.array_equal(
        a, serve(TINY, params, prompt(), temperature=1.0, seed=1)[0])
    assert not np.array_equal(a, b) and not np.array_equal(a, greedy)


# --- several requests in one execution ----------------------------------------

LENS = [9, 5, 16, 12]               # PAD_TO = 16: one row has no padding
TEMPERATURES = [0.0, 0.0, 1.0, 0.7]
SEEDS = [3, 4, 5, 6]


def padded(real, rows, *per_row):
    """Each list of ``real`` rows as `LanguageModel.generate_rows` pads an
    execution to ``rows``: a padded row repeats the first."""
    source = [*range(real), *[0] * (rows - real)]
    return [[values[i] for i in source] for values in per_row]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows, real", [(2, 2), (4, 4), (4, 3)])
def test_rows_of_different_lengths_match_their_single_row_runs(rows, real,
                                                               dtype):
    """Rows of different real lengths, seeds and temperatures in one
    execution, all of them real or three real ones in a program of four
    (what a leader runs whose set the drain closed with two followers):
    each row's ids are those of its own single-row run, greedy and
    sampled alike, and its logits lie inside the file's limits both of
    that run's and of the reference teacher-forced over its own ids."""
    cfg = dataclasses.replace(TINY, dtype=jnp.dtype(dtype))
    limits = verify.LIMITS_FP32 if dtype == "float32" else TINY_BF16_LIMITS
    p = make_params(cfg)
    lens, temps, seeds, ids = padded(
        real, rows, LENS, TEMPERATURES, SEEDS,
        [prompt(b, n) for b, n in enumerate(LENS)])
    tokens, logits, exits = serve_rows(cfg, p, lens, temperatures=temps,
                                       seeds=seeds, ids=np.concatenate(ids))
    assert tokens.shape == (rows, NEW) and exits.shape == (rows, NEW, 4)
    for b, n in enumerate(lens[:real]):
        alone = serve(cfg, p, prompt(b, n), n=n, temperature=temps[b],
                      seed=seeds[b])
        assert np.array_equal(tokens[b], alone[0]), (b, tokens[b], alone[0])
        got = verify.compare_logits(logits[b], alone[1], tokens[b], limits)
        assert got["max_over_std"] <= limits["max_over_std"], (b, got)
        np.testing.assert_allclose(exits[b], alone[2], atol=1e-3)
        want, _ = reference_rows(cfg, p, prompt(b, n), tokens[b], n=n)
        got = verify.compare_logits(logits[b], want, tokens[b], limits)
        if temps[b] == 0:
            assert got["correct"], (b, got)
        else:       # a sampled id need not be the reference's largest
            assert got["max_over_std"] <= limits["max_over_std"], (b, got)
            assert got["mean_over_std"] <= limits["mean_over_std"], (b, got)


def test_rows_do_not_read_each_other_and_padding_changes_no_real_row(params):
    """The fourth row holds a copy of the first, another prompt, or
    another length; ids in a row's padding are anything: the three real
    rows' ids and logits stay what they were."""
    lens = LENS[:3]
    base = np.concatenate([prompt(b, n) for b, n in enumerate(lens)])
    tokens, logits, _ = serve_rows(TINY, params, lens + [lens[0]],
                                   ids=np.concatenate([base, base[:1]]))
    for last, n in ((prompt(9, 16), 16), (prompt(8, 3), 3)):
        noisy = np.concatenate([base, last])
        for b, real in enumerate(lens):
            noisy[b, real:] = 77 + b
        t, l, _ = serve_rows(TINY, params, lens + [n], ids=noisy)
        assert np.array_equal(t[:3], tokens[:3])
        np.testing.assert_allclose(l[:3], logits[:3], atol=1e-5)
    # and a scalar length, seed or temperature stands for every row
    same = serve_rows(TINY, params, [9, 9], ids=np.concatenate(
        [prompt(0), prompt(1)]))
    scalar = looplm.make_generate(TINY, NEW)(
        params, jnp.asarray(np.concatenate([prompt(0), prompt(1)])),
        np.int32(9), np.uint32(3), np.float32(0.0))
    assert np.array_equal(same[0], np.asarray(scalar[0]))


# --- each breakage fails the comparison -------------------------------------

def _fp8(tree):
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype), tree)


def _break(name, monkeypatch, cfg, params):
    """The served path with one thing wrong; returns (cfg, params)."""
    if name == "a loop dropped":
        return dataclasses.replace(cfg, total_ut_steps=3), params
    if name == "two loops share a slot":
        monkeypatch.setattr(looplm, "_cache_slot",
                            lambda r, l: (min(r, 2), l))
    elif name == "a sandwich norm left out":
        monkeypatch.setattr(looplm, "_sandwich",
                            lambda x, update, gain, eps: x + update)
    elif name == "the cache in 8 bits":
        real = looplm.empty_cache
        monkeypatch.setattr(
            looplm, "empty_cache", lambda *a: tuple(
                c.astype(jnp.float8_e4m3fn) for c in real(*a)))
    elif name == "the weights in 8 bits":
        return cfg, _fp8(params)
    return cfg, params


BREAKAGES = ["a loop dropped", "two loops share a slot",
             "a sandwich norm left out", "the cache in 8 bits",
             "the weights in 8 bits"]


@pytest.mark.parametrize("rows, real", [(1, 1), (3, 3), (4, 3)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("what", BREAKAGES)
def test_each_breakage_fails_the_comparison(what, dtype, rows, real,
                                            monkeypatch):
    """Under the float32 limits and under the bf16 limits alike, alone,
    as the last of three rows of different lengths in one execution, and
    as the last of three real rows in an execution of four.  The
    reference always runs the model as stated, on the stated weights."""
    cfg = dataclasses.replace(TINY, dtype=jnp.dtype(dtype))
    limits = verify.LIMITS_FP32 if dtype == "float32" else TINY_BF16_LIMITS
    p = make_params(cfg)
    lens = [5, 12, PROMPT][-real:]
    lens, ids = padded(real, rows, lens, [
        prompt(b) if n == PROMPT else prompt(b, n)
        for b, n in zip(range(real - 1, -1, -1), lens)])
    mine = np.concatenate(ids)

    def last_row(cfg, p):
        tokens, logits, _ = serve_rows(cfg, p, lens, ids=mine)
        return tokens[real - 1], logits[real - 1]

    tokens, logits = last_row(cfg, p)
    want, _ = reference_rows(cfg, p, prompt(), tokens)
    assert verify.compare_logits(logits, want, tokens, limits)["correct"]
    broken_cfg, broken_p = _break(what, monkeypatch, cfg, p)
    tokens, logits = last_row(broken_cfg, broken_p)
    want, _ = reference_rows(cfg, p, prompt(), tokens)
    got = verify.compare_logits(logits, want, tokens, limits)
    assert not got["correct"], (what, got)


def test_the_margin_rule_refuses_a_chosen_id_the_reference_ranks_low():
    rng = np.random.default_rng(0)
    reference = rng.standard_normal((4, 50))
    best = reference.argmax(axis=-1)
    assert verify.compare_logits(reference, reference, best)["correct"]
    worst = reference.argmin(axis=-1)
    got = verify.compare_logits(reference, reference, worst)
    assert got["max_over_std"] == 0 and not got["correct"]
    assert got["margin_over_std"] > 2 and got["argmax_agree"] == 0


# --- attention: the mask, the rule, the counters ----------------------------

def test_masked_attention_is_causal_and_reads_a_cache_to_its_position():
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 8, 4, 16)), jnp.float32)
               for _ in range(3))
    scale = 0.25
    causal = np.asarray(xla_attention(q, k, v, scale, jnp.arange(8)))
    for i in (0, 3, 7):     # row i is plain attention over keys 0..i
        want = xla_attention(q[:, i:i + 1], k[:, :i + 1], v[:, :i + 1],
                             scale)
        np.testing.assert_allclose(causal[:, i:i + 1], want, atol=1e-5)
    # one query against a cache of length 8, valid to position 4: what
    # lies behind is not read
    one = xla_attention(q[:, :1], k, v, scale, jnp.asarray([4]))
    junk = k.at[:, 5:].set(1e4)
    np.testing.assert_allclose(
        one, xla_attention(q[:, :1], junk, v, scale, jnp.asarray([4])),
        atol=1e-5)
    np.testing.assert_allclose(
        one, xla_attention(q[:, :1], k[:, :5], v[:, :5], scale), atol=1e-5)


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_attention_path_rows_for_the_causal_and_decode_calls(platform):
    """Both stay on the XLA path on every platform, whatever their size:
    the kernel has not been taught a mask."""
    assert attention_path(platform, 1, 64, 64, 16, masked=True) \
        == "xla_causal"
    assert attention_path(platform, 1, 1, 128, 16, masked=True) \
        == "xla_decode"
    assert attention_path(platform, 1, 4096, 4096, 16, masked=True) \
        == "xla_causal"
    assert attention_path(platform, 1, 1, 65536, 16,
                          {"data": 4, "tensor": 1, "seq": 1},
                          masked=True) == "xla_decode"
    # and an unmasked call is judged as before
    assert attention_path(platform, 2, 4096, 4096, 8) \
        == ("fused" if platform == "tpu" else "xla_chunked")


def test_the_call_sites_count_their_paths_once_per_trace(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = trace.ATTENTION_PATHS.snapshot()
    q = jnp.zeros((1, 8, 4, 16))
    jax.make_jaxpr(lambda q: scaled_dot_product_attention(
        q, q, q, q_positions=jnp.arange(8)))(q)
    jax.make_jaxpr(lambda q: scaled_dot_product_attention(
        q[:, :1], q, q, impl="pallas", q_positions=jnp.asarray([3])))(q)
    after = trace.ATTENTION_PATHS.snapshot()
    assert after.get("xla_causal", 0) - before.get("xla_causal", 0) == 1
    assert after.get("xla_decode", 0) - before.get("xla_decode", 0) == 1
    assert after.get("fused", 0) == before.get("fused", 0)


# --- the names the program carries -------------------------------------------

LM = "jit(lm_generate)/LoopLM/"
LM_CLASSES = {"lm_attn", "lm_proj", "lm_mlp", "lm_norm", "lm_cache",
              "lm_head", "embed"}


@pytest.mark.parametrize("path, want", [
    (LM + "while/body/layers/while/body/self_attn/q_proj/dot_general",
     "lm_proj"),
    (LM + "layers/while/body/self_attn/o_proj/dot_general", "lm_proj"),
    (LM + "layers/while/body/self_attn/bnhd,bmhd->bhnm/dot_general",
     "lm_attn"),
    (LM + "layers/while/body/self_attn/rotary/cos", "lm_attn"),
    (LM + "layers/while/body/kv_cache/dynamic_update_slice", "lm_cache"),
    (LM + "layers/while/body/mlp/gate_proj/dot_general", "lm_mlp"),
    (LM + "layers/while/body/mlp/jit(silu)/logistic", "lm_mlp"),
    (LM + "layers/while/body/input_layernorm/rsqrt", "lm_norm"),
    (LM + "layers/while/body/post_attention_layernorm_2/mul", "lm_norm"),
    (LM + "while/body/final_norm/mul", "lm_norm"),
    (LM + "while/body/lm_head/dot_general", "lm_head"),
    (LM + "while/body/sample/argmax", "lm_head"),
    (LM + "early_exit_gate/logistic", "lm_head"),
    (LM + "embed_tokens/gather", "embed"),
    (LM + "layers/while/body/add", "lm_proj"),
    (LM + "dynamic_slice", "lm_proj"),
    # the few-row kernel's custom calls (PR 33), as the chip's compiler
    # names them: the scope a call lies under decides, not its own name
    (LM + "while/body/closed_call/layers/while/body/closed_call/self_attn/"
     "q_proj/fewrow_dense_q_proj_k_proj_v_proj/pallas_call", "lm_proj"),
    (LM + "while/body/closed_call/layers/while/body/closed_call/self_attn/"
     "o_proj/fewrow_dense/pallas_call", "lm_proj"),
    (LM + "while/body/closed_call/layers/while/body/closed_call/mlp/"
     "gate_proj/fewrow_dense_gate_proj_up_proj/pallas_call", "lm_mlp"),
    (LM + "while/body/closed_call/layers/while/body/closed_call/mlp/"
     "down_proj/fewrow_dense/pallas_call", "lm_mlp"),
    (LM + "while/body/closed_call/lm_head/fewrow_dense/pallas_call",
     "lm_head"),
])
def test_classify_reads_the_language_models_scopes(path, want):
    assert trace.classify(path) == want


def test_every_op_of_the_compiled_program_falls_in_a_class(params):
    program = looplm.make_generate(TINY, 3)
    text = program.lower(params, jnp.asarray(prompt()), np.int32(PROMPT),
                         np.uint32(0), np.float32(0.0)).compile().as_text()
    assert "jit_lm_generate" in text        # the name a trace shows
    import re
    names = re.findall(r'op_name="([^"]+)"', text)
    assert len(names) > 200
    by_class = {}
    for n in names:
        by_class.setdefault(trace.classify(n), []).append(n)
    assert set(by_class) - {"other"} == LM_CLASSES
    # the remainder: combiners XLA names by their primitive alone, and
    # the program's own key handling outside the model's scope
    for n in by_class.get("other", []):
        assert "LoopLM" not in n, n
    # the scopes are the published modules' names, as the table has them:
    # each is a segment of some operation's path (the projections' come
    # from `dense_each`'s names, not from a literal ``named_scope``)
    segments = {seg for n in names for seg in n.split("/")}
    for scope in ("self_attn", "q_proj", "k_proj", "v_proj", "o_proj",
                  "rotary", "kv_cache", "mlp", "gate_proj", "up_proj",
                  "down_proj", "final_norm", "early_exit_gate", "lm_head",
                  "embed_tokens", "sample", "layers", "LoopLM"):
        assert scope in segments, scope


# --- weights -----------------------------------------------------------------

def test_seeded_weights_are_a_function_of_the_seed_and_sanely_scaled(params):
    again = make_params(TINY)
    other = make_params(TINY, seed=8)
    flat = jax.tree_util.tree_leaves(params)
    assert all(np.array_equal(a, b) for a, b in
               zip(flat, jax.tree_util.tree_leaves(again)))
    assert not np.array_equal(params["lm_head"], other["lm_head"])
    assert abs(float(params["embed_tokens"].std()) - 1.0) < 0.05
    assert abs(float(params["layers"]["q_proj"].std()) - 64 ** -0.5) < 0.01
    gains = np.asarray(params["layers"]["input_layernorm"])
    assert abs(gains.mean() - 1.0) < 0.05 and 0.05 < gains.std() < 0.15
    # a sub-layer's update is small beside the residual stream
    sandwich = np.asarray(params["layers"]["post_attention_layernorm_2"])
    assert abs(sandwich.mean() - 0.1) < 0.005
    assert 0.005 < sandwich.std() < 0.015
    assert jax.tree_util.tree_map(lambda x: tuple(x.shape), params) \
        == looplm.param_shapes(TINY)


def test_a_hugging_face_state_dict_loads_as_the_served_tree(params, tmp_path):
    from comfyui_distributed_tpu.models.checkpoints import (
        load_looplm_checkpoint, save_state_dict)
    sd = {"model.embed_tokens.weight": np.asarray(params["embed_tokens"]),
          "model.norm.weight": np.asarray(params["norm"]),
          "lm_head.weight": np.asarray(params["lm_head"]).T,
          "model.early_exit_gate.weight":
              np.asarray(params["early_exit_gate"]["kernel"])[None],
          "model.early_exit_gate.bias":
              np.asarray(params["early_exit_gate"]["bias"])[None]}
    hf_names = {"q_proj": "self_attn.q_proj", "k_proj": "self_attn.k_proj",
                "v_proj": "self_attn.v_proj", "o_proj": "self_attn.o_proj",
                "gate_proj": "mlp.gate_proj", "up_proj": "mlp.up_proj",
                "down_proj": "mlp.down_proj"}
    for name, leaf in params["layers"].items():
        for l in range(TINY.num_hidden_layers):
            w = np.asarray(leaf[l])
            sd[f"model.layers.{l}.{hf_names.get(name, name)}.weight"] = \
                w.T if w.ndim == 2 else w
    path = str(tmp_path / "tiny-lm.safetensors")
    save_state_dict(sd, path)
    loaded = load_looplm_checkpoint(path, TINY)
    jax.tree_util.tree_map(np.testing.assert_array_equal, loaded, params)
