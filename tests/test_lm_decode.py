"""The one decode driver and the one prefix writer (ISSUE 45,
``models/lm_decode.py``): the driver alone around a toy family defined
here, the six families of `registry.LM_FAMILIES` reaching it, and the
prefix seam against numpy loops.  What each family's blocks compute is in
its own test file; what is held here is what they no longer each hold."""

import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import lm_decode, registry

V, D, NEW = 11, 4, 6
_rng = np.random.default_rng(45)
# whole numbers: a product is exact whatever the rows beside it
TABLE = jnp.asarray(_rng.integers(-3, 4, (V, D)), jnp.float32)
HEAD = jnp.asarray(_rng.integers(-3, 4, (D, V)), jnp.float32)
START = 100          # what the toy prefill has counted already


def toy(last_ids, seed, temperature, new=NEW, kept=()):
    """A family of one embedding table and one head whose state counts
    its steps: the hidden state behind a token is its embedding plus the
    steps before it, which is also what it records."""
    B = len(last_ids)

    def prefill():
        with jax.named_scope("prefill"):
            x = TABLE[jnp.asarray(last_ids)]
        return (x @ HEAD, (x,), jnp.int32(0),
                (jnp.full((B,), START, jnp.int32), jnp.int32(0)), kept)

    def step(token, i, steps):
        x = TABLE[token] + steps.astype(jnp.float32)
        return x @ HEAD, (x,), steps + 1, (i + jnp.ones((B,), jnp.int32),
                                           jnp.int32(1))

    return lm_decode.generate("Toy", B, prefill, step, new, seed,
                              temperature)


LAST = [3, 7, 1]
SEEDS = np.asarray([5, 6, 7], np.uint32)
TEMPS = np.asarray([0.0, 0.9, 1.7], np.float32)


def test_shapes_and_greedy_is_the_argmax():
    tokens, logits, (hidden,), _, _ = toy(LAST, SEEDS, 0.0)
    assert tokens.shape == (3, NEW) and tokens.dtype == jnp.int32
    assert logits.shape == (3, NEW, V) and hidden.shape == (3, NEW, D)
    np.testing.assert_array_equal(tokens, np.argmax(logits, axis=-1))


def test_a_rows_tokens_are_its_own_seeds_and_temperatures():
    tokens, logits, _, _, _ = toy(LAST, SEEDS, TEMPS)
    for b in range(3):                      # alone, and among others
        alone = toy(LAST[b:b + 1], SEEDS[b:b + 1], TEMPS[b:b + 1])
        np.testing.assert_array_equal(alone[0][0], tokens[b])
        np.testing.assert_array_equal(alone[1][0], logits[b])
    others = toy(LAST, SEEDS + np.asarray([9, 0, 9], np.uint32),
                 TEMPS * np.asarray([1, 1, 2], np.float32))
    np.testing.assert_array_equal(others[0][1], tokens[1])
    # the greedy row ignores its seed; a sampled row follows its own
    np.testing.assert_array_equal(others[0][0], tokens[0])
    reseeded = toy(LAST, SEEDS + np.asarray([0, 1, 0], np.uint32), TEMPS)
    assert not np.array_equal(reseeded[0][1], tokens[1])
    np.testing.assert_array_equal(reseeded[0][2], tokens[2])


def test_sampling_is_draws_rule():
    """Token ``i`` is `draw` of the row's key folded with ``i`` on the
    logits returned beside it."""
    tokens, logits, _, _, _ = toy(LAST, SEEDS, TEMPS)
    for b in range(3):
        key = jax.random.PRNGKey(SEEDS[b])
        for i in range(NEW):
            assert int(tokens[b, i]) == int(lm_decode.draw(
                key, logits[b, i], TEMPS[b], i))


def test_record_i_stood_beside_the_logits_token_i_was_drawn_from():
    tokens, logits, (hidden,), _, _ = toy(LAST, SEEDS, TEMPS)
    np.testing.assert_array_equal(hidden[:, 0], TABLE[np.asarray(LAST)])
    np.testing.assert_array_equal(logits, hidden @ HEAD)
    # record i is the step's behind token i - 1, after i - 1 steps
    for i in range(1, NEW):
        np.testing.assert_array_equal(hidden[:, i],
                                      TABLE[tokens[:, i - 1]] + (i - 1))


def test_counts_are_the_prefills_start_plus_the_steps():
    _, _, _, (rows, steps), _ = toy(LAST, SEEDS, TEMPS)
    np.testing.assert_array_equal(
        rows, np.full(3, START + sum(i + 1 for i in range(NEW))))
    assert int(steps) == NEW


@pytest.mark.parametrize("seed,temperature", [(5, 0.9), (SEEDS, 0.9),
                                              (5, TEMPS)])
def test_scalars_are_every_rows(seed, temperature):
    got = toy(LAST, seed, temperature)
    want = toy(LAST, np.broadcast_to(seed, (3,)).astype(np.uint32),
               np.broadcast_to(temperature, (3,)).astype(np.float32))
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


def test_kept_comes_back_untouched_and_empty_parts_stay_empty():
    kept = {"of the prefill": object()}
    assert toy(LAST, SEEDS, TEMPS, kept=kept)[4] is kept

    def bare_step(token, i, state):
        return TABLE[token] @ HEAD, (), state, ()

    tokens, logits, chosen, counts, back = lm_decode.generate(
        "Bare", 2, lambda: (TABLE[:2] @ HEAD, (), (), (), ()), bare_step, 3,
        0, 0.0)
    assert (chosen, counts, back) == ((), (), ())
    assert tokens.shape == (2, 3) and logits.shape == (2, 3, V)


def test_the_scopes_are_the_drivers():
    """``<scope>/decode`` around the steps and ``sample`` around the
    draw: what ``utils/trace`` reads a decode step by."""
    text = jax.jit(lambda s: toy(LAST, s, TEMPS)[0]).lower(
        SEEDS).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    inside = [n for n in names if "/draw" in n or "_gumbel" in n]
    assert inside and all(
        "/Toy/decode/while/body/closed_call/sample/" in n for n in inside)


# --- the six families reach it - ------------------------------------------

@pytest.fixture(params=list(registry.LM_FAMILIES))
def family(request):
    return registry.LM_FAMILIES[request.param].load()


def test_a_familys_generate_is_one_call_of_the_driver(family, monkeypatch):
    calls = []
    real = lm_decode.generate

    def spy(scope, rows, prefill, step, *rest):
        calls.append((scope, rows))
        return real(scope, rows, prefill, step, *rest)

    monkeypatch.setattr(lm_decode, "generate", spy)
    cfg = family.CONFIGS["tiny"]
    params = family.seeded_params(cfg, 0)
    ids = jnp.ones((2, 8), jnp.int32)
    out = jax.eval_shape(
        lambda p: family.generate(cfg, 3, p, ids, jnp.asarray([8, 5]),
                                  jnp.uint32(1), jnp.float32(0.5)), params)
    assert len(calls) == 1 and calls[0][1] == 2
    assert out[0].shape == (2, 3) and out[1].shape == (2, 3, cfg.vocab_size)
    program = family.make_program(cfg, 3)
    assert program.__wrapped__.__name__ == "lm_generate"


def test_a_family_holds_no_loop_of_its_own(family):
    source = inspect.getsource(family)
    assert not hasattr(family, "draw")
    for gone in ("def draw(key, logits", "random.categorical",
                 'named_scope("decode")', 'named_scope("sample")'):
        assert gone not in source, gone
    # the driver knows no family
    assert "models." not in inspect.getsource(lm_decode).split('"""', 2)[2]


# --- the prefix seam -----------------------------------------------------

L, B, T, K = 2, 3, 9, 4
WIDTHS = {"keys [L, B, T, G, D]": (2, 3), "index keys [L, B, T, D_I]": (5,)}
FIRST = np.asarray([0, 3, 5], np.int32)


@pytest.mark.parametrize("width", list(WIDTHS))
def test_write_at_offsets_is_the_numpy_loop(width):
    tail = WIDTHS[width]
    rng = np.random.default_rng(len(tail))
    cache = rng.normal(size=(L, B, T, *tail)).astype(np.float32)
    block = rng.normal(size=(L, K, *tail)).astype(np.float32)
    want = cache.astype(jnp.bfloat16)
    for b in range(B):
        want[:, b, FIRST[b]:FIRST[b] + K] = block.astype(jnp.bfloat16)
    got = jax.jit(lm_decode.write_at_offsets)(
        jnp.asarray(cache, jnp.bfloat16), jnp.asarray(block),
        jnp.asarray(FIRST))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got), want)


def test_write_at_offsets_takes_records_a_row():
    """Records ``[B, T, ...]`` with no layer axis in front, each row's
    block its own."""
    rng = np.random.default_rng(2)
    records = rng.integers(0, 99, (B, T, L, 2)).astype(np.int32)
    blocks = rng.integers(100, 199, (B, K, L, 2)).astype(np.int32)
    want = records.copy()
    for b in range(B):
        want[b, FIRST[b]:FIRST[b] + K] = blocks[b]
    got = jax.jit(lambda r, f: lm_decode.write_at_offsets(
        r, lambda b: jnp.asarray(blocks)[b], f, rows=0))(records, FIRST)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("width", list(WIDTHS))
def test_own_entries_is_the_numpy_loop(width):
    tail = WIDTHS[width]
    rng = np.random.default_rng(len(tail))
    N, at, layer = 4, 3, 1
    cache = rng.normal(size=(L, B, T, *tail)).astype(np.float32)
    new = rng.normal(size=(B, N, *tail)).astype(np.float32)
    own = rng.random((B, N)) < 0.5
    want = new.copy()
    for b in range(B):
        for n in range(N):
            if not own[b, n]:
                want[b, n] = cache[layer, b, at + n]
    got = jax.jit(lm_decode.own_entries)(own, new, cache, layer, at)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_prefix_length_reads_the_snapshot():
    assert lm_decode.prefix_length(None) == 0
    assert lm_decode.prefix_length(
        {"keys": np.zeros((L, K, 2, 3)), "ssm": np.zeros((L, 7))}) == K
