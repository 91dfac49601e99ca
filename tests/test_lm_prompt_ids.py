"""A request's prompt ids are made once (`LanguageModel.prompt_ids` /
`instruction_ids`, models/registry.py): what is kept is the tokenizer's own
result to the id, for a tokenizer that says what a text adds behind a space
and for one that does not; the memo is bounded; the vocabulary refusal is
made at every call; two threads get one array."""

import functools
import threading
import time

import numpy as np
import pytest

from comfyui_distributed_tpu.models import registry
from comfyui_distributed_tpu.models.tokenizer import (HashLMTokenizer,
                                                      JsonLMTokenizer)
from comfyui_distributed_tpu.utils import trace

GUIDE = "Example prompt: a red fox. Detailed prompt: a red fox on fresh snow"


class Counting:
    """A tokenizer that writes its passes down: the text, the ids walked,
    and what ``mark`` said then."""

    def __init__(self, inner, mark=lambda: None):
        self.inner, self.mark, self.passes = inner, mark, []
        self.pad_id, self.vocab_size = inner.pad_id, inner.vocab_size
        self.decode = inner.decode
        if hasattr(inner, "encode_behind_space"):
            self.encode_behind_space = functools.partial(
                self._counted, inner.encode_behind_space)

    def _counted(self, encode, text):
        ids = encode(text)
        self.passes.append((text, len(ids), self.mark()))
        return ids

    def encode(self, text):
        # the inner one's as it is NOW: a test may slow it down
        return self._counted(self.inner.encode, text)


class Contextual:
    """A word's id depends on its place and on how many words the text
    has: the ids of ``a + " " + b`` are NOT those of ``a`` followed by
    those of ``b``, and the class does not say they were (as the model's
    own ``tokenizer.json``, which merges across a space, does not)."""
    pad_id = HashLMTokenizer.pad_id

    def __init__(self, vocab_size):
        self.vocab_size, self._words = vocab_size, HashLMTokenizer(vocab_size)
        self.decode = self._words.decode

    def encode(self, text):
        ids, first = self._words.encode(text), HashLMTokenizer._FIRST
        return ids[:1] + [
            first + (i + 7 * at + len(ids)) % (self.vocab_size - first)
            for at, i in enumerate(ids[1:])]


TOKENIZERS = {"hash": HashLMTokenizer, "contextual": Contextual}


@pytest.fixture(autouse=True)
def tiny_family(monkeypatch):
    monkeypatch.setenv(registry.FAMILY_ENV, "tiny")
    trace.reset_aggregate_metrics()


@pytest.fixture(params=sorted(TOKENIZERS))
def model(request):
    """A tiny model of its own (the registry keeps models by name) whose
    tokenizer counts."""
    model = registry.load_language_model(
        f"ouro-prompt-ids-{request.node.name}.safetensors")
    model.tokenizer = Counting(
        TOKENIZERS[request.param](model.cfg.vocab_size))
    return model


def whole(model, text, prompt_tokens, instructions=""):
    """What the tree made before it kept anything."""
    asked = registry.EXPAND_TEMPLATE.format(text=text)
    ids = model.tokenizer.inner.encode(
        f"{instructions} {asked}" if instructions else asked)
    return np.asarray(ids[:prompt_tokens], np.int32)


def counted():
    snap = trace.GLOBAL_COUNTERS.snapshot()
    return (snap.get("lm.prompt_encodes", 0),
            snap.get("lm.prompt_encode_ids", 0))


def test_the_contextual_stub_really_does_not_concatenate():
    tok = Contextual(512)
    a, b = tok.encode(GUIDE), tok.encode("a cat")
    assert tok.encode(f"{GUIDE} a cat") != a + b[1:]
    assert not hasattr(tok, "encode_behind_space")
    assert not hasattr(JsonLMTokenizer, "encode_behind_space")


@pytest.mark.parametrize("a, b", [
    (GUIDE, "a cat"), ("Trailing space ", " leading space"),
    ("ends in a comma,", ", begins with one"), ("UPPER", "Case"),
    ("ΟΔΟΣ", "ΟΔΟΣ Σ"), ("tab\there", "new\nline"), ("x", ""),
    ("half-", "-word 3d"), ("", "nothing in front"),
])
def test_the_hash_tokenizer_says_what_a_text_adds_behind_a_space(a, b):
    tok = HashLMTokenizer(49152)
    assert tok.encode(f"{a} {b}") == tok.encode(a) + tok.encode_behind_space(b)


@pytest.mark.parametrize("instructions", ["", GUIDE])
def test_the_ids_are_the_tokenizers_own_to_the_id(model, instructions):
    """Edited text, edited instructions and a changed ``prompt_tokens``
    are other keys: each gives what the whole pass gives, a repeat costs
    no pass, and what comes back cannot be written to."""
    asks = [("a cat", 32, instructions), ("a cat", 32, instructions),
            ("a cat, edited", 32, instructions),
            ("a cat", 32, instructions and instructions + " and more"),
            ("a cat", 9, instructions), ("a cat", 48, instructions),
            ("a cat", 32, ""), ("a cat", 32, instructions)]
    seen = {}
    for text, tokens, ins in asks:
        before = len(model.tokenizer.passes)
        ids = model.prompt_ids(text, tokens, ins)
        want = whole(model, text, tokens, ins)
        assert ids.dtype == np.int32 and np.array_equal(ids, want)
        assert not ids.flags.writeable
        made = len(model.tokenizer.passes) - before
        if (text, tokens, ins) in seen:
            assert made == 0 and ids is seen[(text, tokens, ins)]
        seen[(text, tokens, ins)] = ids
    passes = model.tokenizer.passes
    assert counted() == (len(passes), sum(n for _, n, _ in passes))
    spliced = hasattr(model.tokenizer, "encode_behind_space")
    sets = {ins for _, _, ins in asks if ins}
    # a set of instructions is walked once where the tokenizer lets the
    # rest be encoded alone, with every row where it does not
    assert sum(text in sets for text, _, _ in passes) == \
        (len(sets) if spliced else 0)
    assert len(passes) == len(seen) + (len(sets) if spliced else 0)


def test_the_memo_keeps_no_more_than_its_bound(model):
    """Four executions' rows and as many sets of instructions as there
    are snapshots, least recently USED out: what was let go costs a pass
    again, what was kept costs none."""
    rows, sets = 4 * model.row_counts[-1], model.row_counts[-1]
    for i in range(rows):
        model.prompt_ids(f"prompt number {i}", 32)
    assert len(model._row_ids) == rows
    model.prompt_ids("prompt number 0", 32)         # used: the last to go
    before = len(model.tokenizer.passes)
    for i in range(rows, rows + 3):
        model.prompt_ids(f"prompt number {i}", 32)
        assert len(model._row_ids) == rows
    kept = [i for i in range(rows + 3)
            if (f"prompt number {i}", 32, "") in model._row_ids]
    assert kept == [0, *range(4, rows + 3)]
    assert len(model.tokenizer.passes) - before == 3
    model.prompt_ids("prompt number 0", 32)
    assert len(model.tokenizer.passes) - before == 3
    model.prompt_ids("prompt number 1", 32)         # evicted: made again
    assert len(model.tokenizer.passes) - before == 4
    for i in range(sets + 2):
        model.instruction_ids(f"{GUIDE} number {i}")
        assert len(model._instruction_ids) <= sets
    assert list(model._instruction_ids) == [
        f"{GUIDE} number {i}" for i in range(2, sets + 2)]
    before = len(model.tokenizer.passes)
    assert np.array_equal(
        model.instruction_ids(f"{GUIDE} number 0"),
        model.tokenizer.inner.encode(f"{GUIDE} number 0"))
    assert len(model.tokenizer.passes) - before == 1


def test_the_vocabulary_refusal_is_made_on_a_hit_as_on_a_miss(model):
    """What is kept is what the tokenizer gave; whether the device can
    take it is asked at every call."""
    wide = type(model.tokenizer.inner)(model.cfg.vocab_size + 64)
    model.tokenizer = Counting(wide)
    text = next(f"word{i}" for i in range(10_000)
                if whole(model, f"word{i}", 64, GUIDE).max()
                >= model.cfg.vocab_size)
    for _ in range(3):
        with pytest.raises(ValueError, match="vocabulary of"):
            model.prompt_ids(text, 64, GUIDE)
    rows = [text for text, _, _ in model.tokenizer.passes if text != GUIDE]
    assert len(rows) == 1
    with pytest.raises(ValueError, match="a prompt of 0 ids"):
        model.prompt_ids(text, 0)


def test_two_threads_asking_at_once_get_the_same_array(model):
    """The pass is made under the memo's lock: whoever comes second
    waits for it and takes what it made."""
    inner_encode = model.tokenizer.inner.encode
    entered = threading.Event()

    def slow(text):
        entered.set()
        time.sleep(0.2)
        return inner_encode(text)

    model.tokenizer.inner.encode = slow
    got = {}

    def ask(who):
        got[who] = model.prompt_ids("a harbour at night", 32)

    first = threading.Thread(target=ask, args=("first",), daemon=True)
    first.start()
    assert entered.wait(60)
    second = threading.Thread(target=ask, args=("second",), daemon=True)
    second.start()
    for t in (first, second):
        t.join(60)
        assert not t.is_alive()
    assert got["first"] is got["second"]
    assert len(model.tokenizer.passes) == 1
    assert np.array_equal(got["first"], whole(model, "a harbour at night", 32))
