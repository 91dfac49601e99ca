"""The language model's few-row products (PR 33): the rule that sends a
product of 2-8 rows with a resident leaf to the weight-streaming kernel
(``looplm.dense_path``), the kernel itself against ``jnp.dot`` (Pallas
interpreter, CPU), the two scan structures held together on the tiny
models, and the programs of the published sizes lowered and, for a
described v5e, compiled: the one-row program is untouched by the rule and
no decode body of the 4-row program materialises a weight."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import dsa_moe, looplm, mla_moe, \
    sambay, ssm_hybrid, swa_moe
from comfyui_distributed_tpu.ops.pallas import fewrow_dense as fd
from comfyui_distributed_tpu.utils import trace

OURO, PANGU = looplm.OURO_2_6B, mla_moe.OPENPANGU_ULTRA_MOE_SHARE
EXAONE = swa_moe.K_EXAONE_SHARE
GRANITE = ssm_hybrid.GRANITE_4_0_H_MICRO
KEYE = dsa_moe.KEYE_VL2_STAGE
PHI = sambay.PHI_4_MINI_FLASH
FAMILIES = {"ouro": (looplm, OURO), "pangu": (mla_moe, PANGU),
            "exaone": (swa_moe, EXAONE), "granite": (ssm_hybrid, GRANITE),
            "keye": (dsa_moe, KEYE), "phi4flash": (sambay, PHI)}

# every product `_dense` makes with a resident leaf at the published
# sizes: (family, name, K, N, leaves streamed by one call)
PRODUCTS = [
    ("ouro", "q_proj+k_proj+v_proj", 2048, 2048, 3),
    ("ouro", "o_proj", 2048, 2048, 1),
    ("ouro", "gate_proj+up_proj", 2048, 5632, 2),
    ("ouro", "down_proj", 5632, 2048, 1),
    ("ouro", "lm_head", 2048, 49152, 1),
    ("pangu", "q_a_proj", 7680, 1536, 1),
    ("pangu", "q_b_proj", 1536, 24576, 1),
    ("pangu", "o_proj", 16384, 7680, 1),
    ("pangu", "dense gate_proj+up_proj", 7680, 18432, 2),
    ("pangu", "dense down_proj", 18432, 7680, 1),
    ("pangu", "shared gate_proj+up_proj", 7680, 2048, 2),
    ("pangu", "shared down_proj", 2048, 7680, 1),
    ("pangu", "lm_head", 7680, 19200, 1),
    ("exaone", "q_proj", 6144, 8192, 1),
    ("exaone", "k_proj+v_proj", 6144, 1024, 2),
    ("exaone", "o_proj", 8192, 6144, 1),
    ("exaone", "dense gate_proj+up_proj", 6144, 18432, 2),
    ("exaone", "dense down_proj", 18432, 6144, 1),
    ("exaone", "shared gate_proj+up_proj", 6144, 2048, 2),
    ("exaone", "shared down_proj", 2048, 6144, 1),
    ("exaone", "lm_head", 6144, 19200, 1),
    # (PR 40; its head is the tied embedding, transposed: further down)
    ("granite", "in_proj_zx", 2048, 8448, 1),
    ("granite", "out_proj", 4096, 2048, 1),
    ("granite", "input_linear", 2048, 16384, 1),
    ("granite", "output_linear", 8192, 2048, 1),
    ("granite", "q_proj", 2048, 2048, 1),
    ("granite", "k_proj+v_proj", 2048, 512, 2),
    ("granite", "o_proj", 2048, 2048, 1),
    # (PR 42; its head of 151,936 columns is further down)
    ("keye", "q_proj", 2048, 4096, 1),
    ("keye", "k_proj+v_proj", 2048, 512, 2),
    ("keye", "o_proj", 4096, 2048, 1),
    ("keye", "indexer wq", 2048, 1024, 1),
    # (PR 46; its head is the tied embedding, transposed; x_proj (192
    # columns) and dt_proj (160 rows) stay with XLA)
    ("phi4flash", "mamba in_proj", 2560, 10240, 1),
    ("phi4flash", "mamba out_proj", 5120, 2560, 1),
    ("phi4flash", "Wqkv", 2560, 5120, 1),
    ("phi4flash", "cross Wqkv, out_proj", 2560, 2560, 1),
    ("phi4flash", "gmu in_proj", 2560, 5120, 1),
    ("phi4flash", "fc1", 2560, 20480, 1),
    ("phi4flash", "fc2", 10240, 2560, 1),
]
IDS = [f"{p[0]}-{p[1]}" for p in PRODUCTS]


# --- the rule -------------------------------------------------------------------

@pytest.mark.parametrize("family, name, k, n, count", PRODUCTS, ids=IDS)
def test_the_published_shapes_take_the_kernel_at_two_to_eight_rows(
        family, name, k, n, count):
    for rows in range(2, 9):
        assert looplm.dense_path("tpu", rows, k, n) == "fewrow", rows
    # the one-row program, both prefills (4 x 64, 1 x 64), nine rows
    for rows in (1, 9, 64, 256):
        assert looplm.dense_path("tpu", rows, k, n) == "xla", rows
    # every other backend; a mesh of several devices (XLA cannot
    # partition the custom call); a mesh of one is no mesh
    for platform in ("cpu", "gpu"):
        assert looplm.dense_path(platform, 4, k, n) == "xla"
    assert looplm.dense_path("tpu", 4, k, n,
                             mesh_axes={"data": 4, "tensor": 1}) == "xla"
    assert looplm.dense_path("tpu", 4, k, n,
                             mesh_axes={"data": 1, "tensor": 1}) == "fewrow"


@pytest.mark.parametrize("k, n, why", [
    (7680, 576, "kv_a_proj_with_mqa: 576 is not a multiple of 128"),
    (2048, 64, "the indexer's one key: half a lane group"),
    (5120, 192, "Mamba-1's x_proj: 192 is not a multiple of 128"),
    (160, 5120, "Mamba-1's dt_proj: 160 rows"),
    (2048, 16, "the indexer's head weights"),
    (2048, 151936, "a head of 1187 x 128 columns, 1187 prime: the blocks "
                   "could only walk it a lane group at a time (256-byte "
                   "runs of the leaf)"),
    (64, 176, "the tiny models' widths"),
    (2048, 1, "the exit gate's vector"),
    (128, 256, "aligned, and too small to be worth a launch"),
    (2000, 2048, "K unaligned"),
])
def test_what_the_kernels_blocks_do_not_divide_stays_with_xla(k, n, why):
    assert looplm.dense_path("tpu", 4, k, n) == "xla", why


def test_a_scan_walks_the_index_only_where_the_rows_are_few():
    assert [looplm.few_rows("tpu", r) for r in (1, 2, 4, 8, 9, 256)] \
        == [False, True, True, True, False, False]
    assert not looplm.few_rows("cpu", 4)
    assert not looplm.few_rows("tpu", 4, {"data": 2})
    # here, on the CPU, nothing takes the path
    assert not looplm.few_rows_here(4)


def test_a_plain_array_never_takes_the_kernel(monkeypatch):
    """A routed expert's weights are slices made inside the program: a
    custom call would materialise them.  Only a `Stacked` leaf can go."""
    monkeypatch.setattr(looplm, "_where", lambda: ("tpu", None))
    w = jnp.zeros((3, 1024, 1024), jnp.bfloat16)
    assert looplm._streams([looplm.Stacked(w, jnp.int32(1))], 4, OURO)
    assert looplm._streams([looplm.Stacked(w[0])], 4, OURO)
    assert not looplm._streams([w[1]], 4, OURO)
    assert not looplm._streams([looplm.Stacked(w, 1)], 1, OURO)
    # leaves of two shapes do not share a call
    assert not looplm._streams(
        [looplm.Stacked(w, 1), looplm.Stacked(w[:, :512], 1)], 4, OURO)


@pytest.mark.parametrize("family, name, k, n, count", PRODUCTS, ids=IDS)
def test_the_blocks_divide_the_published_shapes_and_fit(
        family, name, k, n, count):
    tk, tn = fd.block_sizes(k, n, count)
    assert k % tk == 0 and n % tn == 0 and tk % 128 == 0 and tn % 128 == 0
    # a tile in flight and one in use for every leaf of the call, well
    # inside what the kernel asks of VMEM
    assert 2 * count * tk * tn * 2 <= fd.VMEM_LIMIT_BYTES // 2
    assert tk * tn * 2 * count <= fd.TILE_BYTES
    # a tile's columns whole (one contiguous run) wherever 128 rows fit
    if n * 128 * 2 * count <= fd.TILE_BYTES:
        assert tn == n


# the routed experts' matrices at the published sizes: (family, experts
# held, d, moe_intermediate_size, experts a token)
EXPERTS = [("pangu", 16, 7680, 2048, 8), ("exaone", 16, 6144, 2048, 8),
           ("keye", 128, 2048, 768, 8)]
EXPERT_IDS = [e[0] for e in EXPERTS]


@pytest.mark.parametrize("rows", [2, 3, 4, 8])
@pytest.mark.parametrize("family, held, d, width, k", EXPERTS, ids=EXPERT_IDS)
def test_experts_that_outnumber_a_few_rows_pairs_take_the_grouped_path(
        family, held, d, width, k, rows):
    """Keye's 128 held experts at 2 to 8 rows; the two shares of 16 held
    experts keep the loop at every row count (8 pairs a row: two rows
    already route as many pairs as experts are held)."""
    cfg = FAMILIES[family][1]
    assert (cfg.experts_held, cfg.hidden_size, cfg.moe_intermediate_size,
            cfg.num_experts_per_tok) == (held, d, width, k)
    want = "grouped" if family == "keye" else "loop"
    assert mla_moe.routed_path("tpu", rows, d, width, held, k) == want
    assert mla_moe.routed_path(
        "tpu", rows, d, width, held, k,
        mesh_axes={"data": 1, "tensor": 1}) == want
    # the same matrices with more experts held than pairs routed, and fewer
    assert mla_moe.routed_path("tpu", rows, d, width, rows * k + 1, k) \
        == "grouped"
    assert mla_moe.routed_path("tpu", rows, d, width, rows * k, k) == "loop"


@pytest.mark.parametrize("platform, rows, mesh_axes, why", [
    ("tpu", 1, None, "the one-row program"),
    ("tpu", 9, None, "more rows than the kernel's resident x holds"),
    ("tpu", 64, None, "a one-row prefill of 64 positions"),
    ("tpu", 256, None, "a prefill of 4 x 64 positions: two tiles' tokens"),
    ("tpu", 4, {"data": 4, "tensor": 1}, "a live mesh of several devices"),
    ("tpu", 4, {"data": 2, "tensor": 2}, "a live mesh of several devices"),
    ("cpu", 4, None, "here, on the CPU"),
    ("gpu", 4, None, "every other backend"),
])
@pytest.mark.parametrize("family, held, d, width, k", EXPERTS, ids=EXPERT_IDS)
def test_what_is_no_few_row_call_keeps_the_loop_of_conditionals(
        family, held, d, width, k, platform, rows, mesh_axes, why):
    # (with experts enough that the pairs would not decide)
    assert mla_moe.routed_path(platform, rows, d, width, 4096, k,
                               mesh_axes=mesh_axes) == "loop", why


@pytest.mark.parametrize("d, width, why", [
    (64, 48, "the tiny models' widths"),
    (2048, 200, "a width the blocks do not divide"),
    (2000, 768, "d unaligned"),
    (256, 128, "aligned, and too small to be worth a launch"),
])
def test_experts_the_blocks_do_not_divide_keep_the_loop(d, width, why):
    assert mla_moe.routed_path("tpu", 4, d, width, 128, 8) == "loop", why


@pytest.mark.parametrize("family, held, d, width, k", EXPERTS, ids=EXPERT_IDS)
def test_the_blocks_divide_the_published_experts_and_fit(
        family, held, d, width, k):
    """Both calls of a block: gate / up together (two leaves), down alone;
    a tile in flight and one in use for every leaf, the slots' float32
    output blocks beside them, inside what the kernel asks of VMEM."""
    for kk, nn, count in ((d, width, 2), (width, d, 1)):
        tk, tn = fd.block_sizes(kk, nn, count)
        assert kk % tk == 0 and nn % tn == 0 and tn == nn
        assert tk * tn * 2 * count <= fd.TILE_BYTES
        assert 2 * count * (tk * tn * 2 + 8 * tn * 4) + 2 * 8 * tk * 2 \
            <= fd.VMEM_LIMIT_BYTES // 2


# --- the kernel -----------------------------------------------------------------

def operands(rows, layers, k, n, count, seed=0):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (rows, k), jnp.float32).astype(jnp.bfloat16)
    leaves = [
        (jax.random.normal(jax.random.fold_in(key, i + 1), (layers, k, n),
                           jnp.float32) / np.sqrt(k)).astype(jnp.bfloat16)
        for i in range(count)]
    return x, leaves


@pytest.mark.parametrize("rows", [2, 3, 4, 8])
@pytest.mark.parametrize("k, n, count, blocks", [
    (256, 384, 1, None),            # one block
    (512, 256, 3, (128, 256)),      # q / k / v; K walked, columns whole
    (256, 512, 2, (128, 128)),      # gate / up; N outside, K inside
    (384, 640, 1, (384, 128)),      # N walked alone
])
def test_the_kernel_is_jnp_dot_on_a_layer_of_the_stacked_leaf(
        rows, k, n, count, blocks):
    x, leaves = operands(rows, 3, k, n, count, seed=rows)
    for layer in (2, 0):
        got = fd.fewrow_dense(x, leaves, jnp.int32(layer), blocks=blocks,
                              interpret=True)
        assert len(got) == count
        for w, y in zip(leaves, got):
            want = jnp.dot(x, w[layer], preferred_element_type=jnp.float32)
            assert y.dtype == jnp.float32 and y.shape == (rows, n)
            # equal to float32 rounding: only the order of the sum over
            # K differs
            np.testing.assert_allclose(y, want, rtol=0, atol=4e-6 * np.sqrt(k))
            assert not np.array_equal(
                y, jnp.dot(x, w[1], preferred_element_type=jnp.float32))


def test_a_leaf_with_no_layer_axis_is_its_own_only_layer():
    x, (w,) = operands(4, 1, 256, 256, 1)
    (y,) = fd.fewrow_dense(x, [w[0]], interpret=True)
    np.testing.assert_allclose(
        y, jnp.dot(x, w[0], preferred_element_type=jnp.float32), atol=1e-4)


def test_the_kernel_runs_under_a_scan_over_the_layer_index():
    """As the decode body calls it: the leaf closed over, the layer a
    traced scalar."""
    x, (w,) = operands(4, 3, 256, 256, 1)

    def body(h, l):
        (y,) = fd.fewrow_dense(h.astype(jnp.bfloat16), [w], l,
                               interpret=True)
        return jnp.tanh(y), None

    got, _ = jax.jit(lambda h: jax.lax.scan(body, h, jnp.arange(3)))(
        x.astype(jnp.float32))
    want = x.astype(jnp.float32)
    for l in range(3):
        want = jnp.tanh(jnp.dot(want.astype(jnp.bfloat16), w[l],
                                preferred_element_type=jnp.float32))
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("x_shape, leaf_shape, dtype", [
    ((4, 256), (2, 128, 256), jnp.bfloat16),    # K differs
    ((4, 200), (2, 200, 256), jnp.bfloat16),    # unaligned
    ((4, 256), (2, 256, 256), jnp.float32),     # another dtype than x's
    ((2, 2, 256), (2, 256, 256), jnp.bfloat16),  # x not [rows, K]
])
def test_operands_the_blocks_cannot_take_are_refused_by_name(
        x_shape, leaf_shape, dtype):
    with pytest.raises(ValueError, match="fewrow_dense"):
        fd.fewrow_dense(jnp.zeros(x_shape, jnp.bfloat16),
                        [jnp.zeros(leaf_shape, dtype)], interpret=True)


# --- the grouped product: a slot an expert (PR 44) ---------------------------------

def grouped_operands(rows, slots, layers, held, k, n, count, shared, seed=0):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (rows, k) if shared else (slots, rows, k),
                          jnp.float32).astype(jnp.bfloat16)
    leaves = [
        (jax.random.normal(jax.random.fold_in(key, i + 1),
                           (layers, held, k, n), jnp.float32)
         / np.sqrt(k)).astype(jnp.bfloat16) for i in range(count)]
    return x, leaves


# slots 6 over 7 experts held: (case, ids, live slots)
GROUPED = {
    "nothing hit": ([0, 0, 0, 0, 0, 0], 0),
    "one hit": ([5, 5, 5, 5, 5, 5], 1),
    "every slot hit": ([0, 1, 3, 4, 5, 6], 6),
    "the last hit repeated behind the hits": ([1, 2, 6, 6, 6, 6], 3),
    "ids behind the hits are not looked at": ([2, 4, 0, 3, 1, 5], 2),
}


@pytest.mark.parametrize("case", GROUPED)
@pytest.mark.parametrize("k, n, count, shared, blocks", [
    (256, 384, 1, True, None),             # one block an expert
    (512, 256, 2, True, (128, 256)),       # gate / up; K walked
    (256, 512, 1, False, (128, 128)),      # down: x a slot; N outside, K inside
    (384, 640, 2, False, (384, 128)),      # N walked alone
])
def test_the_grouped_kernel_is_jnp_dot_on_each_live_slots_expert(
        k, n, count, shared, blocks, case):
    """Every live slot: ``jnp.dot`` on ``leaf[l, ids[s]]``.  A dead slot is
    never written: the interpreter leaves what no grid step wrote as it
    made it, NaN (with nothing hit, the first slot's last block alone is
    handed back as it was fetched)."""
    ids, hits = GROUPED[case]
    rows, slots = 4, len(ids)
    x, leaves = grouped_operands(rows, slots, 3, 7, k, n, count, shared,
                                 seed=hits)
    for layer in (2, 0):
        got = fd.fewrow_grouped(
            x, leaves, jnp.int32(layer), jnp.asarray(ids, jnp.int32),
            jnp.int32(hits), blocks=blocks, interpret=True)
        assert len(got) == count
        for w, y in zip(leaves, got):
            assert y.dtype == jnp.float32 and y.shape == (slots, rows, n)
            for s in range(hits):
                want = jnp.dot(x if shared else x[s], w[layer, ids[s]],
                               preferred_element_type=jnp.float32)
                np.testing.assert_allclose(y[s], want, rtol=0,
                                           atol=4e-6 * np.sqrt(k))
                other = jnp.dot(x if shared else x[s],
                                w[1, (ids[s] + 1) % 7],
                                preferred_element_type=jnp.float32)
                assert not np.allclose(y[s], other, atol=1e-3)
            assert np.isnan(np.asarray(y[max(hits, 1):])).all()


def test_the_grouped_kernel_runs_under_a_scan_with_traced_ids_and_hits():
    """As the decode body calls it: the leaves closed over, the layer, the
    ids and the number hit traced values; gate / up in one call, down in a
    second over each slot's own rows."""
    x, (gate, up) = grouped_operands(4, 3, 2, 5, 256, 128, 2, True)
    _, (down,) = grouped_operands(4, 3, 2, 5, 128, 256, 1, True, seed=1)
    ids = jnp.asarray([[0, 2, 4], [1, 3, 3]], jnp.int32)
    hits = jnp.asarray([3, 2], jnp.int32)

    def body(h, own):
        l, ids, hits = own
        g, u = fd.fewrow_grouped(h.astype(jnp.bfloat16), [gate, up], l, ids,
                                 hits, interpret=True)
        (o,) = fd.fewrow_grouped((g * u).astype(jnp.bfloat16), [down], l,
                                 ids, hits, interpret=True)
        live = (jnp.arange(3) < hits)[:, None, None]
        return jnp.tanh(jnp.sum(jnp.where(live, o, 0.0), axis=0)), None

    got, _ = jax.jit(lambda h: jax.lax.scan(
        body, h, (jnp.arange(2), ids, hits)))(x.astype(jnp.float32))
    want = x.astype(jnp.float32)
    for l in range(2):
        total = 0.0
        for s in range(int(hits[l])):
            e = int(ids[l, s])
            g, u = (jnp.dot(want.astype(jnp.bfloat16), w[l, e],
                            preferred_element_type=jnp.float32)
                    for w in (gate, up))
            total = total + jnp.dot((g * u).astype(jnp.bfloat16), down[l, e],
                                    preferred_element_type=jnp.float32)
        want = jnp.tanh(total)
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("x_shape, leaf_shape, dtype", [
    ((4, 256), (2, 5, 128, 256), jnp.bfloat16),     # K differs
    ((4, 200), (2, 5, 200, 256), jnp.bfloat16),     # unaligned
    ((4, 256), (2, 5, 256, 256), jnp.float32),      # another dtype than x's
    ((2, 4, 256), (2, 5, 256, 256), jnp.bfloat16),  # x for 2 of 3 slots
    ((256,), (2, 5, 256, 256), jnp.bfloat16),       # x with no rows
])
def test_operands_the_grouped_blocks_cannot_take_are_refused_by_name(
        x_shape, leaf_shape, dtype):
    with pytest.raises(ValueError, match="fewrow_grouped"):
        fd.fewrow_grouped(jnp.zeros(x_shape, jnp.bfloat16),
                          [jnp.zeros(leaf_shape, dtype)], 0,
                          jnp.zeros((3,), jnp.int32), 1, interpret=True)


# --- a tied embedding read as the head (PR 40) ------------------------------------

@pytest.mark.parametrize("rows", [2, 4, 8])
@pytest.mark.parametrize("n, k, block_rows", [
    (384, 256, None),               # one block
    (640, 256, 128),                # the rows of the leaf walked
])
def test_the_transposed_kernel_is_jnp_dot_with_the_leaf_transposed(
        rows, n, k, block_rows):
    x, (w,) = operands(rows, 1, n, k, 1, seed=rows)    # w[0]: [n, k]
    leaf = w[0]
    x = x[:, :k] if n >= k else jnp.tile(x, (1, -(-k // n)))[:, :k]
    y = fd.fewrow_dense_t(x, leaf, block_rows=block_rows, interpret=True)
    want = jnp.dot(x, leaf.T, preferred_element_type=jnp.float32)
    assert y.dtype == jnp.float32 and y.shape == (rows, n)
    np.testing.assert_allclose(y, want, rtol=0, atol=4e-6 * np.sqrt(k))
    with pytest.raises(ValueError, match="fewrow_dense_t"):
        fd.fewrow_dense_t(x[:, :k - 56], leaf[:, :k - 56], interpret=True)


def test_the_tied_head_takes_the_kernel_where_a_head_would(monkeypatch):
    """`dense_tied` asks `dense_path` about the ``[d, V]`` weight the
    leaf is the transpose of: the kernel at 2 to 8 rows on a TPU, a
    ``dot_general`` over the second axis of both everywhere else; both
    give ``x @ leaf.T``."""
    leaf = (jax.random.normal(jax.random.PRNGKey(1), (1024, 512),
                              jnp.float32) / 16).astype(jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 1, 512), jnp.float32)
    want = jnp.dot(x.astype(jnp.bfloat16), leaf.T,
                   preferred_element_type=jnp.float32)
    np.testing.assert_allclose(looplm.dense_tied(x, leaf, GRANITE), want,
                               atol=1e-4)
    monkeypatch.setattr(looplm, "_where", lambda: ("tpu", None))
    calls = []
    monkeypatch.setattr(looplm, "fewrow_dense_t", lambda x, leaf: (
        calls.append(x.shape), fd.fewrow_dense_t(x, leaf, interpret=True))[1])
    before = trace.DENSE_PATHS.snapshot().get("fewrow_tied_few", 0)
    np.testing.assert_allclose(looplm.dense_tied(x, leaf, GRANITE), want,
                               atol=1e-4)
    assert calls == [(4, 512)]
    assert trace.DENSE_PATHS.snapshot()["fewrow_tied_few"] == before + 1
    looplm.dense_tied(x[:1], leaf, GRANITE)
    assert calls == [(4, 512)]                       # one row: no kernel
    tn = fd._largest_divisor(100352, fd.TILE_BYTES // 2 // 2048)
    assert tn == 1024 and 100352 % tn == 0


# --- the two scan structures, held together on the tiny models --------------------

def tiny_run(arch, cfg, rows, where, monkeypatch):
    monkeypatch.setattr(looplm, "_where", lambda: where)
    params = arch.seeded_params(cfg, np.uint32(5))
    ids = np.random.RandomState(3).randint(1, cfg.vocab_size, (rows, 8))
    tokens, logits, aux, stats = arch.make_program(cfg, 4)(
        params, ids.astype(np.int32), np.asarray([8, 5, 7, 3][:rows], np.int32),
        np.arange(rows, dtype=np.uint32), np.zeros((rows,), np.float32))
    return np.asarray(tokens), np.asarray(logits), jax.tree_util.tree_map(
        np.asarray, (aux, stats))


@pytest.mark.parametrize("family", ["ouro", "pangu", "exaone", "granite",
                                    "keye", "phi4flash"])
def test_a_scan_over_the_index_gives_what_a_scan_over_the_slices_gives(
        family, monkeypatch):
    """With the platform read as a TPU's the 4-row decode walks the layer
    index with the leaves closed over (`scan_layers`, `Stacked`); the tiny
    widths are no multiples of 128, so every product is still ``jnp.dot``
    and the CPU can run it: the same numbers as the scan over slices."""
    arch = FAMILIES[family][0]
    cfg = arch.CONFIGS["tiny"]
    a = tiny_run(arch, cfg, 4, ("tpu", None), monkeypatch)
    b = tiny_run(arch, cfg, 4, ("cpu", None), monkeypatch)
    assert np.array_equal(a[0], b[0])
    np.testing.assert_allclose(a[1], b[1], rtol=0, atol=1e-5)
    for x, y in zip(jax.tree_util.tree_leaves(a[2]),
                    jax.tree_util.tree_leaves(b[2])):
        np.testing.assert_allclose(x, y, rtol=0, atol=1e-5)


# --- the published sizes, lowered and compiled for the chip -----------------------

def traced(family, rows, where, monkeypatch, sharding=None, positions=64):
    """``lm_generate`` of the published size at ``rows`` rows of
    ``positions`` prompt positions, traced over shapes (no weight is
    made) with the rule reading ``where``."""
    monkeypatch.setattr(looplm, "_where", lambda: where)
    arch, cfg = FAMILIES[family]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree_util.tree_map(
        lambda s: spec(s, cfg.dtype), arch.param_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple))
    return arch.make_program(cfg, 64).trace(
        params, spec((rows, positions), np.int32), spec((rows,), np.int32),
        spec((rows,), np.uint32), spec((rows,), np.float32))


@pytest.mark.parametrize("family", ["ouro", "pangu", "exaone", "granite",
                                    "keye", "phi4flash"])
def test_the_one_row_program_is_untouched_by_the_rule(family, monkeypatch):
    """Its text as lowered with the platform read as a TPU's is, byte for
    byte, its text with the rule off; and the 4-row program's is not."""
    on = traced(family, 1, ("tpu", None), monkeypatch).lower().as_text()
    off = traced(family, 1, ("cpu", None), monkeypatch).lower().as_text()
    assert on == off and "custom_call" not in on
    # (the CPU cannot lower the kernel: the 4-row program's jaxpr)
    four = str(traced(family, 4, ("tpu", None), monkeypatch).jaxpr)
    assert "pallas_call" in four and "fewrow_dense" in four
    assert "pallas_call" not in str(
        traced(family, 4, ("cpu", None), monkeypatch).jaxpr)


@pytest.fixture(scope="module")
def topo():
    """A described 2x2 host of v5e chips (no device attached): what the
    chip's compiler refuses, it refuses here."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - whatever libtpu raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%(?P<name>[\w.\-]+) = (?P<dtype>\w+)\[(?P<dims>[\d,]*)\]"
    r"\S* (?P<op>[\w\-]+)\((?P<rest>.*)$")
# what hands a buffer on without writing one
PASSES_ON = {"get-tuple-element", "parameter", "bitcast", "tuple", "while",
             "conditional", "dynamic-update-slice", "copy-start",
             "copy-done"}


def computations(text):
    """``{name: [instruction lines]}`` of an HLO module's text, the entry
    computation under ``ENTRY``."""
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            out[name].append(line)
    return out


def weight_sized_results(lines, layer_elements):
    """The instructions of one computation that WRITE a bf16 buffer of a
    weight's layer (or more): a ``copy``, a ``dynamic-slice``, or a fusion
    rooted in one, whatever it is called."""
    found = []
    for line in lines:
        m = INSTRUCTION.match(line)
        # (a fusion rooted in a dynamic-update-slice writes the cache in
        # place; Ouro's has as many values as a projection's leaf)
        if not m or m["dtype"] != "bf16" or m["op"] in PASSES_ON \
                or "dynamic-update-slice" in m["name"]:
            continue
        size = int(np.prod([int(d) for d in m["dims"].split(",") if d]))
        if size in layer_elements:
            found.append((m["name"], m["op"], m["dims"]))
    return found


COMPILED = {}


def compiled_four_rows(family, one_chip, monkeypatch, positions=64):
    """The text of the 4-row program of the published size (a prefill of
    4 x ``positions``, 64 steps) as compiled for the described chip, once
    a family and length."""
    if (family, positions) not in COMPILED:
        COMPILED[family, positions] = traced(
            family, 4, ("tpu", None), monkeypatch, one_chip,
            positions).lower().compile().as_text()
    return COMPILED[family, positions]


def weights_of(family):
    """The shapes of a family's large matrices, and the values in a layer
    of each and in each whole leaf."""
    arch, cfg = FAMILIES[family]
    matrices = [s for s in jax.tree_util.tree_leaves(
        arch.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
        if len(s) >= 2 and s[-1] * s[-2] >= 1 << 20]
    return matrices, {s[-1] * s[-2] for s in matrices} \
        | {int(np.prod(s)) for s in matrices}


@pytest.mark.parametrize("family, known", [
    ("ouro", set()),
    # the absorption einsums (per-head batched products, not `_dense`'s)
    # still have XLA slice `kv_b_proj`'s layer out: PERF.md section 7
    ("pangu", {512 * 32768}),
    ("exaone", set()),
    ("granite", set()),
    ("keye", set()),
    ("phi4flash", set()),
])
def test_no_decode_body_of_the_four_row_program_materialises_a_weight(
        family, known, one_chip, no_compile_cache, monkeypatch):
    arch, cfg = FAMILIES[family]
    text = compiled_four_rows(family, one_chip, monkeypatch)
    matrices, layer_elements = weights_of(family)
    bodies = {name: lines for name, lines in computations(text).items()
              if name != "ENTRY" and "fused" not in name
              and any("fewrow_dense" in l and "custom-call(" in l
                      for l in lines)}
    assert bodies, "no computation but the entry's holds the kernel"
    calls = [l for lines in bodies.values() for l in lines
             if "custom-call(" in l and "fewrow_dense" in l]
    # every call reads WHOLE leaves: each weight operand has a leaf's
    # shape (the head's with a layer axis of one), none a layer's
    whole = {tuple(s) for s in matrices} \
        | {(1, *s) for s in matrices if len(s) == 2}
    for call in calls:
        if "fewrow_dense_t" in call:     # a tied leaf [V, d]: further down
            continue
        constraints = call.split("operand_layout_constraints=")[1] \
            .split("frontend_attributes")[0]
        weights = [tuple(int(d) for d in dims) for dims in re.findall(
            r"bf16\[(\d+),(\d+),(\d+)\]", constraints)]
        assert weights and all(w in whole for w in weights), call[:200]
    for name, lines in bodies.items():
        wrote = [w for w in weight_sized_results(lines, layer_elements)
                 if int(np.prod([int(d) for d in w[2].split(",")]))
                 not in known]
        assert not wrote, (name, wrote)
    # and the kernel's operations carry the published modules' scopes
    paths = {p for l in calls for p in re.findall(r'op_name="([^"]+)"', l)}
    classes = {trace.classify(p) for p in paths}
    # (keye: no MLP that is no expert's, the indexer's query projection,
    # and a head that stays with XLA)
    assert classes == ({"lm_proj", "lm_index"} if family == "keye" else
                       {"lm_proj", "lm_mlp", "lm_head"}), paths
    if family == "granite":
        _granite_decode_keeps_its_state_in_place(text, bodies)
    if family == "keye":
        _keye_decode_keeps_its_caches_and_experts_in_place(text, bodies)
    if family == "keye":
        _decode_runs_its_hit_experts_through_one_stream(text, family)
    if family in ("pangu", "exaone"):
        _decode_walks_its_sixteen_experts_in_the_loop(text)
    segments = {seg for p in paths for seg in p.split("/")}
    want = {"ouro": {"q_proj", "o_proj", "gate_proj", "down_proj", "lm_head",
                     "fewrow_dense_q_proj_k_proj_v_proj",
                     "fewrow_dense_gate_proj_up_proj"},
            "pangu": {"q_a_proj", "q_b_proj", "o_proj", "gate_proj",
                      "down_proj", "shared_experts", "lm_head"},
            "exaone": {"q_proj", "o_proj", "gate_proj", "down_proj",
                       "shared_experts", "lm_head",
                       "fewrow_dense_k_proj_v_proj",
                       "fewrow_dense_gate_proj_up_proj"},
            "granite": {"in_proj", "out_proj", "input_linear",
                        "output_linear", "q_proj", "o_proj", "lm_head",
                        "fewrow_dense_k_proj_v_proj",
                        "fewrow_dense_t"},
            "keye": {"q_proj", "o_proj", "indexer", "wq",
                     "fewrow_dense_k_proj_v_proj"},
            "phi4flash": {"in_proj", "out_proj", "Wqkv", "fc1", "fc2",
                          "mamba", "swa", "memory", "full", "gmu", "cross",
                          "lm_head", "fewrow_dense_t"}}[family]
    assert want <= segments, want - segments


def _decode_runs_its_hit_experts_through_one_stream(text, family):
    """PR 44: a decode step's routed experts are two calls of the grouped
    kernel a block (gate / up, then down) whose weight operands are the
    WHOLE expert leaves ``[L, E, K, N]``, under the ``experts`` scope of
    the decode phase, inside ONE conditional (a block that hits nothing);
    no loop over the experts held is left in the decode, and the prefill
    (4 x 64 tokens: no few-row call) keeps its own."""
    arch, cfg = FAMILIES[family]
    leaves = {tuple(shape) for shape in
              arch.param_shapes(cfg)["moe_layers" if family != "keye"
                                     else "layers"]["experts"].values()}
    assert all(len(shape) == 4 and shape[1] == cfg.experts_held
               for shape in leaves), leaves
    lines = [l for body in computations(text).values() for l in body]
    calls = [l for l in lines if "custom-call(" in l and "fewrow_grouped" in l]
    assert sorted("down_proj" in l for l in calls) == [False, True], \
        [c[:120] for c in calls]
    for call in calls:
        count = 1 if "down_proj" in call else 2
        constraints = call.split("operand_layout_constraints=")[1] \
            .split("frontend_attributes")[0]
        weights = [tuple(int(d) for d in dims) for dims in re.findall(
            r"bf16\[(\d+),(\d+),(\d+),(\d+)\]", constraints)]
        assert len(weights) == count and set(weights) <= leaves, call[:300]
        (path,) = re.findall(r'op_name="([^"]+)"', call)
        assert trace.classify(path) == "lm_experts" \
            and trace.phase_of(path) == "decode" \
            and "/experts/cond/" in path, path
    paths = re.findall(r'op_name="([^"]+)"', text)
    assert not any("/decode/" in p and "/experts/while" in p for p in paths)
    assert any("/prefill/" in p and "/experts/while" in p for p in paths)
    # the loop's conditionals (one an expert held) are the prefill's alone
    conditionals = [l for l in lines if " conditional(" in l
                    and "/experts/" in l]
    assert sum("/decode/" in l for l in conditionals) == 1, conditionals


def _decode_walks_its_sixteen_experts_in_the_loop(text):
    """PR 44's rule leaves the two shares of 16 held experts where they
    were (4 rows route 32 pairs: no more conditionals than slots): no
    grouped call, the decode's experts under their loop."""
    # (instructions, not the text: the module's table of stack frames
    # names every function a process has traced)
    assert not [l for l in text.splitlines()
                if "custom-call(" in l and "fewrow_grouped" in l]
    paths = re.findall(r'op_name="([^"]+)"', text)
    assert any("/decode/" in p and "/experts/while" in p for p in paths)


def _keye_decode_keeps_its_caches_and_experts_in_place(text, bodies,
                                                       positions=64):
    """Both caches are written in place and the wide one is READ by
    index: no computation that holds a kernel call (a decode step's own
    or the layer scan's body) writes a buffer of a whole cache (keys or
    values ``[6, 4, T, 4, 128]``, index keys ``[6, 4, T, 64]``), of one
    layer of the keys and values, or of a whole expert leaf
    (``[6, 128, ...]``: a slice handed to the conditional would be a
    copy) but by an in-place ``dynamic-update-slice``; and the head, which
    the rule leaves to XLA, is read where it lies."""
    T = positions + 64
    state = {f"bf16[6,4,{T},4,128]", f"bf16[6,4,{T},64]",
             f"bf16[4,{T},4,128]", "bf16[6,128,2048,768]",
             "bf16[6,128,768,2048]", "bf16[128,2048,768]",
             "bf16[128,768,2048]", "bf16[2048,151936]",
             "bf16[151936,2048]"}
    seen = set()
    for name, lines in bodies.items():
        for line in lines:
            m = INSTRUCTION.match(line)
            if not m:
                continue
            seen.add(f"{m['dtype']}[{m['dims']}]")
            if m["op"] in PASSES_ON or "dynamic-update-slice" in m["name"] \
                    or "dynamic_update_slice" in line:
                continue
            assert f"{m['dtype']}[{m['dims']}]" not in state, \
                (name, line.strip()[:200])
    # (the caches and the expert leaves do pass through those bodies)
    assert {f"bf16[6,4,{T},4,128]", f"bf16[6,4,{T},64]",
            "bf16[6,128,2048,768]"} <= seen
    # a step gathers the keys chosen, it does not slice the layer
    assert any("gather(" in l and "indexer/gather" in l
               for lines in computations(text).values() for l in lines)


def _granite_decode_keeps_its_state_in_place(text, bodies, positions=128):
    """The tied leaf is read where it lies (the transposed kernel's
    operand is the ``[V, d]`` parameter), and no computation that holds a
    kernel call (a decode step's own or a layer scan's body) writes a
    buffer of the whole recurrent state or of a whole cache but by an
    in-place ``dynamic-update-slice`` (or a fusion rooted in one): 302 MB
    of state at 4 rows would be 0.7 ms a step to copy."""
    tied = [l for l in text.splitlines()
            if "custom-call(" in l and "fewrow_dense_t" in l]
    assert tied and all(
        "bf16[100352,2048]{1,0}" in l.split(
            "operand_layout_constraints=")[1] for l in tied)
    state = {"f32[36,4,64,64,128]", f"bf16[4,4,{positions},8,64]"}
    for name, lines in bodies.items():
        for line in lines:
            m = INSTRUCTION.match(line)
            if not m or m["op"] in PASSES_ON \
                    or "dynamic-update-slice" in m["name"] \
                    or "dynamic_update_slice" in line:
                continue
            assert f"{m['dtype']}[{m['dims']}]" not in state, \
                (name, line.strip()[:200])


def test_the_programs_of_a_shared_prefix_compile_for_the_chip(
        one_chip, no_compile_cache, monkeypatch):
    """PR 41 at the published widths and the cell's lengths: the maker
    over the 1,951 shared ids, and the 4-row ``lm_generate`` that starts
    every row from its snapshot and prefills the 97 positions behind it.
    The chip's compiler takes both; the decode steps still stream every
    large leaf through the kernel and keep the state of both kinds in
    place (the SAME decode as the full program's, behind another
    prefill); and without a 4 x 2048 prefill the program's temporaries
    are a quarter of the full one's 2.4 GB."""
    monkeypatch.setattr(looplm, "_where", lambda: ("tpu", None))

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda s: spec(s, GRANITE.dtype), ssm_hybrid.param_shapes(GRANITE),
        is_leaf=lambda x: isinstance(x, tuple))
    held, own = 1951, 97
    maker = ssm_hybrid.make_prefix_program(GRANITE)
    made = maker.lower(params, spec((held,), np.int32)).compile()
    assert "jit_lm_prefix_state" in made.as_text()[:200]
    snapshot = {k: spec(v.shape, v.dtype) for k, v in jax.eval_shape(
        maker, params, spec((held,), np.int32)).items()}
    assert sum(int(np.prod(v.shape)) * v.dtype.itemsize
               for v in snapshot.values()) \
        == ssm_hybrid.prefix_bytes(GRANITE, held) == 76_437_504 + held * 8192
    program = ssm_hybrid.make_program(GRANITE, 64).lower(
        params, spec((4, own), np.int32), spec((4,), np.int32),
        spec((4,), np.uint32), spec((4,), np.float32), snapshot).compile()
    assert program.memory_analysis().temp_size_in_bytes < 0.7e9
    text = program.as_text()
    assert "jit_lm_generate" in text[:200]
    bodies = {name: lines for name, lines in computations(text).items()
              if name != "ENTRY" and "fused" not in name
              and any("fewrow_dense" in l and "custom-call(" in l
                      for l in lines)}
    assert bodies, "no computation but the entry's holds the kernel"
    _granite_decode_keeps_its_state_in_place(text, bodies,
                                             held + own + 64)
    _, layer_elements = weights_of("granite")
    for name, lines in bodies.items():
        assert not weight_sized_results(lines, layer_elements), name
    phases = {trace.phase_of(p)
              for p in re.findall(r'op_name="([^"]+)"', text)
              if "GraniteMoeHybrid" in p}
    assert phases == {"prefill", "decode"}


@pytest.mark.parametrize("family", ["pangu", "exaone"])
def test_a_tile_of_an_experts_tokens_copies_no_weight(
        family, one_chip, no_compile_cache, monkeypatch):
    """A prefill of 4 x 128 positions is four tiles' worth of tokens, so a
    hit expert walks tiles (PR 35; the 4 x 64 of the other tests is not
    gathered).  The expert's three matrices are sliced out of the stacked
    leaves by the conditional, once an expert; the body of the loop over
    its TILES gathers, multiplies ``[tile, d]`` and hands the result to
    `row_scatter_add`, which the chip's compiler takes at the published
    widths, and writes no buffer of a weight's size nor one of the whole
    sum's: the bytes of a hit expert are paid once, whatever its tiles,
    and the sum is updated where it lies."""
    text = compiled_four_rows(family, one_chip, monkeypatch, positions=128)
    _, layer_elements = weights_of(family)
    tile, d = mla_moe.EXPERT_TILE, FAMILIES[family][1].hidden_size
    bodies = {name: lines for name, lines in computations(text).items()
              if "fused" not in name and any(
                  "/experts/" in l and "row_scatter_add" in l
                  and "custom-call(" in l for l in lines)}
    assert bodies, "no computation holds a tile's kernel call"
    for name, lines in bodies.items():
        assert not weight_sized_results(lines, layer_elements), name
        made = [m for m in map(INSTRUCTION.match, lines)
                if m and m["op"] not in PASSES_ON]
        # the sum [4 x 128, d / 128, 128] leaves the kernel and nothing else
        whole = [m["op"] for m in made if m["dtype"] == "f32"
                 and m["dims"] == f"512,{d // 128},128"]
        assert whole == ["custom-call"], (name, whole)
        # a tile's gather and its products, and nothing of all the tokens
        shapes = {(m["dtype"], m["dims"]) for m in made
                  if "/experts/" in m["rest"] and (
                      "gather" in m["rest"] or "dot_general" in m["rest"])}
        assert ("bf16", f"{tile},{d}") in shapes, shapes
        assert not any(dims.startswith("512,") for _, dims in shapes), shapes


def taken_computations(text):
    """The instruction lines that run when `GEGLU`'s conditional takes the
    kernel: the entry computation and the conditional's second branch
    (``lax.cond`` puts the false branch first), each result a buffer."""
    comps = computations(text)
    branches = re.findall(r"branch_computations=\{%([\w.\-]+), %([\w.\-]+)\}",
                          "\n".join(comps["ENTRY"]))
    assert branches, "no conditional in the entry computation"
    return [comps["ENTRY"]] + [comps[true] for _, true in branches]


@pytest.mark.parametrize("b,t,c", [(2, 4096, 640), (2, 1024, 1280),
                                   (2, 4096, 320), (2, 1024, 640),
                                   (2, 256, 1280)])
def test_the_unets_feed_forward_writes_no_8c_projection(
        b, t, c, one_chip, no_compile_cache, monkeypatch):
    """The UNet's `FeedForward` at the five published shapes, traced as a
    TPU traces it and compiled for the described chip (PR 39; here because
    this file holds the fixture): GEGLU is ONE kernel call that hands
    ``[rows, 4c]`` on, the chip's compiler takes its blocks, no
    instruction writes the ``[.., 8c]`` projection, and none writes a
    buffer of the ``proj`` leaf's size: the two index maps read the one
    leaf where it lies."""
    from comfyui_distributed_tpu.models import layers
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ff = layers.FeedForward(dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((b, t, c), jnp.float32, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(ff.init, jax.random.PRNGKey(0), x))
    text = jax.jit(ff.apply).lower(params, x).compile().as_text()
    taken = taken_computations(text)
    made = [m for lines in taken for m in map(INSTRUCTION.match, lines)
            if m and m["op"] not in PASSES_ON]
    calls = [m for m in made if m["op"] == "custom-call"
             and "tpu_custom_call" in m["rest"]]
    assert [(m["dtype"], m["dims"]) for m in calls] \
        == [("bf16", f"{b * t},{4 * c}")]
    assert "/geglu/cond/branch_1_fun/jit(_fused_geglu)/" in calls[0]["rest"]
    # what lies in HBM (a result in memory space 1 is a prefetch into
    # fast memory: at c = 320 the compiler brings the 1.6 MB leaf there)
    in_hbm = [m for m in made
              if "S(1)}" not in m.group(0).split(" = ")[1].split(" ")[0]]
    assert not [m for m in in_hbm if m["dims"].endswith(f",{8 * c}")]
    assert not [m for m in in_hbm if m["dims"] == f"{c},{8 * c}"]


@pytest.mark.parametrize("t,c", [(4096, 640), (1024, 1280)])
def test_the_fan_out_programs_feed_forward_runs_each_chip_on_its_rows(
        t, c, topo, no_compile_cache, monkeypatch):
    """`sdxl_1024_fanout4`'s feed-forward (eight CFG-stacked rows over
    ``data=4``), compiled for the described 2x2 host: XLA cannot partition
    a Mosaic call, so it goes through ``shard_map``; each chip's kernel
    call hands on its own ``[2t, 4c]`` and the program holds no
    collective."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from comfyui_distributed_tpu.models import layers
    mesh = Mesh(np.array(topo.devices).reshape(4, 1, 1),
                ("data", "tensor", "seq"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(layers, "_live_mesh", lambda: mesh)
    ff = layers.FeedForward(dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((8, t, c), jnp.float32, sharding=NamedSharding(
        mesh, PartitionSpec("data")))
    whole = NamedSharding(mesh, PartitionSpec())
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=whole),
        jax.eval_shape(ff.init, jax.random.PRNGKey(0), x))
    text = jax.jit(ff.apply).lower(params, x).compile().as_text()
    calls = [m for lines in taken_computations(text)
             for m in map(INSTRUCTION.match, lines)
             if m and "tpu_custom_call" in m["rest"]]
    assert [(m["dtype"], m["dims"]) for m in calls] \
        == [("bf16", f"{2 * t},{4 * c}")]
    assert not re.search(r"all-gather|all-reduce|collective-permute|"
                         r"all-to-all", text)


def test_a_convolution_that_feeds_a_geglu_keeps_its_space_to_batch_form(
        one_chip, no_compile_cache, monkeypatch):
    """Why `GEGLU` puts its kernel behind a conditional (PR 39).  The
    chip's compiler rewrites a 3x3 convolution over ``[2, 64, 64, c]``
    into one over 64 tiles ``[64, 16, 9, c]``, and gives that up for a
    convolution whose result reaches a custom call through at most one
    product: a ResBlock in front of a transformer, traced as a TPU traces
    it, must still compile to the tiled form (with the bare call it
    compiled to ``[2, 64, 64, c]`` at a 2-row tile, and the UNet's
    convolutions lost more than the kernel won)."""
    from flax import linen as nn
    from comfyui_distributed_tpu.models import layers
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    class Level(nn.Module):
        @nn.compact
        def __call__(self, x, emb, ctx):
            x = layers.ResBlock(320, name="res_0")(x, emb)
            return layers.SpatialTransformer(8, name="attn_0")(x, ctx)

    shaped = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
              for s in ((2, 64, 64, 320), (2, 1280), (2, 77, 768))]
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(Level().init, jax.random.PRNGKey(0), *shaped))
    text = jax.jit(Level().apply).lower(params, *shaped).compile().as_text()
    assert text.count("tpu_custom_call") == 2       # attention, GEGLU
    convs = [m for lines in computations(text).values()
             for m in map(INSTRUCTION.match, lines)
             if m and m["op"] == "fusion" and "kind=kOutput" in m["rest"]
             and re.search(r"res_0/(in|out)_conv/conv_general_dilated",
                           m["rest"])]
    assert {m["dims"] for m in convs} == {"64,16,9,320"}, convs
