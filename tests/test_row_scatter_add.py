"""How a tile of an expert's results goes back into the sum (PR 35): the
kernel ``ops/pallas/row_scatter_add.py`` against numpy (Pallas
interpreter, CPU), the rule that sends a tile there
(``mla_moe.tile_sum_path``), and `_routed` through the kernel against
`_routed` through XLA's scatter.  The kernel compiled for a described v5e
inside the published 4-row programs is ``tests/test_fewrow_dense.py``'s."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import looplm, mla_moe
from comfyui_distributed_tpu.ops.pallas import row_scatter_add as rsa

interpreted = functools.partial(rsa.row_scatter_add, interpret=True)


@pytest.mark.parametrize("count", [0, 1, 7, 16])
def test_the_kernel_adds_the_live_rows_and_touches_no_other(count):
    """``y[rows[i]] += update[i]`` for ``i < count``, to the bit; what
    stands in ``rows`` past ``count`` is never looked at (a row outside
    ``y`` there does no harm)."""
    rng = np.random.default_rng(count)
    t, g, tile = 40, 2, 16
    y = rng.standard_normal((t, g, 128)).astype(np.float32)
    update = rng.standard_normal((tile, g, 128)).astype(np.float32)
    rows = np.sort(rng.permutation(t)[:tile]).astype(np.int32)
    rows[count:] = t + 5
    got = jax.jit(interpreted)(y, rows, count, update)
    want = y.copy()
    want[rows[:count]] += update[:count]
    assert np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("y, update", [
    ((40, 256), (16, 256)),                   # not laid out in lanes
    ((40, 2, 64), (16, 2, 64)),
    ((40, 2, 128), (16, 3, 128)),             # another row's width
    ((40, 2, 128), (8, 2, 128)),              # fewer rows than indices
])
def test_operands_the_kernel_cannot_take_are_refused_by_name(y, update):
    with pytest.raises(ValueError, match="row_scatter_add"):
        rsa.row_scatter_add(jnp.zeros(y, jnp.float32),
                            jnp.zeros((16,), jnp.int32), 0,
                            jnp.zeros(update, jnp.float32))
    with pytest.raises(ValueError, match="row_scatter_add"):
        rsa.row_scatter_add(jnp.zeros((40, 2, 128), jnp.bfloat16),
                            jnp.zeros((16,), jnp.int32), 0,
                            jnp.zeros((16, 2, 128), jnp.bfloat16))


@pytest.mark.parametrize("platform, d, mesh_axes, want", [
    ("tpu", 6144, None, "kernel"),
    ("tpu", 7680, {"data": 1, "tensor": 1}, "kernel"),
    ("tpu", 6144, {"data": 2, "tensor": 1}, "xla"),   # a custom call
    ("tpu", 64, None, "xla"),                         # no whole lane
    ("tpu", 192, None, "xla"),
    ("cpu", 6144, None, "xla"),
    ("gpu", 6144, None, "xla"),
])
def test_the_rule_over_platform_width_and_mesh(platform, d, mesh_axes, want):
    assert mla_moe.tile_sum_path(platform, d, mesh_axes) == want


WIDE = dataclasses.replace(mla_moe.TINY_MLA_MOE, hidden_size=128)


def routed(cfg, where, tokens, monkeypatch, tile=8, seed=5):
    """`_routed` of ``tokens`` random tokens over a share of the tiny
    expert layer at one lane's width, with the platform read as
    ``where`` and the kernel interpreted; and its traced text."""
    monkeypatch.setattr(mla_moe, "EXPERT_TILE", tile)
    monkeypatch.setattr(mla_moe, "row_scatter_add", interpreted)
    monkeypatch.setattr(looplm, "_where", lambda: where)
    whole = dataclasses.replace(cfg, experts_first=0, experts_held=16)
    full = mla_moe.seeded_params(whole, np.uint32(11))["moe_layers"]
    experts = {k: w[:, cfg.experts_first:cfg.experts_first + 4]
               for k, w in full["experts"].items()}
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((tokens, cfg.hidden_size)),
                    jnp.float32)
    _, chosen, weights = mla_moe.route(cfg, full["gate"][1], x)

    def f(x, chosen, weights):
        return mla_moe._routed(cfg, experts, jnp.int32(1), x, chosen,
                               weights)
    return jax.jit(f)(x, chosen, weights), \
        str(jax.make_jaxpr(f)(x, chosen, weights))


@pytest.mark.parametrize("tokens", [17, 40])
@pytest.mark.parametrize("first", [0, 4])
def test_the_tiles_through_the_kernel_are_the_tiles_through_xla(
        tokens, first, monkeypatch):
    """The same sum to float32's last digit (the same rows get the same
    terms in the same order; XLA may fuse the weight's multiply into its
    own add), the same counts; the traced program holds the kernel and no
    scatter where the rule sends a tile there, and the reverse where it
    does not."""
    cfg = dataclasses.replace(WIDE, experts_first=first)
    got, text = routed(cfg, ("tpu", None), tokens, monkeypatch)
    want, plain = routed(cfg, ("cpu", None), tokens, monkeypatch)
    assert "row_scatter_add" in text and "scatter-add" not in text
    assert "row_scatter_add" not in plain and "scatter-add" in plain
    np.testing.assert_allclose(got[0], want[0], rtol=0, atol=1e-6)
    assert float(jnp.abs(want[0]).max()) > 1e-2
    for a, b in zip(got[1:], want[1:]):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("tokens", [4, 8, 16])
def test_a_call_of_one_tile_never_meets_the_kernel(tokens, monkeypatch):
    """A decode step's `_routed` on a TPU, and a call of up to two tiles'
    tokens, is what it is anywhere: no sort, no gather, no kernel."""
    _, text = routed(WIDE, ("tpu", None), tokens, monkeypatch)
    for name in ("row_scatter_add", "pallas_call", "sort", "gather",
                 "scatter"):
        assert name not in text, name
