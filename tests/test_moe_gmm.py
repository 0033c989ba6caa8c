"""``ops/moe_gmm.py`` in interpreter mode against ``jax.lax.ragged_dot``
and its ``jax.vjp``: the grouped product, its transposed form and the
matrices' cotangent, over groupings that end mid-tile, leave rows past the
last group, hold an empty group, fill every row or are one group; and the
tile rule at the three configurations' shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddstore_tpu.models.moe import routed_chunk
from ddstore_tpu.ops import moe_gmm

C, K = 64, 256
# sizes of the groups over C = 64 rows, in tiles of 16
GROUPINGS = {
    "mid-tile-ends": [10, 23, 7, 9],
    "rows-past-the-groups": [5, 20, 3],
    "an-empty-group": [16, 0, 30, 0],
    "every-row": [10, 22, 16, 16],
    "one-group": [64],
    "one-group-short": [37],
    "no-rows": [0, 0, 0],
}
SMALL = (16, 128, 128)      # several tiles a side, groups that straddle


def _operands(sizes, n, dtype, seed=0):
    rng = np.random.default_rng(seed)
    g = len(sizes)
    lhs = jnp.asarray(rng.normal(size=(C, K)), dtype)
    rhs = jnp.asarray(rng.normal(size=(g, K, n)) / 8, dtype)
    dout = jnp.asarray(rng.normal(size=(C, n)), dtype)
    # as the expert layer hands it over: nothing flows into rows past the
    # groups
    dout = jnp.where((jnp.arange(C) < sum(sizes))[:, None], dout, 0)
    return lhs, rhs, dout, jnp.asarray(sizes, jnp.int32)


def _rel(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


def _xla(lhs, rhs, dout, sizes):
    out, pull = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes),
                        lhs, rhs)
    return (out,) + tuple(pull(dout))


def _ours(lhs, rhs, dout, sizes, tiling):
    """(product, rows' cotangent, matrices' cotangent) through the public
    functions and their rule, or the kernels at ``tiling``."""
    if tiling is None:
        out, pull = jax.vjp(lambda a, b: moe_gmm.moe_gmm(a, b, sizes),
                            lhs, rhs)
        direct = (moe_gmm.moe_gmm(dout, rhs, sizes, transpose_rhs=True),
                  moe_gmm.moe_tgmm(lhs, dout, sizes))
        for a, b in zip(pull(dout), direct):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        return (out,) + direct
    steps = moe_gmm.row_steps(sizes, C, tiling[0])
    return (moe_gmm._gmm(lhs, rhs, steps, False, True, tiling),
            moe_gmm._gmm(dout, rhs, steps, True, True, tiling),
            moe_gmm._tgmm(lhs, dout, steps, len(sizes), lhs.dtype, True,
                          tiling))


@pytest.mark.parametrize("tiling", [None, SMALL], ids=["rule", "small-tiles"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("grouping", list(GROUPINGS))
def test_the_products_are_ragged_dots(grouping, dtype, tiling):
    sizes = GROUPINGS[grouping]
    lhs, rhs, dout, sz = _operands(sizes, 256, dtype)
    want = _xla(lhs, rhs, dout, sz)
    got = _ours(lhs, rhs, dout, sz, tiling)
    live = sum(sizes)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
    # rows past the groups read exactly zero, forward and in the rows'
    # cotangent; an empty group's block of the matrices' cotangent too
    assert not np.asarray(got[0], np.float32)[live:].any()
    assert not np.asarray(got[1], np.float32)[live:].any()
    for i, size in enumerate(sizes):
        if not size:
            assert not np.asarray(got[2][i], np.float32).any()
    if dtype == jnp.float32:
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-5)
        return
    # bfloat16: no further from the float32 product of the same (rounded)
    # operands than ragged_dot itself is
    with jax.default_matmul_precision("highest"):
        exact = _xla(*(t.astype(jnp.float32) for t in (lhs, rhs, dout)), sz)
    for g, w, e in zip(got, want, exact):
        assert _rel(g, e) <= 1.5 * _rel(w, e) + 1e-6


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_an_unaligned_width_is_padded_with_zeros(dtype):
    """N = 232 padded to 256 as ``models/moe.py`` pads the experts' width:
    zero columns of the matrices. The product's extra columns are zero and
    the cotangents, sliced back, are the unpadded product's."""
    n, sizes = 232, GROUPINGS["mid-tile-ends"]
    assert moe_gmm.padded(n) == 256 and moe_gmm.padded(256) == 256
    lhs, rhs, dout, sz = _operands(sizes, n, dtype)
    want = _xla(lhs, rhs, dout, sz)

    def ours(lhs, rhs):
        wide = jnp.pad(rhs, ((0, 0), (0, 0), (0, moe_gmm.padded(n) - n)))
        out = moe_gmm.moe_gmm(lhs, wide, sz)
        return out[:, :n], out[:, n:]

    (out, extra), pull = jax.vjp(ours, lhs, rhs)
    assert not np.asarray(extra, np.float32).any()
    got = (out,) + tuple(pull((dout, jnp.zeros_like(extra))))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert _rel(g, w) <= tol


def test_traced_sizes_inside_a_loop():
    """``sizes`` is a value of the step: the schedule is worked out on the
    device, here inside a ``lax.while_loop`` as the layer's trips are."""
    lhs, rhs, _, _ = _operands([1, 1, 1, 1], 128, jnp.float32)

    @jax.jit
    def f(first):
        def body(carry):
            i, total = carry
            sizes = jnp.stack([first + i, 2 * i, 7, 20 - i]).astype(jnp.int32)
            return i + 1, total + moe_gmm.moe_gmm(lhs, rhs, sizes)
        return jax.lax.while_loop(lambda c: c[0] < 3, body,
                                  (jnp.int32(0), jnp.zeros((C, 128))))[1]

    want = sum(jax.lax.ragged_dot(
        lhs, rhs, jnp.asarray([5 + i, 2 * i, 7, 20 - i], jnp.int32))
        for i in range(3))
    np.testing.assert_allclose(f(jnp.int32(5)), want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("tm", [8, 16, 64])
@pytest.mark.parametrize("grouping", list(GROUPINGS))
def test_the_steps_cover_every_row_once(grouping, tm):
    """Every row of every group is in exactly one step's ``[lo, hi)`` on its
    own tile, inside the static count of steps; ``moe_tgmm``'s give every
    group a step, ``moe_gmm``'s every tile."""
    sizes = GROUPINGS[grouping]
    steps = moe_gmm.row_steps(jnp.asarray(sizes, jnp.int32), C, tm)
    ends = np.cumsum(sizes)
    n = C // tm + len(sizes) - 1
    for weights, packed in ((False, steps.rows), (True, steps.weights)):
        packed = np.asarray(packed)
        fields = 4 if weights else 5
        out = list(packed[:fields * n].reshape(fields, n)) \
            + [packed[fields * n:]]
        grp, tile, lo, hi, count = out[0], out[1], out[-3], out[-2], out[-1]
        assert len(grp) == C // tm + len(sizes) - 1
        assert count[-1] <= len(grp)
        seen = np.zeros(C, int)
        for s in range(count[0]):
            assert tile[s] * tm <= lo[s] and hi[s] <= (tile[s] + 1) * tm
            assert ends[grp[s]] - sizes[grp[s]] <= lo[s] or lo[s] >= hi[s]
            assert hi[s] <= ends[grp[s]] or lo[s] >= hi[s]
            seen[lo[s]:hi[s]] += 1
        assert (seen[:sum(sizes)] == 1).all() and not seen[sum(sizes):].any()
        if weights:
            assert set(grp[:count[0]]) == set(range(len(sizes)))
            assert (np.diff(grp[:count[0]]) >= 0).all()
        else:
            out_tile = out[2]
            assert set(out_tile[:count[1]]) == set(range(C // tm))
            assert (np.diff(out_tile) >= 0).all()
            assert not (hi[count[0]:] > lo[count[0]:]).any()
        # past the last step the blocks stay where they are: nothing is
        # fetched
        last = max(count[0] - 1, 0)
        assert (grp[last:] == grp[last]).all()
        assert (tile[last:] == tile[last]).all()


def test_one_walk_serves_every_product_over_the_same_rows():
    """``steps=row_steps(sizes, C)`` handed to several products, as the
    expert layer does, gives what each works out alone."""
    lhs, rhs, dout, sz = _operands(GROUPINGS["mid-tile-ends"], 256,
                                   jnp.float32)
    steps = moe_gmm.row_steps(sz, C)
    f = lambda steps: jax.vjp(
        lambda a, b: moe_gmm.moe_gmm(a, b, sz, steps=steps), lhs, rhs)
    (out, pull), (want, want_pull) = f(steps), f(None)
    for got, w in zip((out,) + pull(dout), (want,) + want_pull(dout)):
        np.testing.assert_array_equal(got, w)
    np.testing.assert_array_equal(
        moe_gmm.moe_tgmm(lhs, dout, sz, steps=steps),
        moe_gmm.moe_tgmm(lhs, dout, sz))


# (tokens a step, top_k, held, routed experts, model width, experts' width)
CELLS = {
    "nemotron3-nano-ep16": (16384, 6, 8, 128, 2688, 1856),
    "lfm2-8b-a1b-ep4": (32768, 4, 8, 32, 2048, 1792),
    "glm47-flash-ep8": (16384, 4, 8, 64, 2048, 1536),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_tile_rule_divides_the_cells_shapes(cell):
    tokens, top_k, held, of, d, hidden = CELLS[cell]
    rows = routed_chunk(tokens, top_k, held, of)
    wide = moe_gmm.padded(hidden)
    assert wide % 128 == 0 and 0 <= wide - hidden < 128
    layout = moe_gmm.layer_tiles(rows, d, wide, jnp.bfloat16)
    assert set(layout) == {"in", "out"}
    for (k, n), forms in zip(((d, wide), (wide, d)), layout.values()):
        assert set(forms) == {"gmm", "gmm_t", "tgmm"}
        for form, (tm, tk, tn) in forms.items():
            assert (tm, tk, tn) == moe_gmm.tiles(form, rows, k, n,
                                                 jnp.bfloat16)
            assert rows % tm == 0 and 256 <= tm <= 512, (form, tm)
            assert k % tk == 0 and tk % 128 == 0, (form, tk)
            assert n % tn == 0 and tn % 128 == 0, (form, tn)


def test_shapes_that_do_not_meet_are_refused():
    lhs, rhs, dout, sz = _operands([10, 20], 128, jnp.float32)
    with pytest.raises(ValueError, match="do not meet"):
        moe_gmm.moe_gmm(lhs[:, :100], rhs, sz)
    steps = moe_gmm.row_steps(sz, C, 16)
    with pytest.raises(ValueError, match="whole tiles"):
        moe_gmm.row_steps(sz, C, 48)
    with pytest.raises(ValueError, match="are not those of"):
        moe_gmm._gmm(lhs, rhs, steps, False, True, (32, 128, 128))
    with pytest.raises(ValueError, match="do not divide"):
        moe_gmm._tgmm(lhs, dout, steps, 2, lhs.dtype, True, (16, 96, 128))
