"""``ops/moe_combine.py`` in interpreter mode against the gather-select-sum
the expert layer ran before it (PR 37's ``_pairs_rows``): the four
configurations' routings at a few dozen tokens, trips with pairs before and
past them, a layer's rows in two trips, unit weights, a trip with no live
row and a layer that holds every expert; and the names the step carries."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddstore_tpu.models.moe import SharedRoutedMoe, routed_chunk
from ddstore_tpu.ops import moe_combine
from ddstore_tpu.utils import profile


def _pairs_rows(rows, rank, live):
    """PR 37's form: ``rows[rank]`` choice first, the pairs outside the
    first ``live`` rows selected away."""
    rank = rank.T
    picked = rows.at[jnp.clip(rank, 0, len(rows) - 1)].get(
        mode="promise_in_bounds")
    return jnp.where(((rank >= 0) & (rank < live))[..., None], picked, 0)


def _gathered(rows, rank, live, weights):
    picked = _pairs_rows(rows, rank, live).astype(jnp.float32)
    if weights is not None:
        picked = picked * weights.T[..., None]
    return picked.sum(axis=0)


def _routing(t, k, held, of, seed):
    """A routing of ``t`` tokens to ``k`` distinct of ``of`` experts, this
    chip holding the first ``held``: ``(rank (T, k), sizes (held,))`` as the
    expert layer sorts its pairs."""
    rng = np.random.default_rng(seed)
    chosen = np.argsort(rng.random((t, of)), axis=1)[:, :k]
    flat = chosen.reshape(-1)
    local = flat < held
    order = np.argsort(np.where(local, flat, held), kind="stable")
    rank = np.argsort(order).reshape(t, k)
    return rank, np.bincount(flat[local], minlength=held)


def _trip(sizes, start, rows):
    """Each group's rows inside ``[start, start + rows)``."""
    ends = np.cumsum(sizes)
    inside = lambda r: np.clip(r, start, start + rows)
    return inside(ends) - inside(ends - sizes)


# (k, d, held, of): sdar, lfm2, nemotron (a width of 21 lane tiles, here
# three), glm47, and a layer that holds every expert (share (0, 1))
CONFIGS = {"sdar": (8, 256, 16, 128), "lfm2": (4, 256, 8, 32),
           "nemotron3": (6, 384, 8, 128), "glm47": (4, 256, 8, 64),
           "every-expert": (4, 128, 8, 8)}


@pytest.mark.parametrize("case", [
    ("sdar", "weighted", "whole"), ("lfm2", "weighted", "whole"),
    ("nemotron3", "weighted", "whole"), ("glm47", "weighted", "whole"),
    ("every-expert", "weighted", "whole"), ("sdar", "unit", "whole"),
    ("nemotron3", "unit", "whole"), ("glm47", "weighted", "past"),
    ("sdar", "weighted", "before-and-past"), ("lfm2", "unit", "two-trips"),
    ("lfm2", "weighted", "two-trips"), ("glm47", "weighted", "none-live"),
    ("every-expert", "unit", "two-trips")],
    ids="-".join)
def test_the_kernel_adds_what_the_gather_added(case):
    """Output against PR 37's form (float32 sums of bfloat16 rows times
    float32 weights: the order of a token's terms is all that may differ),
    and in the rows' own type where the caller asks for it."""
    name, weights, trips = case
    k, d, held, of = CONFIGS[name]
    t = 48 if name == "nemotron3" else 64
    rank, sizes = _routing(t, k, held, of, seed=len(name) + len(trips))
    live = int(sizes.sum())
    rows = routed_chunk(t, k, held, of)
    starts = {"whole": [0],
              # a trip that ends before the last live row, and one that
              # starts after the first: pairs on both sides of it
              "past": [0], "before-and-past": [live // 3],
              "two-trips": [0, -(-live // 2)], "none-live": [live]}[trips]
    if trips == "past":
        rows = live // 2
    elif trips == "two-trips":
        rows = starts[1]
    rng = np.random.default_rng(7)
    w = jnp.asarray(rng.random((t, k)), jnp.float32) \
        if weights == "weighted" else None
    for start in starts:
        here = _trip(sizes, start, rows)
        ys = jnp.asarray(rng.normal(size=(rows, d)), jnp.bfloat16)
        local = jnp.asarray(rank - start, jnp.int32)
        part = moe_combine.moe_combine(ys, local, jnp.asarray(here), w)
        assert part.dtype == jnp.float32 and part.shape == (t, d)
        ref = _gathered(ys, local, int(here.sum()), w)
        np.testing.assert_allclose(part, ref, rtol=1e-6, atol=1e-6)
        cast = moe_combine.moe_combine(ys, local, jnp.asarray(here), w,
                                       dtype=jnp.bfloat16)
        assert cast.dtype == jnp.bfloat16
        np.testing.assert_array_equal(cast, part.astype(jnp.bfloat16))
        if trips == "none-live":
            assert not np.asarray(part).any()
    if trips == "two-trips":
        # every live pair once, over the two trips
        whole = jnp.asarray(rng.normal(size=(live, d)), jnp.bfloat16)
        halves = sum(moe_combine.moe_combine(
            whole[start:start + rows], jnp.asarray(rank - start, jnp.int32),
            jnp.asarray(_trip(sizes, start, rows)), w) for start in starts)
        np.testing.assert_allclose(
            halves, _gathered(whole, jnp.asarray(rank), live, w), rtol=1e-6,
            atol=1e-6)


def test_the_tiles_and_the_stage_at_the_cells_shapes():
    """The rule's tiles at the four configurations' widths (three products
    a block with weights, one without), and the stages they need: a trip's
    every pair live in the worst tile, inside the budget."""
    for t, k, g, d in ((16384, 8, 16, 2048), (32768, 4, 8, 2048),
                       (16384, 6, 8, 2688), (16384, 4, 8, 2048)):
        assert moe_combine.tile(t, k, g, d, jnp.bfloat16, 3) == 128
        assert moe_combine.tile(t, k, g, d, jnp.bfloat16, 1) == 256
    assert moe_combine.tile(48, 6, 8, 384, jnp.bfloat16, 3) == 16
    assert moe_combine.tile(20, 4, 8, 128, jnp.bfloat16, 1) == 20
    rows = moe_combine._stage_rows(128, 8, 16)
    assert rows % 128 == 0 and rows >= 128 * 8 + 2 * 16 * 8


def test_the_layer_names_its_way_back():
    """An expert layer counts its way back as the kernel, and the kernel is
    in its traced step under the name the benchmark reads."""
    layer = SharedRoutedMoe(8, 2, 64, share=(0, 2))
    x = jnp.zeros((32, 128), jnp.float32)
    p = jax.eval_shape(layer.init, jax.random.key(0), x)

    def loss(p, x):
        y, _ = layer.apply(p, x)
        return (y ** 2).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(p, x))
    assert profile.counters()["moe_layout"][""]["combine"] == "pallas"
    assert moe_combine.COMBINE == "pallas"
    # the way back, its replay in ``_routed_bwd`` (dead there: the compiled
    # step drops it) and the way there's transpose, with unit weights
    assert text.count("name=ddstore_moe_combine") == 3
    assert "ddstore_moe_combine" in profile.STEP_SCOPES


def test_the_dense_step_runs_no_expert_kernel():
    """The dense family's train step, compiled: no way back and no grouped
    product in it (its three cells' programs are the parent's)."""
    from ddstore_tpu.models import transformer

    model = transformer.TransformerLM(vocab=64, dim=32, heads=4, layers=2,
                                      compute_dtype=jnp.float32)
    state, tx = transformer.create_train_state(jax.random.key(0), model)
    tok = jnp.zeros((2, 16), jnp.int32)
    pos = jnp.tile(jnp.arange(16, dtype=jnp.int32), (2, 1))
    text = transformer.make_train_step(model, tx).lower(
        state, tok, tok, pos).compile().as_text()
    assert "dense_mlp" in text
    assert "ddstore_moe_combine" not in text and "ddstore_moe" not in text
