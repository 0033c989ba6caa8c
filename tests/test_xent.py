"""Fused linear+cross-entropy oracle tests.

The fused op must match the unfused ``logits = x @ w; log_softmax`` path
— values AND gradients — across block widths (including non-dividing
vocab sizes) and through the model-level ``lm_loss`` entry point, because
the benchmark's train step routes through it at real vocab sizes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddstore_tpu.models import transformer
from ddstore_tpu.ops.xent import fused_linear_xent


def _ref_nll(x, w, targets):
    logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]


@pytest.mark.parametrize("v,block", [(64, 64), (64, 16), (100, 32),
                                     (7, 4), (128, 4096)])
def test_fused_matches_reference(v, block):
    kx, kw, kt = jax.random.split(jax.random.key(v), 3)
    n, d = 33, 16
    x = jax.random.normal(kx, (n, d), jnp.float32)
    w = jax.random.normal(kw, (d, v), jnp.float32) * 0.3
    t = jax.random.randint(kt, (n,), 0, v)
    got = fused_linear_xent(x, w, t, block)
    want = _ref_nll(x, w, t)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("v,block", [(64, 16), (100, 32)])
def test_fused_gradients(v, block):
    kx, kw, kt = jax.random.split(jax.random.key(7 * v), 3)
    n, d = 17, 8
    x = jax.random.normal(kx, (n, d), jnp.float32)
    w = jax.random.normal(kw, (d, v), jnp.float32) * 0.3
    t = jax.random.randint(kt, (n,), 0, v)

    def fused(x, w):
        return fused_linear_xent(x, w, t, block).mean()

    def ref(x, w):
        return _ref_nll(x, w, t).mean()

    gf = jax.jit(jax.grad(fused, argnums=(0, 1)))(x, w)
    gr = jax.jit(jax.grad(ref, argnums=(0, 1)))(x, w)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_extreme_logits_stable():
    """Online logsumexp must survive large-magnitude logits (the naive
    exp-sum overflows f32 at ~88)."""
    n, d, v = 5, 4, 32
    x = jnp.full((n, d), 50.0, jnp.float32)
    w = jnp.ones((d, v), jnp.float32)
    w = w.at[:, 0].set(3.0)
    t = jnp.zeros((n,), jnp.int32)
    got = fused_linear_xent(x, w, t, 8)
    want = _ref_nll(x, w, t)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_lm_loss_fused_matches_unfused():
    model = transformer.TransformerLM(vocab=100, dim=32, heads=4, layers=2,
                                      compute_dtype=jnp.float32)
    state, _ = transformer.create_train_state(jax.random.key(0), model)
    kt, kg = jax.random.split(jax.random.key(1))
    tok = jax.random.randint(kt, (2, 16), 0, 100)
    tgt = jax.random.randint(kg, (2, 16), 0, 100)
    pos = jnp.tile(jnp.arange(16), (2, 1))

    def lossf(fused):
        return lambda p: transformer.lm_loss(model, p, tok, tgt, pos,
                                             fused_xent=fused,
                                             xent_block=32)

    lf, gf = jax.value_and_grad(lossf(True))(state.params)
    lr, gr = jax.value_and_grad(lossf(False))(state.params)
    np.testing.assert_allclose(lf, lr, rtol=1e-5)
    flat_f = jax.tree_util.tree_leaves_with_path(gf)
    flat_r = dict(jax.tree_util.tree_leaves_with_path(gr))
    for path, leaf in flat_f:
        np.testing.assert_allclose(
            leaf, flat_r[path], rtol=2e-4, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


def test_lm_loss_fused_moe_aux():
    """The MoE aux term must survive the fused path unchanged."""
    model = transformer.TransformerLM(vocab=64, dim=16, heads=2, layers=1,
                                      n_experts=2,
                                      compute_dtype=jnp.float32)
    state, _ = transformer.create_train_state(jax.random.key(0), model)
    tok = jnp.zeros((2, 8), jnp.int32)
    pos = jnp.tile(jnp.arange(8), (2, 1))
    lf = transformer.lm_loss(model, state.params, tok, tok, pos,
                             fused_xent=True, xent_block=16)
    lr = transformer.lm_loss(model, state.params, tok, tok, pos,
                             fused_xent=False)
    np.testing.assert_allclose(lf, lr, rtol=1e-5)


def test_train_step_fused():
    """End-to-end: a jitted fused-head train step reduces the loss."""
    model = transformer.TransformerLM(vocab=50, dim=32, heads=4, layers=1,
                                      compute_dtype=jnp.float32)
    state, tx = transformer.create_train_state(jax.random.key(0), model,
                                               lr=1e-2)
    step = transformer.make_train_step(model, tx, fused_xent=True,
                                       donate=False)
    kt = jax.random.key(1)
    tok = jax.random.randint(kt, (4, 16), 0, 50)
    pos = jnp.tile(jnp.arange(16), (4, 1))
    losses = []
    for _ in range(10):
        state, loss = step(state, tok, tok, pos)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices")
def test_lm_loss_fused_under_dp_sp_mesh():
    """Fused head under a dp x sp mesh: the (B, S, D) -> (B*S, D) reshape
    crosses the sequence-sharded axis; GSPMD must still produce the same
    loss AND updated params as the unfused sharded path."""
    from ddstore_tpu.parallel import make_mesh

    mesh = make_mesh({"dp": 2, "sp": 4}, jax.devices()[:8])
    model = transformer.TransformerLM(vocab=128, dim=32, heads=4, layers=1,
                                      mesh=mesh,
                                      compute_dtype=jnp.float32)
    state, tx = transformer.create_train_state(jax.random.key(0), model,
                                               mesh=mesh)
    kt, kg = jax.random.split(jax.random.key(1))
    b, s = 4, 32  # s divisible by sp
    tok = jax.random.randint(kt, (b, s), 0, 128)
    tgt = jax.random.randint(kg, (b, s), 0, 128)
    pos = jnp.tile(jnp.arange(s), (b, 1))

    results = {}
    for fused in (False, True):
        step = transformer.make_train_step(model, tx, mesh=mesh,
                                           donate=False, fused_xent=fused)
        st, loss = step(state, tok, tgt, pos)
        assert np.isfinite(float(loss))
        results[fused] = (float(loss), st.params)
    np.testing.assert_allclose(results[True][0], results[False][0],
                               rtol=1e-5)
    flat_f = jax.tree_util.tree_leaves_with_path(results[True][1])
    flat_r = dict(jax.tree_util.tree_leaves_with_path(results[False][1]))
    for path, leaf in flat_f:
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flat_r[path]), rtol=5e-3,
            atol=5e-4, err_msg=jax.tree_util.keystr(path))
