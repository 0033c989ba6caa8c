"""The causal ring's balanced sequence layout (PR 28): the order, the
ring's static calls against full causal attention, what the counter
says every ring position computes, what a traced ring holds, and the model
that lays its own inputs out (losses, gradients and per-position outputs
against the same parameters without a mesh, in natural order)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddstore_tpu.models import transformer
from ddstore_tpu.ops.attention import mha_reference
from ddstore_tpu.parallel import balanced_order, make_mesh, ring_attention
from ddstore_tpu.utils import profile


@pytest.mark.parametrize("n", [1, 2, 4, 8])
def test_balanced_order_is_the_stated_permutation(n):
    s = 16 * n
    c = s // (2 * n)
    order = balanced_order(s, n)
    assert order.dtype == np.int32 and order.shape == (s,)
    assert sorted(order.tolist()) == list(range(s))
    for i, chunk in enumerate(order.reshape(n, 2 * c)):
        late = 2 * n - 1 - i
        assert chunk[:c].tolist() == list(range(i * c, (i + 1) * c))
        assert chunk[c:].tolist() == list(range(late * c, (late + 1) * c))
    x = np.arange(s) * 3 + 1
    np.testing.assert_array_equal(x[order][np.argsort(order)], x)
    if n == 1:
        np.testing.assert_array_equal(order, np.arange(s))


def test_balanced_order_needs_equal_stripes():
    with pytest.raises(ValueError, match="stripes"):
        balanced_order(20, 4)
    mesh = make_mesh({"sp": 4})
    x = jnp.zeros((1, 1, 36, 16))   # chunks of 9: no two equal stripes
    with pytest.raises(ValueError, match="stripes"):
        ring_attention(x, x, x, mesh=mesh, causal=True, impl="xla")


def _qkv(key, b, h, s, d):
    ks = jax.random.split(jax.random.key(key), 4)
    return tuple(jax.random.normal(k, (b, h, s, d)) for k in ks)


def _balanced(mesh, impl):
    """Natural order in, natural order out, the ring between."""
    def f(q, k, v):
        order = balanced_order(q.shape[2], mesh.shape["sp"])
        out, lse = ring_attention(
            *(jnp.take(t, order, axis=2) for t in (q, k, v)), mesh=mesh,
            causal=True, impl=impl)
        back = np.argsort(order)
        return jnp.take(out, back, axis=2), jnp.take(lse, back, axis=2)
    return f


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_balanced_ring_equals_full_causal_attention(impl, n):
    """``out``, ``lse`` and the gradients of q, k and v, through the XLA
    cases and through the (interpreted) kernels the chip runs."""
    mesh = make_mesh({"sp": n}, jax.devices()[:n])
    q, k, v, tgt = _qkv(20 + n, 2, 2, 32 * n, 16)

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum((out - tgt) ** 2) + 0.1 * jnp.sum(lse)
        return f

    full = lambda q, k, v: mha_reference(q, k, v, causal=True)
    ring = _balanced(mesh, impl)
    out, lse = jax.jit(ring)(q, k, v)
    out_f, lse_f = full(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_f),
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_f),
                               atol=3e-5, rtol=3e-5)
    got = jax.jit(jax.grad(loss(ring), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(loss(full), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-4)


@pytest.mark.parametrize("n", [2, 4, 8])
def test_ring_geometry_says_no_position_waits(n):
    """Every position needs, and its calls compute, the same pairs; summed
    they are the b x h x S(S+1)/2 of whole-sequence causal attention."""
    mesh = make_mesh({"sp": n}, jax.devices()[:n])
    b, h, s, d = 2, 3, 48 * n, 8
    x = jax.ShapeDtypeStruct((b, h, s, d), jnp.float32)
    jax.eval_shape(lambda q: ring_attention(q, q, q, mesh=mesh, causal=True,
                                            impl="xla"), x)
    geo = profile.counters()["ring_geometry"][
        f"causal bh{b * h} s{s} d{d} n{n}"]
    assert (geo["n"], geo["chunk_rows"], geo["order"]) == (
        n, s // n, "balanced")
    assert geo["max_over_mean"] == 1.0
    assert len(geo["pairs_needed"]) == n
    assert sum(geo["pairs_needed"]) == b * h * s * (s + 1) // 2
    assert geo["pairs_computed"] == geo["pairs_needed"]
    assert len(set(geo["pairs_needed"])) == 1
    # without a mask every position takes whole rectangles
    jax.eval_shape(lambda q: ring_attention(q, q, q, mesh=mesh, impl="xla"),
                   x)
    full = profile.counters()["ring_geometry"][
        f"full bh{b * h} s{s} d{d} n{n}"]
    assert full["max_over_mean"] == 1.0
    assert sum(full["pairs_computed"]) == b * h * s * s


def _primitives(jaxpr, into):
    """Names of the kernels called and of every other primitive, nested
    jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            into.append(eqn.params["name"])
        else:
            into.append(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _primitives(sub, into)
    return into


@pytest.mark.parametrize("n", [2, 4])
def test_the_traced_ring_holds_one_kernel_a_step_and_no_cond(n):
    """A layer's ring traces 1 + (n - 1) flash calls a pass (the parent
    traced two in every one of its n steps, inside nested ``cond``s), and
    nothing conditional: the first step is the plain causal call, every
    later one a single unmasked call."""
    mesh = make_mesh({"sp": n}, jax.devices()[:n])
    q = jnp.zeros((1, 2, 32 * n, 16))

    def fwd(q, k, v):
        return ring_attention(q, k, v, mesh=mesh, causal=True, impl="flash")

    def loss(q, k, v):
        return fwd(q, k, v)[0].sum()

    seen = _primitives(jax.make_jaxpr(fwd)(q, q, q).jaxpr, [])
    assert seen.count("ddstore_flash_fwd") == n
    assert "cond" not in seen
    assert seen.count("ppermute") == 2 * (n - 1)
    grad = _primitives(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1, 2)))(q, q, q).jaxpr, [])
    for kernel in ("ddstore_flash_fwd", "ddstore_flash_dkv"):
        assert grad.count(kernel) == n, (kernel, grad.count(kernel))
    assert "ddstore_flash_dq" not in grad       # dq: the backward's too
    assert "cond" not in grad
    # the call shapes: the local chunk causally, then stacked stripe pairs
    c = 16
    calls = profile.counters()["flash_geometry"]["ddstore_flash_fwd"]
    assert any(k.startswith(f"causal bh2 q{2 * c}+0 k{2 * c}+0 d16 ")
               for k in calls)
    assert any(k.startswith(f"full bh4 q{c}+0 k{c}+0 d16 ") for k in calls)


# ---------------------------------------------------------------------------
# The model lays its own inputs out.
# ---------------------------------------------------------------------------

_LM = dict(vocab=64, dim=32, heads=4, layers=2, compute_dtype=jnp.float32)


def _batch(b, s, vocab=64):
    k1, k2 = jax.random.split(jax.random.key(7))
    return (jax.random.randint(k1, (b, s), 0, vocab, jnp.int32),
            jax.random.randint(k2, (b, s), 0, vocab, jnp.int32),
            jnp.tile(jnp.arange(s, dtype=jnp.int32), (b, 1)))


@pytest.mark.parametrize("axes", [{"dp": 2, "sp": 2}, {"sp": 4}],
                         ids=["dp2xsp2", "sp4"])
def test_lm_loss_on_an_sp_mesh_equals_the_natural_order_loss(axes):
    """``lm_loss`` and its gradients on the mesh, natural-order batch in,
    against the same parameters with ``mesh=None``."""
    n_dev = int(np.prod(list(axes.values())))
    mesh = make_mesh(axes, jax.devices()[:n_dev])
    model = transformer.TransformerLM(mesh=mesh, **_LM)
    plain = transformer.TransformerLM(**_LM)
    tok, tgt, pos = _batch(2, 64)
    params = plain.init(jax.random.key(0), tok, pos)

    def vg(m):
        return jax.jit(jax.value_and_grad(
            lambda p: transformer.lm_loss(m, p, tok, tgt, pos)))(params)

    (loss, grads), (want, want_grads) = vg(model), vg(plain)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("features", [False, True], ids=["logits", "feats"])
def test_apply_on_an_sp_mesh_returns_natural_order(features):
    """Per-position outputs come back in the caller's order; a caller that
    says its inputs are laid out gets them back laid out."""
    mesh = make_mesh({"dp": 2, "sp": 2}, jax.devices()[:4])
    model = transformer.TransformerLM(mesh=mesh, **_LM)
    plain = transformer.TransformerLM(**_LM)
    tok, _, pos = _batch(2, 64)
    params = plain.init(jax.random.key(1), tok, pos)
    want = plain.apply(params, tok, pos, features)
    got = jax.jit(lambda p: model.apply(p, tok, pos, features))(params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    order = balanced_order(64, 2)
    laid = jax.jit(lambda p: model.apply(
        p, tok[:, order], pos[:, order], features, ring_ordered=True))(
            params)
    np.testing.assert_allclose(np.asarray(laid),
                               np.asarray(want)[:, order], atol=2e-5)


def test_a_wrong_layout_is_a_wrong_loss():
    """What the benchmark's ``correct`` leans on: positions that do not
    follow their tokens, or a sequence left contiguous on the ring, do not
    give the natural-order loss."""
    mesh = make_mesh({"sp": 2}, jax.devices()[:2])
    model = transformer.TransformerLM(mesh=mesh, **_LM)
    plain = transformer.TransformerLM(**_LM)
    tok, tgt, pos = _batch(2, 64)
    params = plain.init(jax.random.key(2), tok, pos)
    want = float(transformer.lm_loss(plain, params, tok, tgt, pos))
    good = float(jax.jit(lambda p: transformer.lm_loss(
        model, p, tok, tgt, pos))(params))
    assert abs(good - want) < 1e-5 * want

    def contiguous(p):   # natural order handed to the ring as if laid out
        out = model.apply(p, tok, pos, ring_ordered=True)
        return transformer.loss_fn(out, tgt)

    assert abs(float(jax.jit(contiguous)(params)) - want) > 1e-4 * want


def test_pp_sp_step_lays_its_inputs_out_like_lm_loss():
    """The pipelined losses do not pass through ``lm_loss``: they take the
    same helper, and match the unsharded loss and gradients."""
    from ddstore_tpu.models.transformer import lm_from_stages, lm_to_stages
    mesh = make_mesh({"pp": 2, "sp": 2}, jax.devices()[:4])
    model = transformer.TransformerLM(mesh=mesh, **_LM)
    plain = transformer.TransformerLM(**_LM)
    tok, tgt, pos = _batch(4, 32)
    params = plain.init(jax.random.key(3), tok, pos)
    outer, stages = lm_to_stages(params, 2, 2)
    stage_fn = transformer._make_stage_fn(model, 2, mesh=mesh)
    want, want_grads = jax.value_and_grad(lambda p: transformer.loss_fn(
        plain.apply(p, tok, pos), tgt))(params)
    for vg in (transformer.pp_gpipe_value_and_grad,
               transformer.pp_1f1b_value_and_grad):
        loss, (g_o, g_st) = jax.jit(lambda pp: vg(
            model, stage_fn, pp, tok, tgt, pos, n_microbatches=2,
            mesh=mesh))((outer, stages))
        np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(lm_from_stages(g_o, g_st, 2, 2)),
                        jax.tree.leaves(want_grads)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)
