"""Attention stack: Pallas flash kernel (interpret mode on CPU) vs the XLA
reference, and ring attention over the 8-device virtual mesh vs full
attention — exactness is the oracle (ring attention is algebraically exact,
not an approximation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddstore_tpu.ops.attention import (BlockDiffusion, flash_attention,
                                       mha_reference)
from ddstore_tpu.parallel import balanced_order, make_mesh, ring_attention


def _ring(q, k, v, *, mesh, causal, **kw):
    """``ring_attention`` for a natural-order caller: a causal sequence
    goes in in ``balanced_order`` and out and lse come back by its
    inverse, as the ring's contract says."""
    if not causal:
        return ring_attention(q, k, v, mesh=mesh, causal=False, **kw)
    order = balanced_order(q.shape[2], mesh.shape["sp"])
    out, lse = ring_attention(*(jnp.take(t, order, axis=2)
                                for t in (q, k, v)),
                              mesh=mesh, causal=True, **kw)
    back = np.argsort(order)
    return jnp.take(out, back, axis=2), jnp.take(lse, back, axis=2)


def _qkv(key, b=2, h=2, s=256, d=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(jax.random.key(key), 3)
    q = jax.random.normal(kq, (b, h, s, d), dtype)
    k = jax.random.normal(kk, (b, h, s, d), dtype)
    v = jax.random.normal(kv, (b, h, s, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv(0)
    out_r, lse_r = mha_reference(q, k, v, causal=causal)
    out_f, lse_f = flash_attention(q, k, v, causal=causal, block_q=64,
                                   block_k=64)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse_f), np.asarray(lse_r),
                               atol=2e-5, rtol=2e-5)


def test_flash_offsets_match_reference():
    # Offsets shift the causal frontier — the ring-step configuration.
    q, k, v = _qkv(1, s=128)
    for q_off, kv_off in [(128, 0), (0, 128), (64, 64)]:
        out_r, lse_r = mha_reference(q, k, v, causal=True, q_offset=q_off,
                                     kv_offset=kv_off)
        out_f, lse_f = flash_attention(q, k, v, causal=True, q_offset=q_off,
                                       kv_offset=kv_off, block_q=64,
                                       block_k=64)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                                   atol=2e-5, rtol=2e-5)
        # fully-masked rows (kv entirely in the future) give lse=-inf
        mask = np.isfinite(np.asarray(lse_r))
        np.testing.assert_array_equal(np.isfinite(np.asarray(lse_f)), mask)
        np.testing.assert_allclose(np.asarray(lse_f)[mask],
                                   np.asarray(lse_r)[mask], atol=2e-5,
                                   rtol=2e-5)
        assert (np.asarray(out_f)[~np.isfinite(np.asarray(lse_f))] == 0).all()


# ---------------------------------------------------------------------------
# The forward's one-pass body: scores a sub-tile at a time against a
# reference max known before them; a row group whose sum against it passes
# e**tau is taken again by the two-pass body, and lane 1 of the kernel's
# statistics counts that per row (``forward_fallbacks``).
# ---------------------------------------------------------------------------


_ROW, _COL = 200, 150      # query 200's score on key 150 is raised


@pytest.mark.parametrize("kw,tile,jump,falls_back", [
    (dict(block_q=64, block_k=256), (64, 64), 0.0, False),
    (dict(block_q=64, block_k=256), (64, 64), 60.0, True),
    (dict(block_q=64, block_k=64), (64, 64), 60.0, True),
    (dict(block_q=64, block_k=256), (64, 64), 20.0, False),
    (dict(block_q=64, block_k=256), (48, 96), 60.0, True),
    (dict(block_q=64, block_k=64, kv_offset=128), (32, 32), 60.0, False),
    (dict(block_q=8, block_k=512, s=512), (8, 64), 60.0, True)],
    ids=["ordinary", "jump-after-the-first-sub-tile", "jump-in-a-later-step",
         "jump-under-tau", "sub-tiles-that-do-not-divide-the-strips",
         "fully-masked-rows", "traced-shift"])
def test_the_one_pass_forward_and_its_fallback(small_tiles, kw, tile, jump,
                                               falls_back):
    """Out and lse equal the reference's to 2e-5 whether or not a row group
    falls back; the raised row counts its fallback in lane 1, the head
    whose scores are ordinary counts none. A raised key in the row's
    future (``kv_offset``: rows 0-127 see no key at all) raises nothing;
    under a traced shift (64 diagonal positions) the same guard holds."""
    from ddstore_tpu.ops.attention import forward_fallbacks
    small_tiles(tile)
    kw = dict(causal=True, **kw)
    s = kw.pop("s", 256)
    q, k, v = _qkv(21, b=1, h=2, s=s, d=32)
    qr = q[0, 0, _ROW]
    # scale * q . k = jump on (row, col): the rest of the row scores ~N(0, 1)
    k = k.at[0, 0, _COL].set(qr * jump * np.sqrt(32) / jnp.dot(qr, qr))
    out_r, lse_r = mha_reference(q, k, v, causal=True,
                                 kv_offset=kw.get("kv_offset", 0))
    out_f, lse_f = flash_attention(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)
    seen = np.isfinite(np.asarray(lse_r))
    np.testing.assert_array_equal(np.isfinite(np.asarray(lse_f)), seen)
    np.testing.assert_allclose(np.asarray(lse_f)[seen],
                               np.asarray(lse_r)[seen], atol=2e-5, rtol=2e-5)
    fallbacks = np.asarray(forward_fallbacks(q, k, v, **kw))
    assert fallbacks.shape == (1, 2, s)
    assert (fallbacks[0, 1] == 0).all()
    if falls_back:
        assert fallbacks[0, 0, _ROW] >= 1 and fallbacks[0, 0, :64].sum() == 0
    else:
        assert (fallbacks == 0).all()


def test_the_forward_counter_names_its_body():
    """``flash_geometry`` says per forward call which body it lowered, the
    sub-tile and tau; the backward kernel's entries name no body, and no
    dq kernel of its own has any."""
    from ddstore_tpu.ops.attention import _TAU
    from ddstore_tpu.utils import profile
    for d, tile in ((64, "512x512"), (128, "512x256"), (256, "1024x512")):
        x = jax.ShapeDtypeStruct((1, 1, 4096, d), jnp.bfloat16)
        jax.eval_shape(lambda q: flash_attention(q, q, q, causal=True), x)
        calls = profile.counters()["flash_geometry"]
        (fwd,) = [g for c, g in calls["ddstore_flash_fwd"].items()
                  if c.startswith(f"causal bh1 q4096+0 k4096+0 d{d} ")]
        assert (fwd["body"], fwd["tile"], fwd["tau"]) == ("one_pass", tile,
                                                          _TAU)
        assert all("body" not in g
                   for g in calls["ddstore_flash_dkv"].values())
        assert "ddstore_flash_dq" not in calls


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("axes", [{"sp": 8}, {"dp": 2, "sp": 4}])
def test_ring_matches_full(causal, axes):
    mesh = make_mesh(axes)
    q, k, v = _qkv(2, b=4, h=2, s=256, d=32)
    out_full, lse_full = mha_reference(q, k, v, causal=causal)

    @jax.jit
    def run(q, k, v):
        return _ring(q, k, v, mesh=mesh, causal=causal)

    out_ring, lse_ring = run(q, k, v)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_full),
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(lse_ring), np.asarray(lse_full),
                               atol=3e-5, rtol=3e-5)


def test_ring_bf16():
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv(3, b=1, h=2, s=512, d=32, dtype=jnp.bfloat16)
    out_full, _ = mha_reference(q, k, v, causal=True)
    out_ring, _ = jax.jit(lambda a, b, c: _ring(
        a, b, c, mesh=mesh, causal=True))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out_ring, np.float32), np.asarray(out_full, np.float32),
        atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_reference(causal):
    """The custom-VJP flash backward must match XLA autodiff through the
    reference (this is what TPU training differentiates through)."""
    q, k, v = _qkv(5, b=1, h=2, s=128, d=64)
    tgt = jax.random.normal(jax.random.key(9), q.shape)

    def loss_flash(q, k, v):
        out, lse = flash_attention(q, k, v, causal=causal, block_q=64,
                                   block_k=64)
        return jnp.sum((out - tgt) ** 2) + 0.1 * jnp.sum(
            jnp.where(jnp.isfinite(lse), lse, 0.0))

    def loss_ref(q, k, v):
        out, lse = mha_reference(q, k, v, causal=causal)
        return jnp.sum((out - tgt) ** 2) + 0.1 * jnp.sum(
            jnp.where(jnp.isfinite(lse), lse, 0.0))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3,
                                   rtol=2e-3)


@pytest.mark.parametrize("bwd", [(32, 256), (128, 32)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_blocks_match_reference(causal, bwd):
    """The backward's own block shapes (bwd_blocks) are numerics-neutral:
    rectangular blocks different from the forward's — exercising both the
    interior (mask-free) and diagonal-straddling kernel bodies, and query
    blocks taller or shorter than the key blocks the head's dq is summed
    over — must give the same gradients."""
    q, k, v = _qkv(6, b=1, h=2, s=256, d=64)
    tgt = jax.random.normal(jax.random.key(10), q.shape)

    def loss(fn):
        def f(q, k, v):
            out, _ = fn(q, k, v)
            return jnp.sum((out - tgt) ** 2)
        return f

    gr = jax.grad(loss(lambda q, k, v: mha_reference(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=64,
        bwd_blocks=bwd)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3,
                                   rtol=2e-3)


# The one backward kernel against jax.grad of the reference: (b, h, h_kv, s,
# d, layout, flash_attention's keywords). Grouped K/V heads of 1, 4 and 7,
# both layouts, widths 64 and 128, each kind of call the kernel is built
# for; the last cases hold several heads and several key blocks a head, so
# a dq buffer not zeroed at a head's first grid step, or written out at
# any step but its last, reads another head's or part of its own rows.
_FUSED_CASES = [
    pytest.param(1, 2, 2, 256, 64, "bhsd",
                 dict(causal=True, block_q=64, block_k=64,
                      bwd_blocks=(64, 128)), id="causal"),
    pytest.param(1, 2, 2, 256, 64, "bhsd",
                 dict(causal=True, q_offset=64, kv_offset=0, block_q=64,
                      block_k=64), id="causal-q_offset"),
    pytest.param(1, 2, 2, 256, 64, "bhsd",
                 dict(causal=True, q_offset=0, kv_offset=96, block_q=64,
                      block_k=64), id="causal-kv_offset-masked-rows"),
    pytest.param(1, 2, 2, 256, 64, "bhsd", dict(causal=True),
                 id="short-whole-call"),
    pytest.param(1, 2, 2, 256, 64, "bhsd",
                 dict(causal=True, window=40, block_q=32, block_k=64),
                 id="window"),
    pytest.param(1, 2, 1, 256, 64, "bhsd",
                 dict(mask=BlockDiffusion(4, 128), block_q=64, block_k=64),
                 id="block-diffusion"),
    pytest.param(1, 2, 2, 256, 64, "bhsd",
                 dict(causal=False, block_q=64, block_k=64), id="non-causal"),
    pytest.param(1, 4, 1, 256, 64, "bhsd",
                 dict(causal=True, block_q=64, block_k=64), id="gqa-4"),
    pytest.param(1, 7, 1, 128, 64, "bhsd",
                 dict(causal=True, block_q=32, block_k=64), id="gqa-7"),
    pytest.param(1, 7, 1, 128, 128, "bshd",
                 dict(causal=True, block_q=32, block_k=64),
                 id="gqa-7-seq-major-d128"),
    pytest.param(1, 4, 2, 256, 128, "bshd",
                 dict(causal=True, window=100, block_q=64, block_k=128),
                 id="window-seq-major-d128"),
    pytest.param(1, 2, 1, 256, 128, "bshd",
                 dict(mask=BlockDiffusion(8, 128), block_q=64, block_k=64),
                 id="block-diffusion-seq-major-d128"),
    pytest.param(1, 2, 2, 256, 128, "bhsd",
                 dict(causal=False, block_q=64, block_k=128),
                 id="non-causal-d128"),
    pytest.param(2, 3, 3, 512, 64, "bhsd",
                 dict(causal=True, block_q=64, block_k=64,
                      bwd_blocks=(64, 128)), id="heads-and-key-blocks"),
    pytest.param(2, 3, 3, 512, 64, "bhsd",
                 dict(causal=False, block_q=64, block_k=64,
                      bwd_blocks=(128, 64)),
                 id="heads-and-key-blocks-non-causal"),
]


@pytest.mark.parametrize("b,h,h_kv,s,d,layout,kw", _FUSED_CASES)
def test_fused_backward_matches_reference(b, h, h_kv, s, d, layout, kw):
    """dq, dk and dv of the one backward kernel, float32, against jax.grad
    of ``mha_reference``, the lse's cotangent included."""
    ks = jax.random.split(jax.random.key(s + d + h * 8 + h_kv), 4)
    q, w = (jax.random.normal(kk, (b, h, s, d)) for kk in ks[:2])
    k, v = (jax.random.normal(kk, (b, h_kv, s, d)) for kk in ks[2:])
    lay = (lambda t: t.transpose(0, 2, 1, 3)) if layout == "bshd" \
        else (lambda t: t)
    ref_kw = {key: kw[key] for key in ("causal", "q_offset", "kv_offset",
                                       "window", "mask") if key in kw}

    def loss(attend):
        def f(q, k, v):
            out, lse = attend(q, k, v)
            # a wholly masked row's lse is -inf: keep it out of the sum
            return (out * w).sum() + jnp.sin(
                jnp.where(jnp.isfinite(lse), lse, 0.0)).sum()
        return f

    def flash(q, k, v):
        out, lse = flash_attention(lay(q), lay(k), lay(v), layout=layout,
                                   **kw)
        return lay(out), lse

    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: mha_reference(q, k, v, **ref_kw)),
                    argnums=(0, 1, 2))(q, k, v)
    for name, g, w_ in zip(("dq", "dk", "dv"), got, want):
        assert g.shape == w_.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w_), atol=1e-4,
                                   rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("s,d,dtype", [(4096, 64, jnp.bfloat16),
                                       (1024, 128, jnp.float32)])
def test_the_backward_counter_says_dq_is_resident(s, d, dtype):
    """A traced call's backward entry in ``flash_geometry`` records that dq
    is held in VMEM for the head, the bytes of that float32 buffer (S_q d
    4) and the VMEM limit the call sets: the blocks' 32 MiB, the buffer and
    the whole-head dq block's two pipeline buffers."""
    from ddstore_tpu.utils import profile
    x = jax.ShapeDtypeStruct((1, 2, s, d), dtype)
    jax.eval_shape(jax.grad(lambda q: flash_attention(
        q, q, q, causal=True)[0].astype(jnp.float32).sum()), x)
    calls = profile.counters()["flash_geometry"]["ddstore_flash_dkv"]
    (geo,) = [g for c, g in calls.items()
              if c.startswith(f"causal bh2 q{s}+0 k{s}+0 d{d} ")]
    assert geo["dq"] == "resident"
    assert geo["dq_vmem_bytes"] == s * d * 4
    assert geo["vmem_limit"] == 32 * 2 ** 20 + s * d * 4 \
        + 2 * s * d * jnp.dtype(dtype).itemsize


def test_a_head_too_long_for_the_resident_dq_is_refused_by_name():
    """The backward holds a head's dq in VMEM: a head whose buffer would
    take the limit past the cap is refused when differentiated, with the
    ring named; its forward still runs."""
    x = jax.ShapeDtypeStruct((1, 1, 131072, 128), jnp.bfloat16)
    jax.eval_shape(lambda q: flash_attention(q, q, q, causal=True), x)
    with pytest.raises(ValueError, match="dq in VMEM.*ring_attention"):
        jax.eval_shape(jax.grad(lambda q: flash_attention(
            q, q, q, causal=True)[0].astype(jnp.float32).sum()), x)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_impl_matches_full(causal):
    """The flash-per-step ring (the TPU path, forced here so CPU tests
    run the same kernels in interpret mode) must equal full attention —
    forward and gradients (VERDICT round-1 weak #4: the ring never used
    the flash kernel)."""
    mesh = make_mesh({"sp": 4})
    q, k, v = _qkv(6, b=1, h=2, s=128, d=32)
    out_full, lse_full = mha_reference(q, k, v, causal=causal)

    run = jax.jit(lambda q, k, v: _ring(
        q, k, v, mesh=mesh, causal=causal, impl="flash"))
    out_ring, lse_ring = run(q, k, v)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_full),
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(lse_ring), np.asarray(lse_full),
                               atol=3e-5, rtol=3e-5)

    tgt = jax.random.normal(jax.random.key(11), q.shape)

    def loss(fn):
        def f(q, k, v):
            out, _ = fn(q, k, v)
            return jnp.sum((out - tgt) ** 2)
        return f

    g_ring = jax.jit(jax.grad(loss(
        lambda q, k, v: _ring(q, k, v, mesh=mesh, causal=causal,
                              impl="flash")),
        argnums=(0, 1, 2)))(q, k, v)
    g_full = jax.grad(loss(
        lambda q, k, v: mha_reference(q, k, v, causal=causal)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)


def test_ring_flash_impl_rejects_misaligned():
    mesh = make_mesh({"sp": 4})
    q, k, v = _qkv(7, b=1, h=1, s=36, d=16)  # 9-row chunks: not tile-able
    with pytest.raises(ValueError, match="flash"):
        ring_attention(q, k, v, mesh=mesh, causal=True, impl="flash")


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_ring_sp_tp_composition(impl):
    """sp×tp: ring attention over sp with heads sharded over tp inside
    the same shard_map (untested in round 1 — VERDICT next #5). Heads
    are independent, so each tp shard rings only its own H/tp heads."""
    mesh = make_mesh({"sp": 4, "tp": 2})
    q, k, v = _qkv(8, b=2, h=4, s=128, d=16)
    out_full, lse_full = mha_reference(q, k, v, causal=True)

    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = NamedSharding(mesh, P(None, "tp", "sp", None))
    qs, ks, vs = (jax.device_put(t, sh) for t in (q, k, v))

    @jax.jit
    def run(q, k, v):
        return _ring(q, k, v, mesh=mesh, causal=True, heads_axis="tp",
                     impl=impl)

    out, lse = run(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_full),
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_full),
                               atol=3e-5, rtol=3e-5)


def test_flash_default_blocks_fit_any_8_multiple():
    """Default (TPU-tuned, large) blocks are upper bounds: lengths that
    are multiples of 8 but not of the defaults must still work (the
    fitter picks the largest dividing multiple of 8), and misaligned
    lengths must fail identically on every backend."""
    from ddstore_tpu.ops.attention import _fit_block
    assert _fit_block(512, 640) == 320
    assert _fit_block(512, 160) == 160
    assert _fit_block(2048, 8192) == 2048
    assert _fit_block(512, 100) == 0
    q, k, v = _qkv(12, b=1, h=2, s=80, d=16)  # 80 % 512 != 0
    out, lse = flash_attention(q, k, v, causal=True)
    out_r, lse_r = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)
    bad = [jnp.zeros((1, 1, 100, 16))] * 3
    with pytest.raises(ValueError, match="multiples of 8"):
        flash_attention(*bad)


def test_ring_single_axis_mesh_fallback():
    mesh = make_mesh({"sp": 1}, jax.devices()[:1])
    q, k, v = _qkv(4, s=64, d=16)
    out, lse = ring_attention(q, k, v, mesh=mesh, causal=True)
    out_r, lse_r = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r), atol=1e-6)


def test_the_three_kernels_carry_their_names():
    """A device trace names a Mosaic call after ``pallas_call(name=)``: the
    gradient of flash attention holds exactly the two named kernels, the
    forward and the one backward (``ddstore_flash_dkv``, which writes dq
    too); no dq kernel of its own."""
    q, k, v = _qkv(7, s=64)

    def loss(q, k, v):
        out, _ = flash_attention(q, k, v, causal=True, block_q=32,
                                 block_k=32)
        return out.sum()

    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr)
    assert sorted(names) == ["ddstore_flash_dkv", "ddstore_flash_fwd"]


# ---------------------------------------------------------------------------
# The causal geometry (PR 26): dead grid steps fetch nothing, a block that
# straddles the diagonal is computed by sub-tiles.
# ---------------------------------------------------------------------------


def _pallas_calls(fn, *args):
    """(name, grid, primitives of the kernel body, primitives of the index
    maps) of every pallas_call in ``fn``'s jaxpr."""
    found = []

    def prims(jaxpr, into):
        for e in jaxpr.eqns:
            into.add(e.primitive.name)
            for sub in jax.core.jaxprs_in_params(e.params):
                prims(sub, into)
        return into

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                maps = set()
                for bm in gm.block_mappings:
                    prims(bm.index_map_jaxpr.jaxpr, maps)
                found.append((eqn.params["name"], tuple(gm.grid),
                              prims(eqn.params["jaxpr"], set()), maps))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


# sq, sk, q_offset, kv_offset, (block_q, block_k), bwd_blocks. The backward
# cuts a 384-row query block into three lane-high tiles under each strip;
# the forward and the backward strip at 512, which the 1536-row blocks of
# the last case give three of.
_GEOMETRY_CASES = [
    pytest.param(768, 768, 0, 0, (384, 384), None, id="square"),
    pytest.param(768, 1536, 0, 0, (384, 768), None, id="sq<sk"),
    pytest.param(1536, 768, 0, 0, (768, 384), None, id="sq>sk"),
    pytest.param(768, 768, 128, 0, (384, 384), None, id="q_offset"),
    pytest.param(768, 768, 0, 384, (384, 384), None, id="partly-masked-q"),
    pytest.param(768, 768, 0, 1024, (384, 384), None, id="fully-masked-q"),
    pytest.param(768, 768, 64, 200, (384, 768), (768, 384),
                 id="unaligned-offsets"),
    pytest.param(768, 768, 0, 0, (384, 384), (32, 256),
                 id="bwd_blocks"),
    pytest.param(3072, 3072, 0, 0, (1536, 1536), None, id="three-strips"),
]


@pytest.mark.parametrize("sq,sk,q_off,kv_off,blocks,bwd", _GEOMETRY_CASES)
def test_causal_geometry_matches_reference(sq, sk, q_off, kv_off, blocks,
                                           bwd):
    """Causal forward and gradients against the reference where the new
    geometry is entered: at least two blocks of at least three sub-tiles
    each, rectangular calls, offsets that mask a q range wholly or partly
    (out = 0, lse = -inf, no NaN, forward and backward)."""
    from ddstore_tpu.ops.attention import _LANES, _STRIP, _sub_tile
    bq, bk = blocks
    # Two q blocks at least, of three lane-high tiles at least under the
    # backward's strips (three strips in both kernels in the case with
    # 1536-row blocks).
    strips = {name[14:]: (bk if name.endswith("dkv") else bq) // _sub_tile(
        bk if name.endswith("dkv") else bq, want)
        for name, want in _STRIP.items()}
    assert sq // bq >= 2 and bq // _sub_tile(bq, _LANES) >= 3, strips
    assert bq < 1536 or min(strips.values()) >= 3, strips
    kq, kk, kv, kt = jax.random.split(jax.random.key(sq + sk + q_off), 4)
    q = jax.random.normal(kq, (1, 2, sq, 32))
    k = jax.random.normal(kk, (1, 2, sk, 32))
    v = jax.random.normal(kv, (1, 2, sk, 32))
    tgt = jax.random.normal(kt, q.shape)
    kw = dict(causal=True, q_offset=q_off, kv_offset=kv_off)

    def flash(q, k, v):
        return flash_attention(q, k, v, block_q=bq, block_k=bk,
                               bwd_blocks=bwd, **kw)

    def ref(q, k, v):
        return mha_reference(q, k, v, **kw)

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum((out - tgt) ** 2) + 0.1 * jnp.sum(
                jnp.where(jnp.isfinite(lse), lse, 0.0))
        return f

    out_f, lse_f = flash(q, k, v)
    out_r, lse_r = ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)
    seen = np.isfinite(np.asarray(lse_r))
    np.testing.assert_array_equal(np.isfinite(np.asarray(lse_f)), seen)
    np.testing.assert_allclose(np.asarray(lse_f)[seen],
                               np.asarray(lse_r)[seen], atol=2e-5, rtol=2e-5)
    assert (np.asarray(out_f)[~seen] == 0).all()
    assert (np.asarray(lse_f)[~seen] == -np.inf).all()
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3,
                                   rtol=2e-3)


def test_many_diagonal_positions_fall_back_to_a_traced_shift():
    """8-row blocks against 512-wide ones meet the diagonal at 64 different
    positions: more static bodies than the kernels keep, so they mask whole
    blocks by a traced shift, and the counter says what that computes."""
    from ddstore_tpu.ops.attention import causal_geometry
    q, k, v = _qkv(13, b=1, h=1, s=512, d=16)
    geo = causal_geometry(512, 512, (8, 512), (8, 128))
    assert geo.pairs_computed == 512 * 512 and geo.grid_steps == 64

    def loss(fn, **kw):
        return lambda q, k, v: (fn(q, k, v, causal=True, **kw)[0] ** 2).sum()

    gf = jax.grad(loss(flash_attention, block_q=8, block_k=512),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3,
                                   rtol=2e-3)


def test_noncausal_kernels_keep_their_structure():
    """``causal=False`` (the ring's calls at every step but its first)
    lowers to what it always was: the full (bh, outer, inner) grid,
    identity index maps, one body and no loop. The causal call of the
    same shape enumerates its three live steps and reads its block indices
    from the step tables."""
    q = jnp.zeros((1, 2, 768, 32))

    def grad_of(causal):
        def f(q, k, v):
            out, _ = flash_attention(q, k, v, causal=causal, block_q=384,
                                     block_k=384)
            return out.sum()
        return jax.grad(f, argnums=(0, 1, 2))

    names = ["ddstore_flash_dkv", "ddstore_flash_fwd"]
    full = _pallas_calls(grad_of(False), q, q, q)
    assert sorted(c[0] for c in full) == names
    for name, grid, body, maps in full:
        assert grid == (2, 2, 2), (name, grid)
        assert not body & {"while", "scan"}, (name, body)
        assert not maps, (name, maps)
    causal = _pallas_calls(grad_of(True), q, q, q)
    assert sorted(c[0] for c in causal) == names
    for name, grid, body, maps in causal:
        assert grid == (2, 3), (name, grid)
        assert not body & {"while", "scan"}, (name, body)
        assert maps == {"get"}, (name, maps)


@pytest.mark.parametrize("s,bound", [(2048, 1.35), (8192, 1.10),
                                     (16384, 1.10)])
@pytest.mark.parametrize("kernel", ["fwd", "dkv"])
def test_causal_geometry_counts(s, bound, kernel):
    """The counter as arithmetic, at the geometry ``flash_attention``
    derives for a (1, 1, s, 64) causal call with default blocks: what is
    computed beyond the needed pairs stays inside the bound (2.0 at 2048
    and 1.125 at 8192 before sub-tiles), no DMA serves only dead steps,
    and the needed pairs are the s(s+1)/2 the benchmark's rooflines
    count (``benchmarks/ddbench/flops.py``)."""
    from ddstore_tpu.utils import profile
    x = jax.ShapeDtypeStruct((1, 1, s, 64), jnp.bfloat16)
    jax.eval_shape(lambda q: flash_attention(q, q, q, causal=True), x)
    calls = profile.counters()["flash_geometry"]["ddstore_flash_" + kernel]
    (geo,) = [g for call, g in calls.items()
              if call.startswith(f"causal bh1 q{s}+0 k{s}+0 d64 ")]
    assert geo["pairs_needed"] == s * (s + 1) // 2
    assert geo["pairs_computed"] / geo["pairs_needed"] <= bound
    assert geo["steps_fetching_dead"] == 0
    assert geo["grid_steps"] >= 1


def test_causal_geometry_is_what_the_old_geometry_was_not():
    """``causal_geometry`` with a sub-tile equal to the block is the
    block-wise kernel: 2.0 of the needed pairs at S=2048 under 512x2048
    blocks, 1.125 at S=8192 under 1024x1024. Offsets count only visible
    pairs; a wholly masked call needs and computes nothing; the ``q``
    stream (dkv) covers the same tiles as the ``k`` stream."""
    from ddstore_tpu.ops.attention import causal_geometry
    old = causal_geometry(2048, 2048, (512, 2048), (512, 2048))
    assert old.pairs_computed == 4 * 512 * 2048
    assert old.pairs_computed / old.pairs_needed > 1.99
    new = causal_geometry(2048, 2048, (512, 2048), (256, 256))
    assert new.pairs_computed == 36 * 256 * 256 and new.grid_steps == 4
    old8 = causal_geometry(8192, 8192, (1024, 1024), (1024, 1024))
    assert old8.pairs_computed == 36 * 1024 * 1024
    assert (old8.grid_steps, old8.steps_fetching_dead) == (36, 0)
    for stream in "kq":
        ring = causal_geometry(128, 128, (64, 64), (64, 64), 128, 0, stream)
        assert ring.pairs_needed == ring.pairs_computed == 128 * 128
        dead = causal_geometry(128, 128, (64, 64), (64, 64), 0, 128, stream)
        assert dead.pairs_needed == dead.pairs_computed == 0
        half = causal_geometry(256, 256, (128, 128), (128, 128), 0, 128,
                               stream)
        assert half.pairs_needed == 128 * 129 // 2
        assert half.pairs_computed == 128 * 128


# ---------------------------------------------------------------------------
# Sequence-major operands: a head is a column block of (B, S, H D).
# ---------------------------------------------------------------------------

def _both_layouts(d, h_kv, causal, offsets, blocks, h=2, b=2, s=128):
    """``(out, lse, dq, dk, dv)`` of the same call head-major and
    sequence-major, the latter's head tensors transposed back."""
    ks = jax.random.split(jax.random.key(d + h_kv), 5)
    q, do = (jax.random.normal(kk, (b, h, s, d), jnp.float32)
             for kk in ks[:2])
    k, v = (jax.random.normal(kk, (b, h_kv, s, d), jnp.float32)
            for kk in ks[2:4])
    dlse = jax.random.normal(ks[4], (b, h, s), jnp.float32)
    swap = lambda t: t.transpose(0, 2, 1, 3)

    def run(layout):
        lay = swap if layout == "bshd" else (lambda t: t)

        def f(q, k, v):
            out, lse = flash_attention(
                lay(q), lay(k), lay(v), causal=causal, q_offset=offsets[0],
                kv_offset=offsets[1], block_q=blocks[0], block_k=blocks[1],
                layout=layout)
            out = lay(out)
            # a wholly masked row's lse is -inf: keep it out of the sum
            kept = jnp.where(jnp.isfinite(lse), lse, 0.0)
            return (out * do).sum() + (kept * dlse).sum(), (out, lse)

        (_, (out, lse)), grads = jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out, lse) + grads

    return run("bhsd"), run("bshd")


def _assert_bit_equal(head_major, seq_major):
    for name, want, got in zip(("out", "lse", "dq", "dk", "dv"), head_major,
                               seq_major):
        assert got.shape == want.shape, name
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)


@pytest.mark.parametrize("blocks", [(64, 128), (128, 64)])
@pytest.mark.parametrize("d", [64, 128, 256])
@pytest.mark.parametrize("causal,offsets", [(False, (0, 0)), (True, (0, 0)),
                                            (True, (64, 0))],
                         ids=["full", "causal", "causal-q64"])
def test_sequence_major_call_equals_head_major(causal, offsets, d, blocks):
    """The same three kernels addressed through ``(B, S, H D)``: ``out``,
    ``lse`` (B, H, S in both) and the three gradients are the head-major
    call's, bit for bit, since a kernel body sees the same tiles. Width 64
    is no block of (B, S, H 64): refused by name (the model's ``_attend``
    sends such heads head-major: ``tests/test_profile.py``)."""
    if d % 128:
        with pytest.raises(ValueError, match="head width 64.*whole lanes"):
            _both_layouts(d, 2, causal, offsets, blocks)
        return
    _assert_bit_equal(*_both_layouts(d, 2, causal, offsets, blocks))


@pytest.mark.parametrize("causal", [False, True])
def test_sequence_major_call_carries_grouped_kv(causal):
    """Two query heads on one K/V head of 128: the K/V column block is
    ``(bh % H) // group``, and dk, dv are summed over the group as
    head-major; against that call, bit for bit."""
    _assert_bit_equal(*_both_layouts(128, 1, causal, (0, 0), (64, 64)))


def test_flash_refuses_a_layout_it_does_not_know():
    q = jnp.zeros((1, 128, 2, 128), jnp.float32)
    with pytest.raises(ValueError, match="layout 'sbhd'"):
        flash_attention(q, q, q, causal=True, layout="sbhd")
