"""Flash attention against the XLA reference on whatever backend is live.

Everything numeric in ``tests/test_attention.py`` runs the kernels in
interpret mode on the CPU; Mosaic lowering is exactly where an
interpret-correct kernel goes wrong. This check compiles and runs the real
kernels on the current backend and raises on any mismatch, so the entry
points that need the kernel to be right on the chip (``chip_smoke.py``,
``bench.py --phase numerics``) share one implementation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .attention import flash_attention, mha_reference

__all__ = ["flash_reference_check"]


def _check(name, got, want):
    # bf16 inputs/outputs with f32 accumulation: values agree to ~1e-2,
    # except isolated elements where the two summation orders round
    # through bf16 differently (single-ulp cancellation). A real lowering
    # bug mismatches broadly, so: allow <=0.01% of elements outside the
    # 3e-2 band, and bound the worst deviation hard.
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    bad = ~np.isclose(g, w, atol=3e-2, rtol=3e-2)
    frac = bad.mean() if bad.size else 0.0
    worst = float(np.abs(g - w).max()) if g.size else 0.0
    if frac > 1e-4 or worst > 0.25:
        raise AssertionError(
            f"flash != reference on {jax.default_backend()}: {name}: "
            f"{frac:.2%} elements outside tolerance, worst "
            f"|diff|={worst:.4f}")


def _check_case(s, hd, causal, qo, ko):
    kq, kk, kv = jax.random.split(jax.random.key(hd), 3)
    q = jax.random.normal(kq, (1, 4, s, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (1, 4, s, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (1, 4, s, hd), jnp.bfloat16)

    def lossf(fn):
        def f(q, k, v):
            out, _ = fn(q, k, v, causal=causal, q_offset=qo, kv_offset=ko)
            return (out.astype(jnp.float32) ** 2).sum()
        return f

    loss_f, grads_f = jax.jit(jax.value_and_grad(
        lossf(flash_attention), argnums=(0, 1, 2)))(q, k, v)
    loss_r, grads_r = jax.jit(jax.value_and_grad(
        lossf(mha_reference), argnums=(0, 1, 2)))(q, k, v)
    tag = f"S={s} hd{hd} causal={causal} off=({qo},{ko})"
    # Loss is a sum over b*h*s*hd squared outputs; compare the mean.
    _check(f"{tag} loss", loss_f / q.size, loss_r / q.size)
    for nm, gf, gr in zip("qkv", grads_f, grads_r):
        _check(f"{tag} d{nm}", gf, gr)


def flash_reference_check(s: int, s_misaligned: int = 0) -> int:
    """Assert flash == reference ON THE CURRENT BACKEND — outputs AND
    gradients, head_dim 64 and 128, at sequence length ``s``: plain,
    causal, and the two ring offset cases; then the ring's
    ``lax.cond``-of-kernels construct. ``s_misaligned`` (optional) adds
    one causal case at a length that is a multiple of 8 but not of the
    bf16 tile's 16 rows. Returns the number of cases; raises on any
    mismatch — a caller must fail loudly, not time wrong code."""
    ncases = 0
    for hd in (64, 128):
        # (causal, q_offset, kv_offset): plain, causal/diag, ring "past"
        # chunk, ring mid-offset diag.
        for causal, qo, ko in [(False, 0, 0), (True, 0, 0), (True, s, 0),
                               (True, s // 2, s // 2)]:
            _check_case(s, hd, causal, qo, ko)
            ncases += 1
    if s_misaligned:
        _check_case(s_misaligned, 64, True, 0, 0)
        ncases += 1

    # The ring three-case construct: lax.cond selecting between
    # statically-configured Pallas kernels (parallel/ring_attention.py
    # _ring_body) — compile and run every branch on this backend.
    q = jax.random.normal(jax.random.key(7), (1, 2, s, 64), jnp.bfloat16)

    @jax.jit
    def ring_cases(pred_diag, pred_past, q):
        def diag(args):
            return flash_attention(*args, causal=True)

        def past(args):
            return flash_attention(*args, causal=False)

        def masked(args):
            return (jnp.zeros(q.shape, q.dtype),
                    jnp.full(q.shape[:3], -jnp.inf, jnp.float32))

        return jax.lax.cond(
            pred_diag, diag,
            lambda a: jax.lax.cond(pred_past, past, masked, a), (q, q, q))

    for pd, pp, ref_kw in [(True, False, dict(causal=True)),
                           (False, True, dict(causal=False)),
                           (False, False, None)]:
        out, lse = ring_cases(pd, pp, q)
        if ref_kw is None:
            if np.asarray(out).any() or \
                    np.isfinite(np.asarray(lse)).any():
                raise AssertionError(
                    "ring masked branch produced nonzero output")
        else:
            want, _ = jax.jit(lambda q: mha_reference(q, q, q, **ref_kw))(q)
            _check(f"ring-cond {ref_kw}", out, want)
        ncases += 1
    return ncases
