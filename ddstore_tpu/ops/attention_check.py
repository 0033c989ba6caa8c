"""Flash attention against the XLA reference on whatever backend is live.

Everything numeric in ``tests/test_attention.py`` runs the kernels in
interpret mode on the CPU; Mosaic lowering is exactly where an
interpret-correct kernel goes wrong. This check compiles and runs the real
kernels on the current backend and raises on any mismatch: ``chip_smoke.py``'s
kernel leg is the entry point that needs the kernel to be right on the chip.
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


def _check_case(s, hd, causal, qo, ko, kv_heads=4, layout="bhsd"):
    kq, kk, kv = jax.random.split(jax.random.key(hd), 3)
    q = jax.random.normal(kq, (1, 4, s, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (1, kv_heads, s, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (1, kv_heads, s, hd), jnp.bfloat16)
    # the kernels take the heads as ``layout`` says, the reference (B, H,
    # S, D): both are differentiated with respect to the latter
    lay = (lambda t: t.transpose(0, 2, 1, 3)) if layout == "bshd" \
        else (lambda t: t)

    def lossf(fn, lay, **kw):
        def f(q, k, v):
            out, _ = fn(lay(q), lay(k), lay(v), causal=causal, q_offset=qo,
                        kv_offset=ko, **kw)
            return (out.astype(jnp.float32) ** 2).sum()
        return f

    loss_f, grads_f = jax.jit(jax.value_and_grad(
        lossf(flash_attention, lay, layout=layout),
        argnums=(0, 1, 2)))(q, k, v)
    loss_r, grads_r = jax.jit(jax.value_and_grad(
        lossf(mha_reference, lambda t: t), argnums=(0, 1, 2)))(q, k, v)
    tag = f"S={s} hd{hd} {layout} kv{kv_heads} causal={causal} " \
          f"off=({qo},{ko})"
    # Loss is a sum over b*h*s*hd squared outputs; compare the mean.
    _check(f"{tag} loss", loss_f / q.size, loss_r / q.size)
    for nm, gf, gr in zip("qkv", grads_f, grads_r):
        _check(f"{tag} d{nm}", gf, gr)


def flash_reference_check(s: int, s_misaligned: int = 0) -> int:
    """Assert flash == reference ON THE CURRENT BACKEND — outputs AND
    gradients, head_dim 64 and 128, at sequence length ``s``: plain,
    causal, and two offset cases; two sequence-major calls (width 256,
    and width 128 on grouped K/V); then the causal ring's first step and
    its later ones on either side of ``src``. ``s_misaligned``
    (optional) adds one causal case at a length that is a multiple of 8
    but not of the bf16 tile's 16 rows. Returns the number of cases;
    raises on any mismatch — a caller must fail loudly, not time wrong
    code."""
    ncases = 0
    for hd in (64, 128):
        # (causal, q_offset, kv_offset): plain, causal, a kv chunk wholly
        # in the past, a diagonal at a mid offset.
        for causal, qo, ko in [(False, 0, 0), (True, 0, 0), (True, s, 0),
                               (True, s // 2, s // 2)]:
            _check_case(s, hd, causal, qo, ko)
            ncases += 1
    if s_misaligned:
        _check_case(s_misaligned, 64, True, 0, 0)
        ncases += 1
    # Sequence-major operands, a head a column block of (B, S, H D): the
    # latent-attention width, and grouped K/V at the narrowest legal one.
    _check_case(s, 256, True, 0, 0, layout="bshd")
    _check_case(s, 128, True, s // 2, s // 2, kv_heads=2, layout="bshd")
    ncases += 2

    # The causal ring's cases (parallel/ring_attention.py): the plain
    # causal call of its first step, then one unmasked kernel over two
    # stripe pairs, the second selected by the traced side ``src`` is on,
    # combined into the stripes' accumulators — compile and run both sides
    # on this backend, the kernel against the reference in the same
    # construct.
    from ..parallel.ring_attention import causal_ring_step

    kq, kk, kv = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(kq, (1, 2, s, 64), jnp.bfloat16)
    k = jax.random.normal(kk, (2, 1, 2, s, 64), jnp.bfloat16)
    v = jax.random.normal(kv, (2, 1, 2, s, 64), jnp.bfloat16)

    def ring_cases(attend):
        @jax.jit
        def run(src_is_earlier, q, k, v):
            out, lse = attend(q, k[0], v[0], causal=True)
            halves = zip(jnp.split(out, 2, 2), jnp.split(lse, 2, 2))
            early, late = causal_ring_step(attend, src_is_earlier, *halves,
                                           q, k[1], v[1])
            return (out, jnp.concatenate([early[0], late[0]], 2),
                    jnp.concatenate([early[1], late[1]], 2))
        return run

    for earlier in (True, False):
        got = ring_cases(flash_attention)(earlier, q, k, v)
        want = ring_cases(mha_reference)(earlier, q, k, v)
        if earlier:
            _check("ring step 0 (causal)", got[0], want[0])
            ncases += 1
        tag = f"ring step, src {'<' if earlier else '>'} idx"
        _check(f"{tag}: out", got[1], want[1])
        _check(f"{tag}: lse", got[2], want[2])
        ncases += 1
    return ncases
