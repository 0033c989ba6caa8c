"""The Mamba-2 state-space dual (SSD) in chunks: the mixer of a
``nemotron_h`` ``M`` layer between its convolution and its gated norm.

Per head ``h`` (its group ``g = h // (H / G)``) and position ``t``, with
``a_t = dt_t A_h`` (``A_h < 0``, ``dt_t > 0``) and ``S_0 = 0``:

    S_t = exp(a_t) S_{t-1} + dt_t x_t B_{g,t}^T      (P x N)
    y_t = S_t C_{g,t} + D_h x_t

The recurrence is never walked a token at a time. With the sequence cut
into chunks of ``Q`` positions and ``cum_i`` the sum of ``a`` over a
chunk's positions up to and including ``i``:

    within a chunk   y_i  = sum_{j <= i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j
    a chunk's state  Z    = sum_j exp(cum_end - cum_j) dt_j x_j B_j^T
    across chunks    S_c  = exp(cum_end of chunk c - 1) S_{c-1} + Z_{c-1}
    from before      y_i += exp(cum_i) C_i S_c

so the work is four batched products a chunk (``C B^T``, the masked decay
matrix times ``dt x``, the chunk's state, ``C S``) and a scan over ``S / Q``
states. Decays, their sums and the states are float32; the products'
operands are in ``x``'s type and accumulate in float32. ``exp`` only ever
sees a sum of ``a`` over positions after ``j`` up to ``i``, which is at
most 0: a masked entry is ``exp(-inf)``, not a product with zero.

The gradient is autodiff's of this chunked form. Every temporary the size
of the decay matrix (tokens x H x Q) lives inside one call's forward or
backward; a caller that rematerialises its layer (``nn.remat``) saves none
of them. A rule of its own (``jax.custom_vjp`` keeping the six operands,
each product transposed by hand) was written and measured on the v5e at 2
x 8192 tokens, 64 heads of 64, state 128: 21.00 ms forward + backward
against autodiff's 20.33 (PERF.md section 6, PR 33), so it is not here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, D: jax.Array, chunk: int) -> jax.Array:
    """``x`` (b, S, H, P), ``dt`` (b, S, H) positive, ``A`` (H,) negative,
    ``B`` and ``C`` (b, S, G, N) with ``G`` dividing ``H``, ``D`` (H,):
    ``y`` (b, S, H, P) in ``x``'s type. ``S`` must be a multiple of
    ``chunk`` or shorter than it. Differentiable in all six."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    chunk = min(chunk, s)            # a shorter sequence is one chunk
    if s % chunk:
        raise ValueError(f"sequence length {s} is not a multiple of the "
                         f"scan's chunk {chunk}")
    if h % g:
        raise ValueError(f"{g} groups do not divide {h} heads")
    r, q, c = h // g, chunk, s // chunk
    f32, cd = jnp.float32, x.dtype
    with jax.named_scope("ssd"):
        dt = dt.astype(f32)
        # (b, c, g, r, q): a head's decays of a chunk lie together
        cum = jnp.cumsum(
            (dt * A.astype(f32)).reshape(b, c, q, g, r), axis=2
        ).transpose(0, 1, 3, 4, 2)
        xdt = (x.astype(f32) * dt[..., None]).reshape(b, c, q, g, r, p)
        Bc, Cc = (t.astype(cd).reshape(b, c, q, g, n) for t in (B, C))

        # within a chunk: the decay from j to i on C_i . B_j, lower triangle
        cb = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                        preferred_element_type=f32)
        causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
        decay = jnp.exp(jnp.where(
            causal, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        y = jnp.einsum("bcgrij,bcjgrp->bcigrp",
                       (decay * cb[:, :, :, None]).astype(cd),
                       xdt.astype(cd), preferred_element_type=f32)

        # a chunk's own state, and the state each chunk starts from
        to_end = jnp.exp(cum[..., -1:] - cum).transpose(0, 1, 4, 2, 3)
        own = jnp.einsum("bcjgrp,bcjgn->bcgrpn",
                         (xdt * to_end[..., None]).astype(cd), Bc,
                         preferred_element_type=f32)
        through = jnp.exp(cum[..., -1])              # (b, c, g, r)

        def step(state, chunk_c):
            own_c, through_c = chunk_c
            return state * through_c[..., None, None] + own_c, state

        _, before = jax.lax.scan(
            step, jnp.zeros((b, g, r, p, n), f32),
            (own.swapaxes(0, 1), through.swapaxes(0, 1)))
        from_before = jnp.einsum(
            "bcign,cbgrpn->bcigrp", Cc, before.astype(cd),
            preferred_element_type=f32)
        y = y + from_before * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
        y = y.reshape(b, s, h, p) + D.astype(f32)[:, None] * x.astype(f32)
        return y.astype(cd)
