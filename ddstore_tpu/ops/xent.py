"""Fused linear + softmax cross-entropy (the LM-head hot path).

At LM scale the head is the single largest tensor in the step: logits are
``(tokens, vocab)`` — 2 GiB in f32 at 16 Ki tokens x 32 Ki vocab — and the
standard ``logits = x @ W; log_softmax`` pipeline writes them to HBM in the
forward AND re-materializes ``dlogits`` in the backward. This op computes
the same per-token negative log-likelihood by streaming the vocab dimension
in blocks through an online logsumexp, so peak memory is ``(tokens,
block)`` instead of ``(tokens, vocab)`` and the logits never round-trip
HBM. The backward recomputes each logits block from the saved activations
(flash-attention-style rematerialization: trade one extra matmul pass for
the 2x logits traffic).

The matmuls stay large, static and MXU-shaped (``jnp.dot`` with f32
accumulation, vocab blocks of a few thousand columns), the scan is a
``lax.scan`` over a static block count — exactly the control flow XLA
pipelines well on TPU.

Reference parity note: the reference has no model math at all (its model
is the example VAE, /root/reference/examples/vae/vae-ddp.py:174-200); this
op exists for the long-context LM flagship that SURVEY §2.2/§7 adds on
top.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..utils.profile import RECOMPUTE

NEG_INF = float("-inf")


def _num_blocks(v: int, block: int) -> int:
    return -(-v // block)


def _block_cols(w: jax.Array, i: int, block: int) -> jax.Array:
    """Columns ``[i*block, (i+1)*block)`` of ``w``, zero-padded past V.

    ``lax.dynamic_slice`` clamps out-of-range starts, which would silently
    alias the last in-range block; pad once instead so every block is a
    real slice.
    """
    return jax.lax.dynamic_slice_in_dim(w, i * block, block, axis=1)


def _pad_cols(w: jax.Array, block: int) -> jax.Array:
    v = w.shape[1]
    pad = _num_blocks(v, block) * block - v
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
    return w


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_linear_xent(x: jax.Array, w: jax.Array, targets: jax.Array,
                      block: int = 8192,
                      compute_dtype: Optional[jnp.dtype] = None
                      ) -> jax.Array:
    """Per-token NLL of ``softmax(x @ w)`` without materializing logits.

    Args:
      x: ``(n, d)`` activations (any float dtype).
      w: ``(d, v)`` head kernel.
      targets: ``(n,)`` int class ids in ``[0, v)``.
      block: vocab-block width (static; clamped to ``v``).
      compute_dtype: dtype the matmul operands are cast to (accumulation
        is always f32). Default: ``x.dtype``.

    Returns ``(n,)`` f32 negative log-likelihoods; ``nll.mean()`` equals
    ``loss_fn(x @ w, targets)`` of the unfused path up to summation order.
    Differentiable in ``x`` and ``w``.
    """
    nll, _ = _fwd(x, w, targets, block, compute_dtype)
    return nll


def _logits_block(x, wp, i, block, v, compute_dtype):
    """Logits for vocab block ``i`` from the PADDED kernel ``wp``; columns
    past the true vocab size ``v`` are masked to -inf."""
    dt = compute_dtype or x.dtype
    wb = _block_cols(wp, i, block)
    lg = jnp.dot(x.astype(dt), wb.astype(dt),
                 preferred_element_type=jnp.float32)
    col = i * block + jnp.arange(block)
    return jnp.where(col[None, :] < v, lg, NEG_INF)


def _fwd(x, w, targets, block, compute_dtype):
    n, _ = x.shape
    v = w.shape[1]
    block = min(block, v)
    nb = _num_blocks(v, block)
    wp = _pad_cols(w, block)
    rows = jnp.arange(n)

    def body(carry, i):
        m, l, tl = carry
        lg = _logits_block(x, wp, i, block, v, compute_dtype)
        bm = jnp.max(lg, axis=-1)
        m_new = jnp.maximum(m, bm)
        # exp(-inf - -inf) can't occur: m_new >= bm > -inf whenever any
        # real column exists in the block, and m starts finite-safe below.
        l = l * jnp.exp(m - m_new) + jnp.exp(lg - m_new[:, None]).sum(-1)
        t_local = targets - i * block
        in_blk = (t_local >= 0) & (t_local < block)
        picked = lg[rows, jnp.clip(t_local, 0, block - 1)]
        tl = jnp.where(in_blk, picked, tl)
        return (m_new, l, tl), None

    init = (jnp.full((n,), -1e30, jnp.float32),  # finite: avoids inf-inf
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32))
    (m, l, tl), _ = jax.lax.scan(body, init, jnp.arange(nb))
    lse = m + jnp.log(l)
    nll = lse - tl
    return nll, (x, w, targets, lse)


def _bwd(block, compute_dtype, res, g):
    x, w, targets, lse = res
    n, d = x.shape
    v = w.shape[1]
    block = min(block, v)
    nb = _num_blocks(v, block)
    wp = _pad_cols(w, block)
    rows = jnp.arange(n)
    gcol = g[:, None].astype(jnp.float32)
    dt = compute_dtype or x.dtype

    def body(dx, i):
        # the forward's product again: the pass ``recompute`` in a trace
        with jax.named_scope(RECOMPUTE):
            lg = _logits_block(x, wp, i, block, v, compute_dtype)
        p = jnp.exp(lg - lse[:, None])  # softmax block; 0 at padded cols
        t_local = targets - i * block
        in_blk = (t_local >= 0) & (t_local < block)
        onehot = (jnp.arange(block)[None, :]
                  == jnp.clip(t_local, 0, block - 1)[:, None])
        p = p - jnp.where(in_blk[:, None], onehot, False)
        dlg = (p * gcol).astype(dt)
        wb = _block_cols(wp, i, block)
        dx = dx + jnp.dot(dlg, wb.astype(dt).T,
                          preferred_element_type=jnp.float32)
        dwb = jnp.dot(x.astype(dt).T, dlg,
                      preferred_element_type=jnp.float32)
        # dw comes back as stacked per-block ys — carrying the full (d, v)
        # buffer through the scan would stream it through HBM every
        # iteration.
        return dx, dwb

    dx, dws = jax.lax.scan(body, jnp.zeros((n, d), jnp.float32),
                           jnp.arange(nb))
    dw = jnp.moveaxis(dws, 0, 1).reshape(d, nb * block)[:, :v]
    return (dx.astype(x.dtype), dw.astype(w.dtype), None)


fused_linear_xent.defvjp(_fwd, _bwd)
