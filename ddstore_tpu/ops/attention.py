"""Attention ops: Pallas TPU flash attention + XLA reference.

The building block for long-context support (sequence/context parallelism
is absent in the reference — SURVEY §2.2 — and a first-class goal here).
Both implementations return ``(out, lse)`` where ``lse`` is the per-query
log-sum-exp of the attention scores: that pair is the composable unit —
:func:`ddstore_tpu.parallel.ring_attention.ring_attention` combines
``(out, lse)`` blocks across devices with the same online-softmax algebra
the kernel uses across key blocks.

Design notes (TPU):
* the kernel streams K/V blocks through VMEM with a running (m, l, acc)
  online softmax in f32 scratch — O(S) memory, no S×S materialization;
* QK^T and PV ride the MXU via ``jnp.dot`` with f32 accumulation;
* causal masking takes global ``q_offset``/``kv_offset`` so the same
  kernel serves ring-attention steps, where the kv chunk's global
  position rotates per step;
* on CPU (tests) the identical kernel runs in interpreter mode;
* the three kernels carry stable names (``ddstore_flash_fwd``,
  ``ddstore_flash_dq``, ``ddstore_flash_dkv``): a device trace names the
  custom call after them, on one chip and inside the ring's branches.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _causal_liveness(iq, ik, block_q, block_k, q_offset, kv_offset):
    """(live, diag) for a causal (q-block, k-block) pair: ``live`` = the
    block has any unmasked entry; ``diag`` = it straddles the diagonal
    and needs the iota mask (blocks entirely in the past are mask-free —
    the mask's compare/select is pure VPU cost). THE single classification
    shared by the forward and both backward kernels."""
    q_lo = q_offset + iq * block_q
    k_lo = kv_offset + ik * block_k
    live = k_lo <= q_lo + block_q - 1
    diag = live & (k_lo + block_k - 1 > q_lo)
    return live, diag


def _masked_dispatch(causal, live, diag, update):
    """Run ``update(masked)`` under the liveness predicates: the diagonal
    body with masking, interior live blocks without, dead blocks not at
    all (non-causal: one unmasked body, unconditionally)."""
    if causal:
        pl.when(diag)(lambda: update(True))
        pl.when(live & ~diag)(lambda: update(False))
    else:
        update(False)


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = False, q_offset: int = 0,
                  kv_offset: int = 0, scale: Optional[float] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Plain-XLA attention over (..., S, D); returns (out, lse in f32)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("...qd,...kd->...qk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[-2])[:, None]
        kpos = kv_offset + jnp.arange(k.shape[-2])[None, :]
        s = jnp.where(kpos <= qpos, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    # Fully-masked rows (possible in ring steps) must yield out=0, lse=-inf
    # without NaNs: exp(-inf - -inf) is guarded by zeroing those rows.
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - safe_m)
    p = jnp.where(jnp.isfinite(m), p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("...qk,...kd->...qd", p, v,
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(l, 1e-30)
    lse = (safe_m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
    lse = jnp.where(jnp.isfinite(m[..., 0]), lse, NEG_INF)
    return out.astype(q.dtype), lse


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                  *, scale, causal, q_offset, kv_offset, block_q, block_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # For causal, a K/V block entirely in the future contributes nothing —
    # predicate the whole accumulation away (≈halves causal FLOPs). Blocks
    # entirely in the PAST need no mask either: the iota/compare/select on
    # a (block_q, block_k) tile is pure VPU work and the kernel is
    # VPU-bound, so interior blocks take a mask-free body and only the
    # O(S/block) diagonal-straddling blocks pay for masking.
    if causal:
        live, diag = _causal_liveness(iq, ik, block_q, block_k, q_offset,
                                      kv_offset)
    else:
        live, diag = True, False

    def update(masked):
        q = q_ref[0]  # (block_q, D)
        k = k_ref[0]  # (block_k, D)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        if masked:
            qpos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kv_offset + ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)

        m_prev = m_scr[:, :1]                               # (block_q, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Rows with everything masked so far keep m=-inf; safe_m keeps the
        # subtraction finite and exp(-inf - 0) = 0 zeroes their p exactly
        # (no full-block select needed).
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    _masked_dispatch(causal, live, diag, update)

    @pl.when(ik == nk - 1)
    def _():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        m = m_scr[:, :1]
        lse = jnp.where(jnp.isfinite(m),
                        m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
        lse_ref[0] = jnp.broadcast_to(lse, (block_q, 128))


# Grid-step overhead on TPU is ~0.3us and steps run sequentially per core,
# so blocks must be big enough that the MXU work dominates: 512x2048 blocks
# measured 100.8 TF/s vs 12.8 TF/s at 128x128 on v5e (7.0x over XLA's 14.4).
_SEMS = ("parallel", "parallel", "arbitrary")


def _tpu_params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=_SEMS)}


def _fwd_impl(q, k, v, causal, q_offset, kv_offset, scale, block_q, block_k,
              interpret):
    """Runs the forward kernel; returns (out, lse, lse128-residual)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    grid = (b * h, sq // block_q, sk // block_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, q_offset=q_offset,
        kv_offset=kv_offset, block_q=block_q, block_k=block_k)
    out_f, lse_f = pl.pallas_call(
        kernel,
        name="ddstore_flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            # lse carries a broadcast 128-lane dim purely so its block is
            # (block_q, 128)-tile-aligned for the TPU lowering; lane 0 is
            # the value. The full tensor doubles as the backward residual.
            pl.BlockSpec((1, block_q, 128), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running denom
            pltpu.VMEM((block_q, d), jnp.float32),    # running numerator
        ],
        interpret=interpret,
        **_tpu_params(interpret),
    )(qf, kf, vf)
    return (out_f.reshape(b, h, sq, d), lse_f[..., 0].reshape(b, h, sq),
            lse_f)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, dta_ref, dq_ref,
                   dq_acc, *, scale, causal, q_offset, kv_offset, block_q,
                   block_k):
    """dq for one q block, streaming k/v blocks (recompute-p flash bwd).

    ``dta`` packs the per-row residual scalars into one 128-lane tensor
    (lane 0 = c = delta - dlse with delta = rowsum(do*o); lane 1 = lse):
    one streamed side input instead of two."""
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    if causal:
        live, diag = _causal_liveness(iq, ik, block_q, block_k, q_offset,
                                      kv_offset)
    else:
        live, diag = True, False

    def update(masked):
        q = q_ref[0]
        k = k_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if masked:
            qpos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kv_offset + ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        lse = dta_ref[0][:, 1:2]                             # (block_q, 1)
        # Fully-masked rows have lse = -inf; exp(s - safe_lse) is then
        # exp(-inf - big) = 0 for every column — no full-block select.
        safe_lse = jnp.where(jnp.isfinite(lse), lse, 1e30)
        p = jnp.exp(s - safe_lse)
        do = do_ref[0]
        dp = jnp.dot(do, v_ref[0].T, preferred_element_type=jnp.float32)
        # ds = p * (dp - c) with c = delta - dlse packed in lane 0.
        t = p * (dp - dta_ref[0][:, :1])
        dq_acc[:] = dq_acc[:] + jnp.dot(
            t.astype(k.dtype), k, preferred_element_type=jnp.float32) * scale

    _masked_dispatch(causal, live, diag, update)

    @pl.when(ik == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, dta_ref, dk_ref,
                    dv_ref, dk_acc, dv_acc, *, scale, causal, q_offset,
                    kv_offset, block_q, block_k):
    """dk/dv for one k/v block, streaming q blocks.

    The q-side streams (q, do, dta) re-fetch every grid step here (their
    block index rides the innermost loop), so the packed single ``dta``
    side input (c = delta - dlse in lane 0, lse in lane 1) halves the
    f32 side-stream HBM traffic vs separate lse + dta tensors."""
    ik, iq = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if causal:
        live, diag = _causal_liveness(iq, ik, block_q, block_k, q_offset,
                                      kv_offset)
    else:
        live, diag = True, False

    def update(masked):
        q = q_ref[0]
        k = k_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if masked:
            qpos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kv_offset + ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        lse = dta_ref[0][:, 1:2]
        safe_lse = jnp.where(jnp.isfinite(lse), lse, 1e30)
        p = jnp.exp(s - safe_lse)
        do = do_ref[0]
        dv_acc[:] = dv_acc[:] + jnp.dot(
            p.astype(do.dtype).T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v_ref[0].T, preferred_element_type=jnp.float32)
        t = p * (dp - dta_ref[0][:, :1])
        dk_acc[:] = dk_acc[:] + jnp.dot(
            t.astype(q.dtype).T, q, preferred_element_type=jnp.float32) \
            * scale

    _masked_dispatch(causal, live, diag, update)

    @pl.when(iq == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(3, 11)))
def _flash(q, k, v, causal, q_offset, kv_offset, scale, block_q, block_k,
           bwd_blocks, interpret):
    out, lse, _ = _fwd_impl(q, k, v, causal, q_offset, kv_offset, scale,
                            block_q, block_k, interpret)
    return out, lse


def _flash_fwd(q, k, v, causal, q_offset, kv_offset, scale, block_q,
               block_k, bwd_blocks, interpret):
    out, lse, _ = _fwd_impl(q, k, v, causal, q_offset, kv_offset,
                            scale, block_q, block_k, interpret)
    # Residual is the THIN (B, H, S) lse — the kernel's 128-lane output
    # is tile-alignment scaffolding and holding it across fwd→bwd would
    # cost 128x the activation memory (~1 GiB at the S=8192 LM config).
    return (out, lse), (q, k, v, out, lse)


def _flash_bwd(causal, q_offset, kv_offset, scale, block_q, block_k,
               bwd_blocks, interpret, res, g):
    q, k, v, out, lse = res
    do, dlse = g
    # The backward kernels stream different data patterns than the
    # forward (dq: k/v innermost; dkv: the whole q side innermost), so
    # they take their own block shapes.
    bq_dq, bk_dq, bq_dkv, bk_dkv = bwd_blocks
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bhs = b * h
    qf = q.reshape(bhs, sq, d)
    kf = k.reshape(bhs, sk, d)
    vf = v.reshape(bhs, sk, d)
    dof = do.reshape(bhs, sq, d)
    # Per-row residual scalars packed into ONE 128-lane tensor: lane 0
    # carries c = delta - dlse (delta = rowsum(do*o); the lse cotangent
    # folds into the same term since ds = p*(dp - delta + dlse)), lane 1
    # carries lse. stack+pad lowers to a single fused 128-lane write —
    # per-lane .at[].set constructions each cost a full-tensor
    # dynamic-update-slice pass (~2 ms/layer on v5e, profiled).
    delta = jnp.sum(dof.astype(jnp.float32)
                    * out.reshape(bhs, sq, d).astype(jnp.float32), axis=-1)
    c = delta - dlse.reshape(bhs, sq).astype(jnp.float32)
    dta = jnp.pad(jnp.stack([c, lse.reshape(bhs, sq)], axis=-1),
                  ((0, 0), (0, 0), (0, 126)))

    common = dict(scale=scale, causal=causal, q_offset=q_offset,
                  kv_offset=kv_offset)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=bq_dq, block_k=bk_dq,
                          **common),
        name="ddstore_flash_dq",
        grid=(bhs, sq // bq_dq, sk // bk_dq),
        in_specs=[
            pl.BlockSpec((1, bq_dq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk_dq, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk_dq, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bq_dq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bq_dq, 128), lambda bh, i, j: (bh, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_dq, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bhs, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq_dq, d), jnp.float32)],
        interpret=interpret,
        **_tpu_params(interpret),
    )(qf, kf, vf, dof, dta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=bq_dkv, block_k=bk_dkv,
                          **common),
        name="ddstore_flash_dkv",
        grid=(bhs, sk // bk_dkv, sq // bq_dkv),
        in_specs=[
            pl.BlockSpec((1, bq_dkv, d), lambda bh, j, i: (bh, i, 0)),
            pl.BlockSpec((1, bk_dkv, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, bk_dkv, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, bq_dkv, d), lambda bh, j, i: (bh, i, 0)),
            pl.BlockSpec((1, bq_dkv, 128), lambda bh, j, i: (bh, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk_dkv, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, bk_dkv, d), lambda bh, j, i: (bh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhs, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bhs, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk_dkv, d), jnp.float32),
            pltpu.VMEM((bk_dkv, d), jnp.float32),
        ],
        interpret=interpret,
        **_tpu_params(interpret),
    )(qf, kf, vf, dof, dta)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


_flash.defvjp(_flash_fwd, _flash_bwd)


def _fit_block(block: int, s: int) -> int:
    """Largest multiple of 8 that divides ``s`` and is <= ``block``
    (0 if none — i.e. s is not a multiple of 8)."""
    block = min(block, s)
    for b in range(block - block % 8, 7, -8):
        if s % b == 0:
            return b
    return 0


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, q_offset: int = 0,
                    kv_offset: int = 0, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    bwd_blocks: Optional[Tuple[int, int, int, int]] = None,
                    interpret: Optional[bool] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Pallas flash attention over (B, H, S, D); returns (out, lse).

    Differentiable: the backward pass is the standard recompute-p flash
    backward as two Pallas kernels (dq streaming K/V blocks; dk/dv
    streaming Q blocks), so training never materializes S×S. Sequence
    lengths must be multiples of 8 (callers pad; the data layer's budgets
    already guarantee static shapes). On non-TPU backends the same
    kernels run in interpreter mode.

    block_q/block_k (forward) and ``bwd_blocks`` = (block_q_dq,
    block_k_dq, block_q_dkv, block_k_dkv) are upper bounds, fitted per
    call to the largest divisor of the sequence length that is a multiple
    of 8. The defaults are length-adaptive, tuned on v5e with FULL
    fwd+dq+dkv gradients: 512x2048 below S=8192 (measured ~101 TF/s
    useful vs ~13 TF/s at 128x128 — grid-step overhead, not FLOPs,
    dominates small blocks) and 1024x1024 at S>=8192 (2048-wide q blocks
    exceed VMEM). The backward defaults follow block_q/block_k unless
    overridden.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if block_q is None:
        block_q = 1024 if sq >= 8192 else 512
    if block_k is None:
        block_k = 1024 if sq >= 8192 else 2048
    # Block sizes are upper bounds: fit each to the largest multiple of 8
    # (Mosaic sublane tile) that divides the sequence. Any seq length
    # divisible by 8 therefore works with the big TPU-tuned defaults
    # (e.g. sq=640 fits block_q=320); a misaligned length fails with the
    # same error on every backend, not just at TPU lowering time.
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    if not block_q or not block_k:
        raise ValueError(f"seq lens ({sq},{sk}) must be multiples of 8 "
                         f"(TPU tile alignment)")
    if bwd_blocks is None:
        bwd_blocks = (block_q, block_k, block_q, block_k)
    else:
        if any(bl < 8 for bl in bwd_blocks):
            raise ValueError(f"bwd_blocks entries must be >= 8 (TPU "
                             f"sublane tile), got {bwd_blocks}")
        bq_dq, bk_dq, bq_dkv, bk_dkv = bwd_blocks
        bwd_blocks = (_fit_block(bq_dq, sq), _fit_block(bk_dq, sk),
                      _fit_block(bq_dkv, sq), _fit_block(bk_dkv, sk))
        if not all(bwd_blocks):
            raise ValueError(f"seq lens ({sq},{sk}) must be multiples of "
                             f"8 (TPU tile alignment)")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _flash(q, k, v, causal, q_offset, kv_offset, scale, block_q,
                  block_k, bwd_blocks, interpret)
