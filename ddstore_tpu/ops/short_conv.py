"""Gated short convolution: the mixer of an ``lfm2_moe`` ``conv`` layer
(``Lfm2ShortConv``), between its two projections.

``[Bg | Cg | u]`` are the three thirds of the input projection's output;
``z = Bg * u``; a causal depthwise convolution of ``L`` taps over the
sequence, ``c[t] = sum_j taps[j] * z[t - (L - 1) + j]`` with ``z`` zero
before the sequence's start; ``y = Cg * c``. Position ``t`` reads positions
``t - L + 1 .. t`` and no other. Products and the accumulation over the
taps are float32, whatever the input's type; ``y`` comes back in it.

Its least work is bytes (three thirds read, one written: 4 C a token
forward; ``Bg, Cg, u, dy`` read and three thirds written, 7 C, backward;
against 3 L C multiply-adds), so what matters is that each row is read
once. Two Pallas kernels (``ddstore_short_conv_fwd`` / ``_bwd``) walk each
sequence in blocks of rows, all channels at once: the forward carries the
last rows of ``z`` from block to block in VMEM (no halo is fetched), the
backward carries them too, fetches the eight rows after its block of ``dy``
and ``Cg`` for the transposed taps, and accumulates the taps' gradient in
an output block that stays resident. On the CPU the same kernels run in
interpreter mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_chip

# Rows of a block (all 3 C channels wide), the lanes a strip of the body
# works on at a time (its float32 temporaries are rows x strip), and the
# rows of the carried / fetched halo (a sublane tile; at least L - 1).
_ROWS, _STRIP, _HALO = 256, 512, 8
_VMEM_LIMIT = 64 * 1024 * 1024


def _strips(c: int):
    w = _STRIP if c % _STRIP == 0 else c
    return [(lo, lo + w) for lo in range(0, c, w)]


def _shifted(halo, x, k):
    """Rows of ``[halo ; x]`` ``k`` before each row of ``x`` (k > 0: the
    past, ``halo`` the ``_HALO`` rows before ``x``) or of ``[x ; halo]``
    ``-k`` after it (k < 0: ``halo`` the rows after)."""
    n = x.shape[0]
    if k == 0:
        return x
    if k > 0:
        both = jnp.concatenate([halo, x], axis=0)
        return pltpu.roll(both, shift=k, axis=0)[_HALO:]
    both = jnp.concatenate([x, halo], axis=0)
    return pltpu.roll(both, shift=n + _HALO + k, axis=0)[:n]


def _fwd_kernel(x_ref, w_ref, y_ref, tail_ref, *, c, taps):
    @pl.when(pl.program_id(1) == 0)
    def _():
        tail_ref[:] = jnp.zeros_like(tail_ref)

    f32 = jnp.float32
    for lo, hi in _strips(c):
        bg = x_ref[0, :, lo:hi].astype(f32)
        cg = x_ref[0, :, c + lo:c + hi].astype(f32)
        u = x_ref[0, :, 2 * c + lo:2 * c + hi].astype(f32)
        z = bg * u
        tail = tail_ref[:, lo:hi]
        acc = w_ref[taps - 1:taps, lo:hi] * z
        for j in range(taps - 1):
            acc = acc + w_ref[j:j + 1, lo:hi] * _shifted(tail, z,
                                                         taps - 1 - j)
        y_ref[0, :, lo:hi] = (cg * acc).astype(y_ref.dtype)
        tail_ref[:, lo:hi] = z[z.shape[0] - _HALO:]


def _bwd_kernel(x_ref, dy_ref, xn_ref, dyn_ref, w_ref, dx_ref, dw_ref,
                tail_ref, *, c, taps):
    b, s = pl.program_id(0), pl.program_id(1)

    @pl.when(s == 0)
    def _():
        tail_ref[:] = jnp.zeros_like(tail_ref)

    @pl.when((b == 0) & (s == 0))
    def _():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    f32 = jnp.float32
    last = s == pl.num_programs(1) - 1
    for lo, hi in _strips(c):
        bg = x_ref[0, :, lo:hi].astype(f32)
        cg = x_ref[0, :, c + lo:c + hi].astype(f32)
        u = x_ref[0, :, 2 * c + lo:2 * c + hi].astype(f32)
        dy = dy_ref[0, :, lo:hi].astype(f32)
        z = bg * u
        tail = tail_ref[:, lo:hi]
        dc = dy * cg
        # the rows after this block: none after the sequence's last
        dcn = jnp.where(last, 0.0, dyn_ref[0, :, lo:hi].astype(f32)
                        * xn_ref[0, :, c + lo:c + hi].astype(f32))
        acc = w_ref[taps - 1:taps, lo:hi] * z
        dz = w_ref[taps - 1:taps, lo:hi] * dc
        dw_ref[taps - 1:taps, lo:hi] += jnp.sum(dc * z, axis=0,
                                                keepdims=True)
        for j in range(taps - 1):
            k = taps - 1 - j
            zk = _shifted(tail, z, k)
            acc = acc + w_ref[j:j + 1, lo:hi] * zk
            dz = dz + w_ref[j:j + 1, lo:hi] * _shifted(dcn, dc, -k)
            dw_ref[j:j + 1, lo:hi] += jnp.sum(dc * zk, axis=0, keepdims=True)
        dx_ref[0, :, lo:hi] = (dz * u).astype(dx_ref.dtype)
        dx_ref[0, :, c + lo:c + hi] = (dy * acc).astype(dx_ref.dtype)
        dx_ref[0, :, 2 * c + lo:2 * c + hi] = (dz * bg).astype(dx_ref.dtype)
        tail_ref[:, lo:hi] = z[z.shape[0] - _HALO:]


def _fit_rows(s: int, rows: int) -> int:
    """Largest multiple of the halo that divides ``s`` and is <= ``rows``."""
    for r in range(min(rows, s) // _HALO * _HALO, 0, -_HALO):
        if s % r == 0:
            return r
    raise ValueError(f"sequence length {s} must be a multiple of {_HALO}")


def _padded_taps(taps):
    return jnp.pad(taps.astype(jnp.float32),
                   ((0, _HALO - taps.shape[0]), (0, 0)))


def _params(interpret):
    return {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv(bcu, taps, rows, interpret):
    return _conv_fwd(bcu, taps, rows, interpret)[0]


def _conv_fwd(bcu, taps, rows, interpret):
    b, s, c3 = bcu.shape
    c, n = c3 // 3, taps.shape[0]
    with jax.named_scope("short_conv"):
        y = pl.pallas_call(
            functools.partial(_fwd_kernel, c=c, taps=n),
            name="ddstore_short_conv_fwd",
            grid=(b, s // rows),
            in_specs=[pl.BlockSpec((1, rows, c3), lambda i, j: (i, j, 0)),
                      pl.BlockSpec((_HALO, c), lambda i, j: (0, 0))],
            out_specs=pl.BlockSpec((1, rows, c), lambda i, j: (i, j, 0)),
            out_shape=jax.ShapeDtypeStruct((b, s, c), bcu.dtype),
            scratch_shapes=[pltpu.VMEM((_HALO, c), jnp.float32)],
            interpret=interpret, **_params(interpret))(
                bcu, _padded_taps(taps))
    return y, (bcu, taps)


def _conv_bwd(rows, interpret, res, dy):
    bcu, taps = res
    b, s, c3 = bcu.shape
    c, n = c3 // 3, taps.shape[0]
    per, halos = rows // _HALO, s // _HALO
    after = lambda i, j: (i, jnp.minimum((j + 1) * per, halos - 1), 0)
    dy = dy.astype(bcu.dtype)
    with jax.named_scope("short_conv"):
        dx, dw = pl.pallas_call(
            functools.partial(_bwd_kernel, c=c, taps=n),
            name="ddstore_short_conv_bwd",
            grid=(b, s // rows),
            in_specs=[pl.BlockSpec((1, rows, c3), lambda i, j: (i, j, 0)),
                      pl.BlockSpec((1, rows, c), lambda i, j: (i, j, 0)),
                      pl.BlockSpec((1, _HALO, c3), after),
                      pl.BlockSpec((1, _HALO, c), after),
                      pl.BlockSpec((_HALO, c), lambda i, j: (0, 0))],
            out_specs=[pl.BlockSpec((1, rows, c3), lambda i, j: (i, j, 0)),
                       pl.BlockSpec((_HALO, c), lambda i, j: (0, 0))],
            out_shape=[jax.ShapeDtypeStruct(bcu.shape, bcu.dtype),
                       jax.ShapeDtypeStruct((_HALO, c), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((_HALO, c), jnp.float32)],
            interpret=interpret, **_params(interpret))(
                bcu, dy, bcu, dy, _padded_taps(taps))
    return dx, dw[:n].astype(taps.dtype)


_conv.defvjp(_conv_fwd, _conv_bwd)


def gated_short_conv(bcu: jax.Array, taps: jax.Array, *,
                     interpret: Optional[bool] = None) -> jax.Array:
    """``bcu`` (B, S, 3 C) = ``[Bg | Cg | u]``, ``taps`` (L, C) with
    ``taps[L - 1]`` the current position's: ``y`` (B, S, C) in ``bcu``'s
    type. Differentiable in both. ``S`` must be a multiple of 8 and ``L``
    at most 8: the carried halo's rows."""
    if not 1 <= taps.shape[0] <= _HALO:
        raise ValueError(f"{taps.shape[0]} taps: the carried halo holds "
                         f"{_HALO} rows, one a tap")
    if interpret is None:
        interpret = not on_chip()
    return _conv(bcu, taps, _fit_rows(bcu.shape[1], _ROWS), interpret)


# ---------------------------------------------------------------------------
# The plain form: a Mamba-2 layer's convolution (``NemotronHMamba2Mixer``).
# No gates: ``y = silu(bias + sum_j taps[j] * x[t - (L - 1) + j])``, ``x``
# zero before the sequence's start, products and the accumulation over the
# taps in float32, ``y`` back in ``x``'s type. Its least work is bytes too
# (x read, y written forward; x and dy read, dx written backward), so two
# kernels of the same build as the gated pair (``ddstore_conv_silu_fwd`` /
# ``_bwd``): blocks of rows, all channels, the last rows of ``x`` carried in
# VMEM; the backward fetches the eight rows after its block of ``x`` and
# ``dy`` and computes their pre-activation again for the transposed taps.
# Taps and bias travel as one (8, C) block, the bias its last row, and their
# gradients come back the same way.
# ---------------------------------------------------------------------------


def _pre_activation(w_ref, halo, z, lo, hi, taps):
    """``bias + sum_j taps[j] * z[t - (L - 1) + j]`` for the rows of ``z``,
    ``halo`` the ``_HALO`` rows before them."""
    acc = w_ref[_HALO - 1:_HALO, lo:hi] + w_ref[taps - 1:taps, lo:hi] * z
    for j in range(taps - 1):
        acc = acc + w_ref[j:j + 1, lo:hi] * _shifted(halo, z, taps - 1 - j)
    return acc


def _silu_fwd_kernel(x_ref, w_ref, y_ref, tail_ref, *, c, taps):
    @pl.when(pl.program_id(1) == 0)
    def _():
        tail_ref[:] = jnp.zeros_like(tail_ref)

    for lo, hi in _strips(c):
        z = x_ref[0, :, lo:hi].astype(jnp.float32)
        acc = _pre_activation(w_ref, tail_ref[:, lo:hi], z, lo, hi, taps)
        y_ref[0, :, lo:hi] = (acc * jax.nn.sigmoid(acc)).astype(y_ref.dtype)
        tail_ref[:, lo:hi] = z[z.shape[0] - _HALO:]


def _silu_bwd_kernel(x_ref, dy_ref, xn_ref, dyn_ref, w_ref, dx_ref, dw_ref,
                     tail_ref, *, c, taps):
    b, s = pl.program_id(0), pl.program_id(1)

    @pl.when(s == 0)
    def _():
        tail_ref[:] = jnp.zeros_like(tail_ref)

    @pl.when((b == 0) & (s == 0))
    def _():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    f32 = jnp.float32
    last = s == pl.num_programs(1) - 1

    def through_silu(dy, acc):
        sig = jax.nn.sigmoid(acc)
        return dy * sig * (1.0 + acc * (1.0 - sig))

    for lo, hi in _strips(c):
        z = x_ref[0, :, lo:hi].astype(f32)
        tail = tail_ref[:, lo:hi]
        mine = z[z.shape[0] - _HALO:]
        dc = through_silu(dy_ref[0, :, lo:hi].astype(f32),
                          _pre_activation(w_ref, tail, z, lo, hi, taps))
        # the rows after this block: none after the sequence's last
        dcn = jnp.where(last, 0.0, through_silu(
            dyn_ref[0, :, lo:hi].astype(f32), _pre_activation(
                w_ref, mine, xn_ref[0, :, lo:hi].astype(f32), lo, hi, taps)))
        dz = w_ref[taps - 1:taps, lo:hi] * dc
        dw_ref[taps - 1:taps, lo:hi] += jnp.sum(dc * z, axis=0,
                                                keepdims=True)
        dw_ref[_HALO - 1:_HALO, lo:hi] += jnp.sum(dc, axis=0, keepdims=True)
        for j in range(taps - 1):
            k = taps - 1 - j
            dz = dz + w_ref[j:j + 1, lo:hi] * _shifted(dcn, dc, -k)
            dw_ref[j:j + 1, lo:hi] += jnp.sum(
                dc * _shifted(tail, z, k), axis=0, keepdims=True)
        dx_ref[0, :, lo:hi] = dz.astype(dx_ref.dtype)
        tail_ref[:, lo:hi] = mine


def _taps_and_bias(taps, bias):
    f32 = jnp.float32
    return jnp.concatenate([
        taps.astype(f32),
        jnp.zeros((_HALO - 1 - taps.shape[0], taps.shape[1]), f32),
        bias.astype(f32)[None]])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_silu(x, taps, bias, rows, interpret):
    return _conv_silu_fwd(x, taps, bias, rows, interpret)[0]


def _conv_silu_fwd(x, taps, bias, rows, interpret):
    b, s, c = x.shape
    with jax.named_scope("short_conv"):
        y = pl.pallas_call(
            functools.partial(_silu_fwd_kernel, c=c, taps=taps.shape[0]),
            name="ddstore_conv_silu_fwd",
            grid=(b, s // rows),
            in_specs=[pl.BlockSpec((1, rows, c), lambda i, j: (i, j, 0)),
                      pl.BlockSpec((_HALO, c), lambda i, j: (0, 0))],
            out_specs=pl.BlockSpec((1, rows, c), lambda i, j: (i, j, 0)),
            out_shape=jax.ShapeDtypeStruct((b, s, c), x.dtype),
            scratch_shapes=[pltpu.VMEM((_HALO, c), jnp.float32)],
            interpret=interpret, **_params(interpret))(
                x, _taps_and_bias(taps, bias))
    return y, (x, taps, bias)


def _conv_silu_bwd(rows, interpret, res, dy):
    x, taps, bias = res
    b, s, c = x.shape
    n = taps.shape[0]
    per, halos = rows // _HALO, s // _HALO
    block = pl.BlockSpec((1, rows, c), lambda i, j: (i, j, 0))
    after = pl.BlockSpec((1, _HALO, c), lambda i, j: (
        i, jnp.minimum((j + 1) * per, halos - 1), 0))
    whole = pl.BlockSpec((_HALO, c), lambda i, j: (0, 0))
    dy = dy.astype(x.dtype)
    with jax.named_scope("short_conv"):
        dx, dw = pl.pallas_call(
            functools.partial(_silu_bwd_kernel, c=c, taps=n),
            name="ddstore_conv_silu_bwd",
            grid=(b, s // rows),
            in_specs=[block, block, after, after, whole],
            out_specs=[block, whole],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct((_HALO, c), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((_HALO, c), jnp.float32)],
            interpret=interpret, **_params(interpret))(
                x, dy, x, dy, _taps_and_bias(taps, bias))
    return dx, dw[:n].astype(taps.dtype), dw[_HALO - 1].astype(bias.dtype)


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def short_conv(x: jax.Array, taps: jax.Array, bias: jax.Array, *,
               interpret: Optional[bool] = None) -> jax.Array:
    """``x`` (B, S, C), ``taps`` (L, C) with ``taps[L - 1]`` the current
    position's, ``bias`` (C,): ``silu`` of the causal depthwise convolution
    plus ``bias``, (B, S, C) in ``x``'s type. Differentiable in all three.
    ``S`` must be a multiple of 8 and ``L`` at most 7: the carried halo's
    rows, less the one the bias travels in."""
    if not 1 <= taps.shape[0] < _HALO:
        raise ValueError(f"{taps.shape[0]} taps: the block of taps and "
                         f"bias holds {_HALO - 1} taps")
    if interpret is None:
        interpret = not on_chip()
    return _conv_silu(x, taps, bias, _fit_rows(x.shape[1], _ROWS), interpret)
