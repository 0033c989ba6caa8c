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

Decays, their sums and the states are float32; the products' operands are
in ``x``'s type and accumulate in float32. ``exp`` only ever sees a sum of
``a`` over positions after ``j`` up to ``i``, which is at most 0: a masked
entry is ``exp(-inf)``, not a product with zero.

Two Pallas kernels (``ddstore_ssd_fwd`` / ``ddstore_ssd_bwd``) under a
``jax.custom_vjp``, on a grid over (batch row, group, chunks) with the
chunks innermost and in order (the backward's in reverse). A step reads
its rows of ``x`` as the group's ``H / G`` heads' columns of ``(b, S, H
P)`` and of ``B``, ``C`` as the group's columns of ``(b, S, G N)``: no
operand is transposed to head-major. It forms ``C B^T`` once for the
group and, a head at a time, the masked decay matrix and its product with
``dt x``; ``C S`` and the chunk's own state are one product each over all
of the group's heads. The group's ``(N, H / G x P)`` float32 state is
carried from chunk to chunk in VMEM, zeroed at a row's and group's first
chunk: the decay matrix (tokens x H x Q), ``C B^T`` and the chunk states
of the XLA form this replaces never reach HBM. Heads narrower than a lane
tile are worked on two (``128 // P``) at a time: each head's matrix meets
the pair's columns and a select keeps its own, which costs the MXU what a
product 64 wide does and slices nothing inside a tile.

Only ``cum`` (within-chunk cumulative sums of ``dt A``, ``(b, S, H)``
float32) is made by XLA, and handed over twice: beside ``dt`` as columns
``[dt | cum]`` ``(b, S, 2 H)``, of which a lane rotation brings the step's
group to the front and a lane gather spreads a head's value over its
head's lanes (one permutation a vreg; a broadcast a head and a select
cost the XLU twice that, and the XLU is the kernels' busiest unit), and as
rows ``(b, G, H / G, S)``: a decay matrix needs both.
XLA's autodiff takes ``cum``'s and ``dt``'s gradients back to ``dt`` and
``A``.

The backward keeps the six operands and the states the chunks started
from, written once by the forward in ``x``'s type (134 MB a layer at the
cell's shape; a forward that is not differentiated writes none). Nothing
else is saved, so a caller under ``nn.remat`` pays a second forward kernel
and no more. Walking the chunks in reverse with the state's cotangent in
VMEM it writes ``dx``, ``dB``, ``dC`` (summed over the group's heads by the
products themselves), ``ddt`` and ``dcum`` once each and ``dD`` as sums a
row and group that XLA finishes. Two identities keep (Q, Q) reductions out
of it: the decay matrix's gradient summed along row ``i`` is ``dy_i .
scan_i`` (the output less its skip term, computed once more: from ``y`` in
``x``'s type the gradient in ``A`` read 4.6e-2 from the recurrence's, this
way 4.7e-3), less the part from before, which the same dot product covers;
summed along column ``j`` it is ``(dt x)_j .`` its gradient; and the
chunk's last ``cum`` collects ``dS . S`` of the state it hands on. Sums
over a head's ``P`` lanes are products with a 0/1 matrix (the float32
summand split in two bfloat16 halves): lane reductions would cost the XLU
more than the products cost the MXU.

Measured on the v5e at (2, 8192), 64 heads of 64 on 8 groups, state 128,
chunk 128, bfloat16, a layer with XLA's part around the kernels (PERF.md
section 6, PR 34): the XLA form 10.68 ms forward and 19.54 forward +
backward; these kernels 1.75 and 5.13 with two chunks a grid step, 1.92
and 5.47 with one (four read 1.75 / 4.39 where two read 1.76 / 4.50,
before the backward computed the scan's output again, and double the
kernels' code: two). In the step a forward kernel is 0.94 ms and the
backward 2.40. The head sums as one bfloat16 product read 4.94 for 5.13
and were not taken. The states are kept, not computed again: the forward
holds each in VMEM when it would write it (134 MB a layer, 0.16 ms), and a
second sweep would read ``x`` and ``B`` again (138 MB) and multiply; that
alternative, and a backward of two kernels, were not measured.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_chip

# What ``counters()["mixer_layout"]`` reports of a Mamba-2 layer's scan.
SCAN = "pallas"

# Chunks a grid step works through (its blocks are this many times Q rows;
# the second chunk fills the first's stalls), and the lanes of a vreg.
_CHUNKS, _LANES = 2, 128
_VMEM_LIMIT = 64 * 1024 * 1024

_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _slabs(r: int, p: int):
    """The group's ``r`` heads of ``p`` lanes as (first lane, past the
    last, first head, heads): as many heads together as fill a lane tile."""
    k = max(1, min(r, _LANES // p))
    while r % k:
        k -= 1
    return [(h * p, (h + k) * p, h, k) for h in range(0, r, k)]


def _over_heads(parts, lane, p):
    """``parts[i]`` on the lanes of the slab's head ``i``."""
    out = parts[-1]
    for i in reversed(range(len(parts) - 1)):
        out = jnp.where(lane < (i + 1) * p, parts[i], out)
    return out


def _wide(v, heads):
    """Per-head values ``v`` (Q, lanes; the group's head ``h`` in lane
    ``h``), each over its head's lanes: lane ``l`` of the result takes lane
    ``heads[l]``, one permutation a vreg."""
    w = heads.shape[1]
    v = v[:, :w] if v.shape[1] >= w else jnp.pad(
        v, ((0, 0), (0, w - v.shape[1])))
    return jnp.take_along_axis(v, heads, axis=1)


def _decay(cum, cumt, h, causal):
    return jnp.exp(jnp.where(causal, cum[:, h:h + 1] - cumt[h:h + 1, :],
                             -jnp.inf))


def _dot(a, b, dims=_NN):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _per_head(cols_ref, rows_ref, rows, r):
    """A chunk's ``dt`` and ``cum`` of the step's group as columns: two
    rotations of ``[dt | cum]`` (Q, 2 H) that bring the group's ``dt`` and
    its ``cum`` to lanes ``0 .. r - 1``; and its ``cum`` as rows (r, Q)."""
    h = cols_ref.shape[2] // 2
    cols = cols_ref[0, rows, :]
    first = pl.program_id(1) * r
    front = lambda at: pltpu.roll(
        cols, lax.rem(2 * h - at - first, 2 * h), 1)
    return front(0), front(h), rows_ref[0, 0, :, rows]


def _slab_lanes(q, r, p):
    """What a step's chunks share: the causal mask (Q, Q), the slabs, the
    lane numbers of a slab (1, w) and, a slab, the head of each of its
    lanes (Q, w), counted within the group."""
    slabs = _slabs(r, p)
    w = slabs[0][1]
    lane = lax.broadcasted_iota(jnp.int32, (q, w), 1)
    in_slab = sum(((lane >= i * p).astype(jnp.int32)
                   for i in range(1, w // p)), jnp.zeros((q, w), jnp.int32))
    causal = (lax.broadcasted_iota(jnp.int32, (q, q), 0)
              >= lax.broadcasted_iota(jnp.int32, (q, q), 1))
    return causal, lane[:1], [(lo, hi, h0, k, in_slab + h0)
                              for lo, hi, h0, k in slabs]


def _fwd_kernel(x_ref, cols_ref, rows_ref, b_ref, c_ref, d_ref,
                y_ref, *rest, q, p, save):
    st_ref, state = rest if save else (None,) + rest

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[:] = jnp.zeros_like(state)

    f32, cd = jnp.float32, x_ref.dtype
    r = rows_ref.shape[2]
    causal, lane, slabs = _slab_lanes(q, r, p)
    for j in range(x_ref.shape[1] // q):
        rows = slice(j * q, (j + 1) * q)
        Bm, Cm = b_ref[0, rows, :], c_ref[0, rows, :]
        cb, Bt = _dot(Cm, Bm, _NT), Bm.T
        dt, cum, cumt = _per_head(cols_ref, rows_ref, rows, r)
        into, out_of = jnp.exp(cum), jnp.exp(cum[q - 1:q, :] - cum)
        if save:
            st_ref[0, j] = state[:].astype(cd)
        for lo, hi, h0, k, heads in slabs:
            xs = x_ref[0, rows, lo:hi].astype(f32)
            xdt = xs * _wide(dt, heads)
            xd = xdt.astype(cd)
            before = state[:, lo:hi]
            own = _over_heads(
                [_dot((_decay(cum, cumt, h0 + i, causal) * cb).astype(cd), xd)
                 for i in range(k)], lane, p)
            wide_into = _wide(into, heads)
            y_ref[0, rows, lo:hi] = (
                own + wide_into * _dot(Cm, before.astype(cd))
                + d_ref[:, lo:hi] * xs).astype(cd)
            # exp(cum) at the chunk's last row is the decay through it
            state[:, lo:hi] = before * wide_into[q - 1:q, :] + _dot(
                Bt, (xdt * _wide(out_of, heads)).astype(cd))


def _head_sums(v, ind):
    """``v`` (Q, lanes) float32 summed over each head's lanes, as rows
    (r, Q), through the 0/1 matrix ``ind`` (r, lanes) in ``x``'s type: a
    float32 product at full precision, or two bfloat16 products, the second
    of what the first's rounding left."""
    if ind.dtype == jnp.float32:
        return lax.dot_general(ind, v, _NT, precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    hi = v.astype(ind.dtype)
    lo = (v - hi.astype(jnp.float32)).astype(ind.dtype)
    return _dot(ind, hi, _NT) + _dot(ind, lo, _NT)


def _bwd_kernel(x_ref, dy_ref, cols_ref, rows_ref, b_ref, c_ref,
                d_ref, st_ref, ind_ref, dx_ref, db_ref, dc_ref, drows_ref,
                dd_ref, dstate, dend, *, q, p):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[:] = jnp.zeros_like(dstate)
        dend[:] = jnp.zeros_like(dend)
        dd_ref[:] = jnp.zeros_like(dd_ref)

    f32, cd = jnp.float32, x_ref.dtype
    r = rows_ref.shape[2]
    causal, lane, slabs = _slab_lanes(q, r, p)
    last = lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    for j in reversed(range(x_ref.shape[1] // q)):
        rows = slice(j * q, (j + 1) * q)
        Bm, Cm = b_ref[0, rows, :], c_ref[0, rows, :]
        cb, Ct = _dot(Cm, Bm, _NT), Cm.T
        dt, cum, cumt = _per_head(cols_ref, rows_ref, rows, r)
        into, out_of = jnp.exp(cum), jnp.exp(cum[q - 1:q, :] - cum)
        dcb = jnp.zeros((q, q), f32)
        dB = jnp.zeros(Bm.shape, f32)
        dC = jnp.zeros(Cm.shape, f32)
        sum_cum = jnp.zeros((r, q), f32)
        sum_dt = jnp.zeros((r, q), f32)
        for lo, hi, h0, k, heads in slabs:
            xs = x_ref[0, rows, lo:hi].astype(f32)
            dyb = dy_ref[0, rows, lo:hi]
            dys = dyb.astype(f32)
            dskip = d_ref[:, lo:hi]
            wide_dt, wide_out = _wide(dt, heads), _wide(out_of, heads)
            wide_into = _wide(into, heads)
            xdt = xs * wide_dt
            xd = xdt.astype(cd)
            before = st_ref[0, j, :, lo:hi]
            dafter = dstate[:, lo:hi]
            dafter_cd = dafter.astype(cd)
            xw = (xdt * wide_out).astype(cd)
            own, dxd = [], []
            for i in range(k):
                decay = _decay(cum, cumt, h0 + i, causal)
                mine = dyb if k == 1 else jnp.where(
                    (lane >= i * p) & (lane < (i + 1) * p), dys,
                    0.0).astype(cd)
                dcb = dcb + _dot(mine, xd, _NT) * decay
                m = (decay * cb).astype(cd)
                own.append(_dot(m, xd))
                dxd.append(_dot(m, dyb, _TN))
            # the gradients of dt x within the chunk and of dt x decayed to
            # the chunk's end, and the scan's output once more (no skip)
            dxd, dxw = _over_heads(dxd, lane, p), _dot(Bm, dafter_cd)
            scan = _over_heads(own, lane, p) + wide_into * _dot(Cm, before)
            gx = dxd + dxw * wide_out
            dx_ref[0, rows, lo:hi] = (gx * wide_dt + dskip * dys).astype(cd)
            dd_ref[0, :, lo:hi] += jnp.sum(dys * xs, axis=0, keepdims=True)
            ind = ind_ref[:, lo:hi]
            # cum_i takes every decay that ends at i and gives every one
            # that starts there, each weighed with the products' own
            # operands; the chunk's last also takes dS . S of the state
            # handed on
            handed = jnp.sum(ind.astype(f32) * dend[:, lo:hi], axis=1,
                             keepdims=True)
            sum_cum = sum_cum + jnp.where(last, handed, 0.0) + _head_sums(
                dys * scan - xd.astype(f32) * dxd - xw.astype(f32) * dxw, ind)
            sum_dt = sum_dt + _head_sums(gx * xs, ind)
            du = (dys * wide_into).astype(cd)
            dB = dB + _dot(xw, dafter_cd, _NT)
            dC = dC + _dot(du, before, _NT)
            dbefore = dafter * wide_into[q - 1:q, :] + _dot(Ct, du)
            dstate[:, lo:hi] = dbefore
            dend[:, lo:hi] = jnp.sum(dbefore * before.astype(f32), axis=0,
                                     keepdims=True)
        dcb = dcb.astype(cd)
        dc_ref[0, rows, :] = (dC + _dot(dcb, Bm)).astype(dc_ref.dtype)
        db_ref[0, rows, :] = (dB + _dot(dcb, Cm, _TN)).astype(db_ref.dtype)
        drows_ref[0, 0, :r, rows] = sum_dt
        drows_ref[0, 0, r:, rows] = sum_cum


def _params(interpret):
    return {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT)}


def _specs(x, B, h, g, q, rows, order):
    """Block specs of what a step reads by row block: ``x``-like (b, S,
    H P), per-head columns (b, S, 2 H), per-head rows (b, G, rows, S),
    ``B``-like (b, S, G N), a per-lane row (1, H P), the chunks' states
    (b, S / Q, N, H P). ``order`` maps the grid's innermost index to the
    row block."""
    rp, n = x.shape[2] // g, B.shape[2] // g
    wide = pl.BlockSpec((1, rows, rp), lambda i, j, c: (i, order(c), j))
    cols = pl.BlockSpec((1, rows, 2 * h), lambda i, j, c: (i, order(c), 0))
    across = lambda r: pl.BlockSpec((1, 1, r, rows),
                                    lambda i, j, c: (i, j, 0, order(c)))
    group = pl.BlockSpec((1, rows, n), lambda i, j, c: (i, order(c), j))
    lanes = pl.BlockSpec((1, rp), lambda i, j, c: (0, j))
    states = pl.BlockSpec((1, rows // q, n, rp),
                          lambda i, j, c: (i, order(c), 0, j))
    return wide, cols, across, group, lanes, states


def _layouts(dt, cum, g):
    """``[dt | cum]`` (b, S, 2 H), and ``cum`` with a group's heads as
    rows (b, G, r, S)."""
    b, s, h = dt.shape
    return (jnp.concatenate([dt, cum], axis=2),
            cum.reshape(b, s, g, h // g).transpose(0, 2, 3, 1))


def _rows(s, q):
    k = min(_CHUNKS, s // q)
    while (s // q) % k:
        k -= 1
    return k * q


def _forward(x, dt, cum, B, C, D, g, q, interpret, save):
    b, s, hp = x.shape
    h, n = dt.shape[2], B.shape[2] // g
    r, p, rows = h // g, hp // h, _rows(s, q)
    wide, cols, across, group, lanes, states = _specs(
        x, B, h, g, q, rows, lambda c: c)
    out_specs, out_shape = [wide], [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if save:
        out_specs.append(states)
        out_shape.append(jax.ShapeDtypeStruct((b, s // q, n, hp), x.dtype))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, q=q, p=p, save=save),
        name="ddstore_ssd_fwd",
        grid=(b, g, s // rows),
        in_specs=[wide, cols, across(r), group, group, lanes],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, r * p), jnp.float32)],
        interpret=interpret, **_params(interpret))(
            x, *_layouts(dt, cum, g), B, C, jnp.repeat(D, p)[None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(x, dt, cum, B, C, D, g, q, interpret):
    with jax.named_scope("ssd"):
        return _forward(x, dt, cum, B, C, D, g, q, interpret, False)[0]


def _scan_fwd(x, dt, cum, B, C, D, g, q, interpret):
    with jax.named_scope("ssd"):
        y, states = _forward(x, dt, cum, B, C, D, g, q, interpret, True)
    return y, (x, dt, cum, B, C, D, states)


def _scan_bwd(g, q, interpret, res, dy):
    x, dt, cum, B, C, D, states = res
    b, s, hp = x.shape
    h, n = dt.shape[2], B.shape[2] // g
    r, p, rows = h // g, hp // h, _rows(s, q)
    steps = s // rows
    wide, cols, across, group, lanes, state = _specs(
        x, B, h, g, q, rows, lambda c: steps - 1 - c)
    f32 = jnp.float32
    # lane l of the group's r p belongs to head l // p
    ind = (jnp.arange(r)[:, None]
           == jnp.arange(r * p)[None, :] // p).astype(x.dtype)
    with jax.named_scope("ssd"):
        dx, dB, dC, drows, dD = pl.pallas_call(
            functools.partial(_bwd_kernel, q=q, p=p),
            name="ddstore_ssd_bwd",
            grid=(b, g, steps),
            in_specs=[wide, wide, cols, across(r), group, group, lanes,
                      state,
                      pl.BlockSpec((r, r * p), lambda i, j, c: (0, 0))],
            out_specs=[wide, group, group, across(2 * r),
                       pl.BlockSpec((1, 1, r * p), lambda i, j, c: (i, 0, j))],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct(B.shape, B.dtype),
                       jax.ShapeDtypeStruct(C.shape, C.dtype),
                       jax.ShapeDtypeStruct((b, g, 2 * r, s), f32),
                       jax.ShapeDtypeStruct((b, 1, hp), f32)],
            scratch_shapes=[pltpu.VMEM((n, r * p), f32),
                            pltpu.VMEM((1, r * p), f32)],
            interpret=interpret, **_params(interpret))(
                x, dy.astype(x.dtype), *_layouts(dt, cum, g), B, C,
                jnp.repeat(D, p)[None], states, ind)
        back = lambda t: t.transpose(0, 3, 1, 2).reshape(b, s, h)
        return (dx, back(drows[:, :, :r]), back(drows[:, :, r:]), dB, dC,
                dD.reshape(b, h, p).sum((0, 2)))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, D: jax.Array, chunk: int, *,
        interpret: Optional[bool] = None) -> jax.Array:
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
    if interpret is None:
        interpret = not on_chip()
    f32, cd = jnp.float32, x.dtype
    with jax.named_scope("ssd"):
        dt = dt.astype(f32)
        cum = jnp.cumsum((dt * A.astype(f32)).reshape(b, s // chunk, chunk, h),
                         axis=2).reshape(b, s, h)
    y = _scan(x.reshape(b, s, h * p), dt, cum,
              B.astype(cd).reshape(b, s, g * n),
              C.astype(cd).reshape(b, s, g * n), D.astype(f32), g, chunk,
              interpret)
    return y.reshape(b, s, h, p)
