"""The expert layer's grouped products: rows sorted by expert times that
expert's matrix, as Pallas kernels.

``lhs`` (C, K) holds the rows of ``G`` groups one after the other from row
0, ``sizes`` (G,) rows each (a traced value: the step's routing), and
``rhs`` (G, K, N) a matrix a group:

    moe_gmm    out[r] = lhs[r] @ rhs[g(r)]                  (C, N)
    its transposed form   out[r] = lhs[r] @ rhs[g(r)].T     (C, K), lhs (C, N)
    moe_tgmm   out[g] = lhs[rows of g].T @ dout[rows of g]  (G, K, N)

The contract is ``jax.lax.ragged_dot``'s, which these replace in
``models/moe.py``: operands in their own type (bfloat16 in a step), float32
accumulation, results in the operands' type, **rows past ``sizes.sum()``
written as zeros**, an empty group costs no product and its block of
``moe_tgmm`` is zero. ``moe_gmm`` is differentiable in ``lhs`` and ``rhs``
(one ``jax.custom_vjp``: the rows' cotangent is the transposed form, the
matrices' ``moe_tgmm``).

The rows are cut into tiles of ``tm``. A tile that straddles groups is
visited once a group, each visit writing its own rows; so the kernels' grid
has a static ``C / tm + G - 1`` row steps, of which the routing fills some:
which group and which tile each step works on, and the rows of the tile that
are its group's, are worked out from ``sizes`` on the device
(:func:`row_steps`: a few dozen small ``jnp`` operations, once for all the
products over one grouping) and reach the kernels by scalar prefetch (one
packed int32 vector a kernel: a call site costs the step's trace, lowering
and SMEM copies by its operands),
as in ``jax.experimental.pallas.ops.tpu.megablox``. Steps past the last are
skipped: their index maps repeat the last step's blocks, so nothing is
fetched for them. ``moe_gmm`` visits the tiles past the last group too, once
each and without fetching, to write their zeros.

``moe_gmm``'s grid is (N tiles, row steps, K tiles), the contraction
innermost into a float32 VMEM accumulator (none where one K tile is the
whole contraction). With the whole contraction in one block, consecutive row
steps of one group find their matrix block already in VMEM: a group's matrix
is read once an N tile whatever ``tm``. ``moe_tgmm``'s is (K tiles, N tiles,
row steps): a group's block accumulates in VMEM over its row steps and is
written once.

Widths are whole lane tiles: :func:`padded` is the width a caller pads a
free width to (Nemotron's 1856 to 1920, with zero columns / rows of the
matrices), and the tiles are a function of the shape alone (:func:`tiles`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_chip

# What ``counters()["moe_layout"]`` reports of an expert layer's products.
PRODUCTS = "pallas"

_LANES = 128
# Rows a tile: a group's last tile is multiplied whole, so a tile costs up to
# its rows a group in products nobody reads, and a short one feeds the MXU
# badly (PERF.md section 6, PR 36: the sweep).
_ROWS = 256
_VMEM_LIMIT = 100 * 1024 * 1024
_VMEM_BUDGET = 80 * 1024 * 1024

_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def padded(width: int) -> int:
    """``width`` in whole lane tiles: the next multiple of 128."""
    return -(-width // _LANES) * _LANES


def _widths(width: int):
    """The tile widths ``width`` can be cut into: its divisors in whole
    lane tiles, ascending; a width that is no multiple of 128 only whole."""
    if width % _LANES:
        return [width]
    lanes = width // _LANES
    return [d * _LANES for d in range(1, lanes + 1) if lanes % d == 0]


def _row_tile(c: int, most: int) -> int:
    """The largest power of two up to ``most`` that divides ``c`` rows (at
    least 8), or all of them where none does."""
    tm = most
    while tm >= 8:
        if c % tm == 0:
            return tm
        tm //= 2
    return c


def _vmem_bytes(form: str, tm: int, tk: int, tn: int, size: int) -> int:
    """What a grid step holds in VMEM: its three blocks (rows x k, rows x n
    and the matrices' k x n, whichever of them is the output) twice, the
    pipeline's two buffers, and the output's block twice more in float32:
    a step's product and the accumulator."""
    out = {"gmm": tm * tn, "gmm_t": tm * tk, "tgmm": tk * tn}[form]
    return 2 * size * (tm * tk + tm * tn + tk * tn) + 2 * 4 * out


def tiles(form: str, c: int, k: int, n: int, dtype) -> Tuple[int, int, int]:
    """``(tm, tk, tn)`` of one product over ``c`` rows with matrices (k, n):
    ``form`` is ``gmm`` (contracts k), ``gmm_t`` (the transposed form,
    contracts n) or ``tgmm`` (contracts the rows). A function of the shape
    and the type alone: the matrices' block as large as :data:`_VMEM_BUDGET`
    holds, whole where it can be (a group's matrix is then fetched once, and
    the contraction needs no accumulator), among the widths' divisors in
    whole lane tiles; the larger contraction first among equals."""
    tm = _row_tile(c, _ROWS)
    size = jnp.dtype(dtype).itemsize
    deep = (lambda tk, tn: tn) if form == "gmm_t" else (lambda tk, tn: tk)
    blocks = sorted(((tk, tn) for tk in _widths(k) for tn in _widths(n)),
                    key=lambda b: (b[0] * b[1], deep(*b)), reverse=True)
    for tk, tn in blocks:
        if _vmem_bytes(form, tm, tk, tn, size) <= _VMEM_BUDGET:
            break
    return tm, tk, tn


def layer_tiles(c: int, d: int, hidden: int, dtype) -> dict:
    """The tiles of an expert layer's products over ``c`` rows, model width
    ``d`` and experts' width ``hidden``: ``{"in": ..., "out": ...}`` (the
    products into the experts' width and out of it), each ``{form: (tm, tk,
    tn)}`` for the forward's ``gmm``, and the backward's ``gmm_t`` and
    ``tgmm``."""
    forms = lambda k, n: {form: tiles(form, c, k, n, dtype)
                          for form in ("gmm", "gmm_t", "tgmm")}
    return {"in": forms(d, hidden), "out": forms(hidden, d)}


class Steps(NamedTuple):
    """The row steps of the products over one grouping of ``C`` rows, as
    the kernels take them by scalar prefetch (:func:`row_steps`): per step
    ``s`` of the ``n = C / tm + G - 1`` a field ``i`` at ``[i n + s]`` of
    one int32 vector, the counts behind the fields. ``rows`` are
    ``moe_gmm``'s (either form): a step's group, the tile it reads, the
    tile it writes, the rows ``[lo, hi)`` of that tile that are the
    group's, then ``[working steps, all steps]``; empty groups have no
    step, and after the groups' steps come those of the tiles no group
    reaches, which write zeros. ``weights`` are ``moe_tgmm``'s: group,
    tile, ``lo``, ``hi``, then ``[steps]``, where every group has a step
    (an empty one with no rows). A step past the last repeats the last
    one's group and tile."""
    rows: jax.Array
    weights: jax.Array


@functools.partial(jax.jit, static_argnums=(1, 2))
def row_steps(sizes: jax.Array, c: int, tm: Optional[int] = None) -> Steps:
    """The steps of ``sizes`` (G,) rows a group over ``c`` rows in tiles of
    ``tm`` (the rule's, where none is given): a few dozen small ``jnp``
    operations on the device, once for every product over these rows; one
    function of the lowered step, called wherever a layer, pass or trip
    walks its rows."""
    tm = tm or _row_tile(c, _ROWS)
    if sizes.ndim != 1 or c % tm:
        raise ValueError(f"sizes {sizes.shape} over {c} rows in tiles of "
                         f"{tm}: want (G,) and whole tiles")
    g, n_tiles = sizes.shape[0], c // tm
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = lax.div(starts, tm)
    touched = jnp.where(sizes > 0, lax.div(ends - 1, tm) - first + 1, 0)
    s = jnp.arange(n_tiles + g - 1, dtype=jnp.int32)

    def walk(visits):
        """Group, tile and rows of every step, each group taking ``visits``
        steps, and their count."""
        stop = jnp.cumsum(visits)
        at = jnp.minimum(s, jnp.maximum(stop[-1] - 1, 0))
        grp = jnp.minimum(
            (at[:, None] >= stop[None, :]).sum(1, dtype=jnp.int32), g - 1)
        tile = jnp.minimum(first[grp] + at - (stop - visits)[grp],
                           n_tiles - 1)
        return (grp, tile, jnp.maximum(starts[grp], tile * tm),
                jnp.minimum(ends[grp], (tile + 1) * tm), stop[-1])

    grp, tile, lo, hi, n_work = walk(touched)
    live_tiles = lax.div(ends[-1] + tm - 1, tm)
    work = s < n_work
    out_tile = jnp.where(
        work, tile, jnp.minimum(live_tiles + s - n_work, n_tiles - 1))
    zero = jnp.zeros_like(lo)
    rows = (grp, tile, out_tile, jnp.where(work, lo, zero),
            jnp.where(work, hi, zero),
            jnp.stack([n_work, n_work + n_tiles - live_tiles]))
    *weights, n_steps = walk(jnp.maximum(touched, 1))
    return Steps(jnp.concatenate(rows),
                 jnp.concatenate([*weights, n_steps[None]]))


def _mine(tile, lo, hi, tm):
    """(tm, 1): which rows of ``tile`` are in ``[lo, hi)``."""
    rows = tile * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (rows >= lo) & (rows < hi)


def _gmm_kernel(steps, lhs_ref, rhs_ref, out_ref, *acc, tm, n, dims):
    s, k, last_k = pl.program_id(1), pl.program_id(2), pl.num_programs(2) - 1
    out_tile, lo, hi = steps[2 * n + s], steps[3 * n + s], steps[4 * n + s]
    work = s < steps[5 * n]

    def product():
        return lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                               preferred_element_type=jnp.float32)

    def write(value):
        # the step's own rows; the others are a neighbouring group's, kept,
        # or on a tile's first visit nobody's yet: zero
        mine = _mine(out_tile, lo, hi, tm)
        value = value.astype(out_ref.dtype)
        new = (s == 0) | (out_tile != steps[2 * n + jnp.maximum(s - 1, 0)])

        @pl.when(new)
        def _():
            out_ref[...] = jnp.where(mine, value, jnp.zeros_like(value))

        @pl.when(jnp.logical_not(new))
        def _():
            out_ref[...] = jnp.where(mine, value, out_ref[...])

    @pl.when(work)
    def _():
        if not acc:
            write(product())
            return

        @pl.when(k == 0)
        def _():
            acc[0][...] = product()

        @pl.when(k > 0)
        def _():
            acc[0][...] += product()

        @pl.when(k == last_k)
        def _():
            write(acc[0][...])

    @pl.when(jnp.logical_not(work) & (s < steps[5 * n + 1]) & (k == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _tgmm_kernel(steps, lhs_ref, dout_ref, out_ref, acc, *, tm, n,
                 mask_lhs):
    s = pl.program_id(2)
    count = steps[4 * n]
    live = s < count
    mine, lo, hi = steps[s], steps[2 * n + s], steps[3 * n + s]
    first = (s == 0) | (steps[jnp.maximum(s - 1, 0)] != mine)
    last = (s == count - 1) | (steps[jnp.minimum(s + 1, n - 1)] != mine)

    @pl.when(live & first)
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(live & (hi > lo))
    def _():
        rows = _mine(steps[n + s], lo, hi, tm)
        a, b = lhs_ref[...], dout_ref[...]
        if mask_lhs:
            a = jnp.where(rows, a, jnp.zeros_like(a))
        else:
            b = jnp.where(rows, b, jnp.zeros_like(b))
        acc[...] += lax.dot_general(a, b, _TN,
                                    preferred_element_type=jnp.float32)

    @pl.when(live & last)
    def _():
        out_ref[...] = acc[...].astype(out_ref.dtype)


def _params(interpret):
    return {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT)}


def _interpret(interpret):
    return not on_chip() if interpret is None else interpret


def _check(lhs, contracted, g, steps, tm):
    """The number of row steps, where the operands meet."""
    if lhs.ndim != 2 or contracted != lhs.shape[1]:
        raise ValueError(f"rows {lhs.shape} do not meet matrices that "
                         f"contract {contracted}")
    if lhs.shape[0] % tm:
        raise ValueError(f"{lhs.shape[0]} rows are not whole tiles of {tm}")
    n = lhs.shape[0] // tm + g - 1
    if steps.rows.shape != (5 * n + 2,) or steps.weights.shape != (4 * n + 1,):
        raise ValueError(f"steps {steps.rows.shape} are not those of "
                         f"{lhs.shape[0]} rows of {g} groups in tiles of "
                         f"{tm}")
    return n


@functools.partial(jax.jit, inline=True, static_argnums=(3, 4, 5))
def _gmm(lhs, rhs, steps, transpose_rhs, interpret, tiling=None):
    """``moe_gmm`` (or its transposed form) over ``steps`` (a
    :class:`Steps`), with the tiles given or the shape's own. Traced once a
    shape, whatever the number of calls: a step holds a product a layer,
    pass and trip, and ``jax.vjp``, ``nn.remat`` and the trips' loop each
    trace theirs again."""
    c = lhs.shape[0]
    g, k, n = rhs.shape
    form = "gmm_t" if transpose_rhs else "gmm"
    tm, tk, tn = tiling or tiles(form, c, k, n, lhs.dtype)
    steps_n = _check(lhs, n if transpose_rhs else k, g, steps, tm)
    if k % tk or n % tn:
        raise ValueError(f"tiles {tk} x {tn} do not divide {k} x {n}")
    # the contraction's tile and the output's; a step with no product
    # keeps the contraction's block the last one fetched
    t_in, t_out = (tn, tk) if transpose_rhs else (tk, tn)
    n_in, n_out = lhs.shape[1] // t_in, (k if transpose_rhs else n) // t_out

    def held(s, i, steps):
        if n_in == 1:
            return i
        return jnp.where(s < steps[5 * steps_n], i, n_in - 1)

    def rhs_block(j, s, i, steps):
        i = held(s, i, steps)
        return (steps[s], j, i) if transpose_rhs else (steps[s], i, j)

    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, n=steps_n,
                          dims=_NT if transpose_rhs else _NN),
        name="ddstore_moe_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_out, steps_n, n_in),
            in_specs=[
                pl.BlockSpec((tm, t_in), lambda j, s, i, steps: (
                    steps[steps_n + s], held(s, i, steps))),
                pl.BlockSpec((None, t_out, t_in) if transpose_rhs
                             else (None, t_in, t_out), rhs_block)],
            out_specs=pl.BlockSpec(
                (tm, t_out),
                lambda j, s, i, steps: (steps[2 * steps_n + s], j)),
            scratch_shapes=[pltpu.VMEM((tm, t_out), jnp.float32)]
            if n_in > 1 else []),
        out_shape=jax.ShapeDtypeStruct((c, n_out * t_out), lhs.dtype),
        interpret=interpret, **_params(interpret))(steps.rows, lhs, rhs)


@functools.partial(jax.jit, inline=True, static_argnums=(3, 4, 5, 6))
def _tgmm(lhs, dout, steps, g, dtype, interpret, tiling=None):
    """``moe_tgmm`` for ``g`` groups over ``steps``, (g, K, N) in
    ``dtype``."""
    c, k = lhs.shape
    n = dout.shape[1]
    tm, tk, tn = tiling or tiles("tgmm", c, k, n, lhs.dtype)
    steps_n = _check(lhs, k, g, steps, tm)
    if dout.ndim != 2 or dout.shape[0] != c:
        raise ValueError(f"{c} rows and a cotangent {dout.shape}")
    if k % tk or n % tn:
        raise ValueError(f"tiles {tk} x {tn} do not divide {k} x {n}")
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, n=steps_n,
                          mask_lhs=tk < tn),
        name="ddstore_moe_tgmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k // tk, n // tn, steps_n),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, s, steps: (
                    steps[steps_n + s], i)),
                pl.BlockSpec((tm, tn), lambda i, j, s, steps: (
                    steps[steps_n + s], j))],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda i, j, s, steps: (steps[s], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((g, k, n), dtype),
        interpret=interpret, **_params(interpret))(steps.weights, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _product(lhs, rhs, steps, interpret):
    return _gmm(lhs, rhs, steps, False, interpret)


def _product_fwd(lhs, rhs, steps, interpret):
    return _gmm(lhs, rhs, steps, False, interpret), (lhs, rhs, steps)


def _product_bwd(interpret, res, dout):
    lhs, rhs, steps = res
    dout = dout.astype(lhs.dtype)
    return (_gmm(dout, rhs, steps, True, interpret),
            _tgmm(lhs, dout, steps, rhs.shape[0], rhs.dtype, interpret),
            None)


_product.defvjp(_product_fwd, _product_bwd)


def moe_gmm(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array, *,
            steps: Optional[Steps] = None, transpose_rhs: bool = False,
            interpret: Optional[bool] = None) -> jax.Array:
    """``lhs`` (C, K) and ``rhs`` (G, K, N): row r of group g times
    ``rhs[g]``, (C, N) in ``lhs``'s type, the groups ``sizes`` (G,) rows
    each from row 0 and the rows past them zero. Differentiable in ``lhs``
    and ``rhs``. With ``transpose_rhs``, ``lhs`` is (C, N) and row r is
    multiplied by ``rhs[g].T``: (C, K); that form is the first's rows'
    cotangent and has no rule of its own. ``steps``: what
    ``row_steps(sizes, C)`` gave, from a caller with several products over
    the same rows (worked out here otherwise)."""
    interpret = _interpret(interpret)
    if steps is None:
        steps = row_steps(sizes, lhs.shape[0])
    if transpose_rhs:
        return _gmm(lhs, rhs, steps, True, interpret)
    return _product(lhs, rhs, steps, interpret)


def moe_tgmm(lhs: jax.Array, dout: jax.Array, sizes: jax.Array, *,
             steps: Optional[Steps] = None,
             interpret: Optional[bool] = None) -> jax.Array:
    """``lhs`` (C, K) and ``dout`` (C, N): ``lhs[rows of g].T @ dout[rows
    of g]`` a group, (G, K, N) in ``lhs``'s type: ``moe_gmm``'s cotangent
    for ``rhs``. An empty group's block is zero; rows past the groups are
    not read."""
    if steps is None:
        steps = row_steps(sizes, lhs.shape[0])
    return _tgmm(lhs, dout, steps, sizes.shape[0], lhs.dtype,
                 _interpret(interpret))
