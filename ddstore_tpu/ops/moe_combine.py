"""The expert layer's way back from its experts' rows, as one Pallas kernel
(``ddstore_moe_combine``) that reads the rows of the held pairs and no
others:

    y[t] = sum_j w[t, j] rows[rank[t, j]]       (T, d) float32

over the pairs ``(t, j)`` whose row ``0 <= rank[t, j] < live`` is among the
trip's rows; the others add nothing and are never read. ``rows`` (C, d) are
the grouped product's rows of one trip in its layout (``models/moe.py``):
the pairs sorted by held expert, ``sizes`` (G,) rows a group from row 0,
``live = sizes.sum()``; without weights every term is taken once (the
cotangent of the gather to the experts' rows).

The rows are sorted by expert and, inside a group, by token; so the held
pairs of a tile of ``tm`` tokens are one contiguous run of rows a group.
Before the kernel, a few small ``jnp`` operations over ``rank`` (T k G
comparisons, no sort, no gather of rows) give each tile's runs: the first
chunk of ``_CHUNK`` rows and the number of chunks of each, and each pair's
row in the tile's stage (:func:`_runs`). A grid step is a tile: it starts
the DMAs of the next tile's runs, a whole chunk each (a DMA moves whole
tiles of the rows' layout: a chunk is one), into the other of two stages in
VMEM, waits for its own, and places the stage's rows onto their tokens with
the MXU: the tile's (tm, 128) one-hot matrix a block of 128 staged rows,
holding each pair's weight where its token meets its row, times the block.
A token meets a staged row once at most, so the product adds each pair's
term alone: exact with unit weights, and with float32 weights split into
three bfloat16 parts (each product exact, accumulated in float32: the
float32 weight to its last bit). A tile's stage holds its live rows and at
most two chunks more a run; the rest of its last block is a row of some
earlier tile, multiplied by zero.

Tiles are a function of the shape alone (:func:`tile`); the chip chose
them (PERF.md section 6, PR 38). Off the chip the kernel runs in interpret
mode.
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

# What ``counters()["moe_layout"]`` reports of an expert layer's way back.
COMBINE = "pallas"

# Rows a DMA: the rows' tile in HBM along the rows (8 for 16- and 32-bit
# types alike); a slice of rows that is not whole tiles cannot be moved.
_CHUNK = 8
# Staged rows a product: the MXU's depth.
_BLOCK = 128
_VMEM_LIMIT = 100 * 1024 * 1024
_VMEM_BUDGET = 64 * 1024 * 1024


def tile(tokens: int, top_k: int, groups: int, d: int, dtype,
         parts: int) -> int:
    """Tokens a grid step. Every token of a tile meets every row of its
    stage in the products, so a smaller tile multiplies fewer rows, and a
    larger one takes fewer steps (about 0.85 us each at 16 runs a tile:
    their DMAs); the chip's sweep at SDAR's and LFM2's shapes put the
    balance at 128 tokens where the weights take three products a block and
    256 where one does (PERF.md section 6, PR 38). The largest power of two
    up to that which divides ``tokens`` and whose two stages, in ``dtype``
    and ``d`` wide, fit :data:`_VMEM_BUDGET`; at least 8, or all the
    tokens where none divides them."""
    size = jnp.dtype(dtype).itemsize
    tm = 128 if parts > 1 else 256
    while tm > 8 and (tokens % tm or 2 * _stage_rows(tm, top_k, groups)
                      * d * size > _VMEM_BUDGET):
        tm //= 2
    return tm if tokens % tm == 0 else tokens


def _stage_rows(tm: int, k: int, g: int) -> int:
    """Rows of one stage: a tile's held pairs (at most ``min(k, g)`` a
    token) in whole chunks, and two chunks more a run, in whole blocks."""
    chunks = tm * min(k, g) // _CHUNK + 2 * min(g, tm * k) + 1
    return -(-chunks * _CHUNK // _BLOCK) * _BLOCK


def _runs(rank, sizes, tm):
    """``(runs, pos)``: each tile's runs as the kernel takes them by scalar
    prefetch (one int32 vector: the first chunk of every (tile, group) run,
    its number of chunks, then every tile's chunks in all), and each pair's
    row in its tile's stage, -1 where the pair is not live."""
    t, k = rank.shape
    g = sizes.shape[0]
    tiles = t // tm
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    live = ends[-1]
    valid = (rank >= 0) & (rank < live)
    group = (rank[..., None] >= ends).sum(-1, dtype=jnp.int32)
    mine = (group[..., None] == jnp.arange(g)) & valid[..., None]
    count = mine.reshape(tiles, tm * k, g).sum(1, dtype=jnp.int32)
    lo = ends - sizes + jnp.cumsum(count, 0) - count
    first = lo // _CHUNK
    chunks = jnp.where(count > 0, (lo + count - 1) // _CHUNK - first + 1, 0)
    # a pair's row in the stage: its group's chunks come after the earlier
    # groups' in the tile
    shift = (jnp.cumsum(chunks, 1) - chunks - first) * _CHUNK
    pos = rank + (mine.reshape(tiles, tm * k, g)
                  * shift[:, None, :]).sum(-1).reshape(t, k)
    runs = jnp.concatenate([first.reshape(-1), chunks.reshape(-1),
                            chunks.sum(1)])
    return runs, jnp.where(valid, pos, -1)


def _kernel(runs, rows_hbm, pos_ref, *refs, tm, k, g, weighted, parts):
    w_ref, refs = (refs[0], refs[1:]) if weighted else (None, refs)
    out_ref, stage, sem, *acc = refs
    # the sum in float32: in the output, or beside it where that is not
    acc = acc[0] if acc else out_ref
    i, n = pl.program_id(0), pl.num_programs(0)
    slot = lax.rem(i, 2)

    def copy(into, src, dst):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(pl.multiple_of(src * _CHUNK, _CHUNK), _CHUNK)],
            stage.at[into, pl.ds(pl.multiple_of(dst * _CHUNK, _CHUNK),
                                 _CHUNK)],
            sem.at[into])

    def fetch(tile, into):
        """Start the DMAs of ``tile``'s runs into stage ``into``."""
        def run(e, placed):
            first = runs[tile * g + e]
            count = runs[(n + tile) * g + e]

            def one(c, _):
                copy(into, first + c, placed + c).start()
                return 0

            lax.fori_loop(0, count, one, 0)
            return placed + count

        lax.fori_loop(0, g, run, 0)

    @pl.when(i == 0)
    def _():
        # the rows past a tile's last chunk in its last block are read
        # (times zero): finite, once the stages are
        stage[...] = jnp.zeros_like(stage)
        fetch(0, 0)

    @pl.when(i + 1 < n)
    def _():
        fetch(i + 1, 1 - slot)

    total = runs[2 * n * g + i]

    def wait(c, _):
        copy(slot, 0, 0).wait()
        return 0

    lax.fori_loop(0, total, wait, 0)

    acc[...] = jnp.zeros_like(acc)
    pos = pos_ref[...]
    w = w_ref[...] if weighted else None
    # float32 rows (off the cells) in one exact product
    precision = (lax.Precision.HIGHEST if stage.dtype == jnp.float32
                 else None)

    def block(b, _):
        at = pl.multiple_of(b * _BLOCK, _BLOCK)
        rows = stage[slot, pl.ds(at, _BLOCK), :]
        cols = at + lax.broadcasted_iota(jnp.int32, (tm, _BLOCK), 1)
        if weighted:
            meet = jnp.zeros((tm, _BLOCK), jnp.float32)
            for j in range(k):
                meet = jnp.where(pos[:, j:j + 1] == cols, w[:, j:j + 1],
                                 meet)
        else:
            meet = jnp.zeros((tm, _BLOCK), jnp.bool_)
            for j in range(k):
                meet = meet | (pos[:, j:j + 1] == cols)
            meet = meet.astype(jnp.float32)
        y = acc[...]
        for _ in range(parts):
            part = meet.astype(rows.dtype)
            y += lax.dot_general(
                part, rows, (((1,), (0,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32)
            meet = meet - part.astype(jnp.float32)
        acc[...] = y
        return 0

    lax.fori_loop(0, -(-total * _CHUNK // _BLOCK), block, 0)
    if acc is not out_ref:
        out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _combine(rows, rank, sizes, weights, dtype, interpret):
    c, d = rows.shape
    t, k = rank.shape
    g = sizes.shape[0]
    weighted = weights is not None
    # float32 weights in the rows' 16-bit type: three parts hold all 24
    # bits; unit weights and float32 rows need one
    parts = 3 if weighted and jnp.dtype(rows.dtype).itemsize < 4 else 1
    tm = tile(t, k, g, d, rows.dtype, parts)
    if c % _CHUNK:
        rows = jnp.pad(rows, ((0, -c % _CHUNK), (0, 0)))
    runs, pos = _runs(rank, sizes, tm)
    pairs = pl.BlockSpec((tm, k), lambda i, runs: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, k=k, g=g, weighted=weighted,
                          parts=parts),
        name="ddstore_moe_combine",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t // tm,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), pairs]
            + ([pairs] if weighted else []),
            out_specs=pl.BlockSpec((tm, d), lambda i, runs: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, _stage_rows(tm, k, g), d), rows.dtype),
                pltpu.SemaphoreType.DMA((2,))]
            + ([] if dtype == jnp.float32
               else [pltpu.VMEM((tm, d), jnp.float32)])),
        out_shape=jax.ShapeDtypeStruct((t, d), dtype),
        interpret=interpret,
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT)}),
    )(runs, rows, pos, *([weights.astype(jnp.float32)] if weighted else []))


def moe_combine(rows: jax.Array, rank: jax.Array, sizes: jax.Array,
                weights: Optional[jax.Array] = None, *,
                dtype=jnp.float32,
                interpret: Optional[bool] = None) -> jax.Array:
    """``rows`` (C, d) of one trip, ``rank`` (T, k) each pair's row counted
    from the trip's first (before or past it: not here), ``sizes`` (G,) the
    rows of each group of the trip from row 0, ``weights`` (T, k) or none
    (each term once): ``y[t] = sum_j weights[t, j] rows[rank[t, j]]`` over
    the pairs with ``0 <= rank < sizes.sum()``, summed in float32 and given
    in ``dtype``, (T, d), reading those rows alone."""
    if interpret is None:
        interpret = not on_chip()
    return _combine(rows, rank.astype(jnp.int32), sizes, weights,
                    jnp.dtype(dtype), interpret)
