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
* a causal call spends nothing on pairs the mask kills: its grid is the
  enumeration of its live blocks (scalar-prefetched step tables: a dead
  block is no step, so it costs no DMA and no step), and a block that
  straddles the diagonal is computed in static strips that end at the
  diagonal, while the fetched block stays large. ``causal_geometry``
  counts what a call computes and fetches from the same functions the
  kernels' set-up runs on, and ``utils.profile.counters()`` keeps it per
  compiled shape. ``causal=False`` lowers as it always did;
* the block-diffusion training mask (:class:`BlockDiffusion`: a noised and
  a clean half in one sequence) is a description, never a dense array: its
  grid is the same kind of enumeration over the mask's three live
  quadrants, and only the blocks its edges cross are masked;
* a sliding window (``window=W``: a query sees its own key and the W - 1
  before it) is a second, lower edge of the causal mask: a block wholly
  before the window is no step either, and a block either edge crosses is
  computed in strips that end at both;
* the forward takes a step's scores a sub-tile at a time against a
  reference max known before them (``_flash_kernel``): keys on sublanes,
  queries on lanes, no block-wide f32 scores written out; a step whose
  sums pass e**``_TAU`` is taken again by the two-pass body, and lane 1 of
  its statistics output counts that (``forward_fallbacks``);
* the backward is one kernel on the key-major walk (key blocks outer,
  query blocks inner): each live block pair's probabilities and their
  cotangent are formed once and feed dv and dk, accumulated for the key
  block, and dq, accumulated in a float32 buffer that holds the whole
  head's rows in VMEM from the head's first grid step to its last
  (``_bwd_kernel``);
* on CPU (tests) the identical kernel runs in interpreter mode;
* the two kernels carry stable names (``ddstore_flash_fwd``, and
  ``ddstore_flash_dkv`` for the backward, which writes dq, dk and dv): a
  device trace names the custom call after them, on one chip and inside
  the ring's branches.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_chip
from ..utils import profile

NEG_INF = float("-inf")


# Rows (forward) or columns (backward) of one compute strip inside a block
# that straddles the causal diagonal, per kernel, and the grain its other
# side is cut to (the lane width). The FETCHED block stays large; what is
# computed of it ends at the diagonal. Chosen on the chip (PERF.md section
# 6): neither kernel gains from a narrower strip what twice the bodies
# cost to trace and lower at every start.
_STRIP = {"ddstore_flash_fwd": 512, "ddstore_flash_dkv": 512}
# Heads wider than 128 (width 256): the backward strips at 256, where the
# dk/dv half of it gained 5 % at S=2048 (4.54 against 4.78 ms a call of
# 160 heads) and 1.5 % at S=8192, the forward nothing; its blocks are
# square, so one diagonal position and two more bodies.
_STRIP_WIDE = dict(_STRIP, ddstore_flash_dkv=256)
_LANES = 128
# A diagonal block's position against the diagonal (first row - first
# column) is one of a few static values, each a kernel body of its own;
# beyond this many bodies the kernel masks whole blocks by a traced shift.
_MAX_DIAG_BODIES = 32

# Codes of a grid step (``_steps``): what to run, and whether the step
# opens or closes its row of the accumulation.
_NOTHING, _INTERIOR, _DIAGONAL = 0, 1, 2
_FIRST, _LAST = 1 << 16, 1 << 17


def _tiles(num, den, n):
    """``min(max(num, 0) // den, n)``: a count of whole tiles."""
    return np.minimum(np.maximum(num, 0) // den, n)


def _live_cols(row_lo, rows, col_lo, cols, n):
    """Causal classification of ``n`` column tiles of width ``cols`` from
    position ``col_lo`` against the rows ``[row_lo, row_lo + rows)``:
    ``(n_past, n_live)``. Tiles ``[0, n_past)`` lie wholly in the past (no
    mask), ``[n_past, n_live)`` straddle the diagonal, the rest are dead.
    THE classification of the forward kernel (k/v stream) at both
    sizes, blocks over the grid and lane-wide tiles inside a block, and of
    the counter."""
    return (_tiles(row_lo - col_lo + 1, cols, n),
            _tiles(row_lo + rows - 1 - col_lo + cols, cols, n))


def _live_rows(col_lo, cols, row_lo, rows, n):
    """The same classification seen from the columns ``[col_lo, col_lo +
    cols)`` over ``n`` row tiles of height ``rows`` from ``row_lo`` (the
    backward kernel's q stream): ``(first_live, first_past)``. Tiles ``[0,
    first_live)`` are dead, ``[first_live, first_past)`` straddle the
    diagonal, ``[first_past, n)`` lie wholly in the past."""
    return (_tiles(col_lo - row_lo, rows, n),
            _tiles(col_lo + cols - 1 - row_lo + rows - 1, rows, n))


class BlockDiffusion(NamedTuple):
    """The training mask of block diffusion (BD3-LM, arXiv:2503.09573
    section 4; SDAR, arXiv:2510.06303) as a description: the sequence is
    ``[noised ; clean]``, two halves of ``half`` positions each, both cut
    into blocks of ``block``. With i, j positions inside their halves: a
    noised query i sees noised key j iff ``j // block == i // block`` (its
    own block) and clean key j iff ``j // block < i // block`` (the blocks
    before it); a clean query i sees clean key j iff ``j // block <= i //
    block``; no clean query sees a noised key. ``half**2 + half * block``
    live pairs of ``(2 half)**2``. ``block = 1`` makes every noised
    position the last of a causal sequence whose earlier tokens are
    clean."""
    block: int
    half: int


# A tile's quadrant under the mask: noised queries on noised keys (their
# own block: a band on the diagonal), noised on clean (the blocks before),
# clean on clean (those and its own). Clean on noised is dead throughout.
_NN, _NC, _CC = 0, 1, 2


def block_diffusion_mask(mask: BlockDiffusion) -> np.ndarray:
    """The dense ``(2 half, 2 half)`` boolean form of ``mask``, rows
    queries: for the references and the tests, never for the kernels."""
    blk = np.arange(mask.half) // mask.block
    same, before = blk[None, :] == blk[:, None], blk[None, :] < blk[:, None]
    return np.block([[same, before], [np.zeros_like(same), same | before]])


def _mask_tiles(kind, block, q_lo, rows, k_lo, cols):
    """``(live, full)`` of tiles of one quadrant ``kind``, rows ``[q_lo,
    q_lo + rows)`` by columns ``[k_lo, k_lo + cols)`` in their halves' own
    coordinates (arrays broadcast): whether any pair of the tile is live,
    and whether all are. THE classification under the mask, of blocks over
    the grid and of lane-wide tiles inside a block, and of the counter."""
    qb_lo, qb_hi = q_lo // block, (q_lo + rows - 1) // block
    kb_lo, kb_hi = k_lo // block, (k_lo + cols - 1) // block
    diagonal = (kb_lo <= qb_hi) & (qb_lo <= kb_hi)
    one_block = (kb_lo == qb_hi) & (qb_lo == kb_hi)
    live = np.select([kind == _NN, kind == _NC], [diagonal, kb_lo < qb_hi],
                     kb_lo <= qb_hi)
    full = np.select([kind == _NN, kind == _NC], [one_block, kb_hi < qb_lo],
                     kb_hi <= qb_lo)
    return live, full


class FlashGeometry(NamedTuple):
    """How one kernel tiles one call: what its set-up builds the grid, the
    index maps and the strips from, and what that costs per batch*head
    (``utils.profile.counters()`` keeps it per compiled shape)."""
    sq: int
    sk: int
    block_q: int
    block_k: int
    sub_q: int                 # rows of a compute tile in a diagonal block
    sub_k: int                 # and its columns
    q_offset: int
    kv_offset: int
    stream: str                # innermost grid axis: "k" (fwd), "q" (bwd)
    pairs_needed: int          # (query, key) pairs with key <= query
    pairs_computed: int        # pairs in the tiles a body runs over
    grid_steps: int
    steps_fetching_dead: int   # DMAs of a streamed block no live step uses
    mask: Optional[BlockDiffusion] = None   # set: this mask, not the causal
    blocks_live: int = 0       # under ``mask`` or ``window``: blocks holding
    #                            a live pair
    window: Optional[int] = None   # set: causal with keys > query - window


def _steps(geo):
    """``_enumerate`` (``_enumerate_masked`` under a mask,
    ``_enumerate_window`` under a window) for the call ``geo``
    describes."""
    if geo.mask is not None:
        return _enumerate_masked(geo.mask, geo.block_q, geo.block_k,
                                 geo.stream)
    if geo.window is not None:
        return _enumerate_window(geo.sq, geo.sk, geo.block_q, geo.block_k,
                                 geo.q_offset, geo.kv_offset, geo.window,
                                 geo.stream)
    return _enumerate(geo.sq, geo.sk, geo.block_q, geo.block_k,
                      geo.q_offset, geo.kv_offset, geo.stream)


@functools.lru_cache(maxsize=256)
def _enumerate(sq, sk, bq, bk, q_offset, kv_offset, stream):
    """The grid of one causal kernel, enumerated: only the steps whose
    block is live, in the kernel's order (a row that is dead throughout
    keeps one step, to write its zeros). Returns ``(outer, inner, code,
    shifts)``: per step the two block indices and ``_INTERIOR`` or
    ``_DIAGONAL + v`` (``v`` indexes ``shifts``, the static values of first
    row - first column over the diagonal blocks) with ``_FIRST`` /
    ``_LAST`` set on a row's ends."""
    nq, nk = sq // bq, sk // bk
    if stream == "k":
        outer, inner = np.indices((nq, nk))
        iq, ik = outer, inner
        n_past, n_live = _live_cols(q_offset + iq * bq, bq, kv_offset, bk,
                                    nk)
        live, past = ik < n_live, ik < n_past
        keep = live | ((n_live == 0) & (ik == 0))
    else:
        outer, inner = np.indices((nk, nq))
        ik, iq = outer, inner
        first_live, first_past = _live_rows(kv_offset + ik * bk, bk,
                                            q_offset, bq, nq)
        live, past = iq >= first_live, iq >= first_past
        keep = live | ((first_live == nq) & (iq == nq - 1))
    shift = q_offset + iq * bq - kv_offset - ik * bk
    shifts, variant = np.unique(shift[live & ~past], return_inverse=True)
    code = _codes(keep, live, past, variant)
    return tuple(np.asarray(a[keep], np.int32) for a in (outer, inner, code)
                 ) + (tuple(int(x) for x in shifts),)


def _codes(keep, live, full, variant):
    """The step codes of ``_enumerate`` / ``_enumerate_masked`` from the
    blocks kept as steps, the live ones, those live throughout and the
    index of each partly live one's body."""
    code = np.full(live.shape, _NOTHING)
    code[live & full] = _INTERIOR
    code[live & ~full] = _DIAGONAL + variant.ravel()
    kept = np.cumsum(keep, axis=1)
    code |= np.where(keep & (kept == 1), _FIRST, 0)
    code |= np.where(keep & (kept == kept[:, -1:]), _LAST, 0)
    return code


@functools.lru_cache(maxsize=256)
def _enumerate_masked(mask, bq, bk, stream):
    """``_enumerate`` under a :class:`BlockDiffusion` mask: the steps of
    one kernel over the ``2 half`` positions, only the blocks holding a
    live pair, none of which crosses a half (``bq`` and ``bk`` divide
    ``half``). A block the mask's edges cross is ``_DIAGONAL + v``, ``v``
    indexing the last result: the static ``(quadrant, first row - first
    column)`` of those blocks, both in their halves' coordinates."""
    n = 2 * mask.half
    nq, nk = n // bq, n // bk
    outer, inner = np.indices((nq, nk) if stream == "k" else (nk, nq))
    iq, ik = (outer, inner) if stream == "k" else (inner, outer)
    q_clean, k_clean = iq * bq >= mask.half, ik * bk >= mask.half
    q_lo, k_lo = iq * bq % mask.half, ik * bk % mask.half
    kind = np.where(k_clean, np.where(q_clean, _CC, _NC), _NN)
    live, full = _mask_tiles(kind, mask.block, q_lo, bq, k_lo, bk)
    live &= k_clean | ~q_clean
    # every query has its own block's keys and every key its own block's
    # queries: no row of the grid is dead throughout
    assert live.any(axis=1).all()
    keys, variant = np.unique((kind * n + q_lo - k_lo)[live & ~full],
                              return_inverse=True)
    code = _codes(live, live, full, variant)
    variants = tuple((int(key + mask.half) // n,
                      int(key + mask.half) % n - mask.half) for key in keys)
    return tuple(np.asarray(a[live], np.int32) for a in (outer, inner, code)
                 ) + (variants,)


def _window_tiles(row_lo, rows, col_lo, cols, window):
    """``(live, full)`` of tiles, rows ``[row_lo, row_lo + rows)`` by
    columns ``[col_lo, col_lo + cols)`` in global positions (arrays
    broadcast), under a sliding window: a pair is live iff ``0 <= row - col
    < window``. THE classification under a window, of blocks over the grid
    and of lane-wide tiles inside a block, and of the counter."""
    near = row_lo - (col_lo + cols - 1)        # the least row - col of a tile
    far = row_lo + rows - 1 - col_lo           # and the greatest
    return (far >= 0) & (near < window), (near >= 0) & (far < window)


@functools.lru_cache(maxsize=256)
def _enumerate_window(sq, sk, bq, bk, q_offset, kv_offset, window, stream):
    """``_enumerate`` under a sliding window of ``window`` keys: only the
    blocks holding a live pair are steps (a row dead throughout keeps one,
    to write its zeros, as there); a block wholly before the window is dead
    like one wholly after the diagonal. A block either edge crosses is
    ``_DIAGONAL + v``, ``v`` indexing the last result: the static first row
    - first column of those blocks."""
    nq, nk = sq // bq, sk // bk
    outer, inner = np.indices((nq, nk) if stream == "k" else (nk, nq))
    iq, ik = (outer, inner) if stream == "k" else (inner, outer)
    live, full = _window_tiles(q_offset + iq * bq, bq, kv_offset + ik * bk,
                               bk, window)
    last = 0 if stream == "k" else inner.shape[1] - 1
    keep = live | (~live.any(axis=1, keepdims=True) & (inner == last))
    shift = q_offset + iq * bq - kv_offset - ik * bk
    shifts, variant = np.unique(shift[live & ~full], return_inverse=True)
    code = _codes(keep, live, full, variant)
    return tuple(np.asarray(a[keep], np.int32) for a in (outer, inner, code)
                 ) + (tuple(int(x) for x in shifts),)


class _Edges(NamedTuple):
    """What a strip under a sliding window keeps of its scores: the pairs
    whose column less their row is at most ``hi`` (the diagonal) and at
    least ``lo`` (the window's lower edge), counted from the strip's
    corner; None where that edge cuts nothing of the strip."""
    lo: Any
    hi: Any


def _window_strips(geo, shift):
    """``_strips`` under a window, for a block whose first row lies
    ``shift`` past its first column: ``_runs``' strips, ``(rows, cols,
    edges)`` (``edges`` None: every pair of the strip is live)."""
    w = geo.window
    for rows, cols, _ in _runs(
            geo, lambda *tile: _window_tiles(*tile, w), shift, 0):
        s = shift + rows.start - cols.start
        edges = _Edges(s - w + 1 if rows.start - rows.stop + 1 < s - w + 1
                       else None,
                       s if cols.stop - cols.start - 1 > s else None)
        yield rows, cols, None if edges == (None, None) else edges


class _Band(NamedTuple):
    """What a strip under the mask keeps of its scores: the pairs whose
    column's block less their row's, counted from the strip's corner
    (a block's multiple), is at most ``hi`` and, where ``lo`` is set, at
    least ``lo``. Blocks are ``1 << log2b`` long."""
    lo: Any
    hi: Any
    log2b: int


def _band(kind, shift, log2b):
    """The band of a strip of quadrant ``kind`` whose first row lies
    ``shift`` (static; a block's multiple) past its first
    column."""
    d = shift >> log2b
    return _Band(d if kind == _NN else None, d - (kind == _NC), log2b)


def _runs(geo, classify, row0, col0):
    """The static strips of a block an edge of a mask crosses: each
    ``sub_q`` rows (forward) or ``sub_k`` columns (backward) over the
    contiguous run of lane-wide tiles on its other side that hold a live
    pair, ``(rows, cols, full)`` (``full``: every pair of the run is live).
    ``classify(row_lo, rows, col_lo, cols)`` is the mask's ``(live, full)``
    of tiles, the block's first row and column at ``row0``, ``col0``."""
    bq, bk, tq, tk = geo.block_q, geo.block_k, geo.sub_q, geo.sub_k
    nq, nk = np.arange(bq // tq), np.arange(bk // tk)
    for g in (nq if geo.stream == "k" else nk):
        if geo.stream == "k":
            live, full = classify(row0 + g * tq, tq, col0 + nk * tk, tk)
        else:
            live, full = classify(row0 + nq * tq, tq, col0 + g * tk, tk)
        if not live.any():
            continue
        lo, hi = int(np.argmax(live)), int(len(live) - np.argmax(live[::-1]))
        assert live[lo:hi].all()
        rows, cols = (slice(g * tq, (g + 1) * tq), slice(lo * tk, hi * tk)) \
            if geo.stream == "k" else (
            slice(lo * tq, hi * tq), slice(g * tk, (g + 1) * tk))
        yield rows, cols, bool(full[lo:hi].all())


def _masked_strips(geo, variant):
    """``_strips`` under the mask, for a block of quadrant and shift
    ``variant``: ``_runs``' strips, ``(rows, cols, band)`` (``band`` None:
    every pair of the strip is live)."""
    kind, shift = variant
    block = geo.mask.block
    for rows, cols, full in _runs(
            geo, functools.partial(_mask_tiles, kind, block), max(shift, 0),
            max(-shift, 0)):
        yield rows, cols, None if full else _band(
            kind, shift + rows.start - cols.start, block.bit_length() - 1)


def _strips(geo, shift):
    """The compute strips of a diagonal block whose first row lies
    ``shift`` past its first column: ``(rows, cols, strip_shift)`` as
    static slices of the block. A forward strip is ``sub_q`` rows by
    every ``sub_k``-wide tile up to the last live one; a backward strip is
    ``sub_k`` columns by every ``sub_q``-high tile from the first live one.
    One matmul chain each, masked by its own shift. Under a mask ``shift``
    is a variant of ``_enumerate_masked`` (``_masked_strips``); under a
    window the strips end at both edges (``_window_strips``)."""
    if geo.mask is not None:
        yield from _masked_strips(geo, shift)
        return
    if geo.window is not None:
        yield from _window_strips(geo, shift)
        return
    bq, bk, tq, tk = geo.block_q, geo.block_k, geo.sub_q, geo.sub_k
    if geo.stream == "k":
        for g in range(bq // tq):
            live = int(_live_cols(shift + g * tq, tq, 0, tk, bk // tk)[1])
            if live:
                yield (slice(g * tq, (g + 1) * tq), slice(0, live * tk),
                       shift + g * tq)
    else:
        for c in range(bk // tk):
            first = int(_live_rows(c * tk, tk, shift, tq, bq // tq)[0])
            if first < bq // tq:
                yield (slice(first * tq, bq), slice(c * tk, (c + 1) * tk),
                       shift + first * tq - c * tk)


def _static_diagonal(geo, shifts):
    """Whether the diagonal blocks' strips are few enough to be static
    bodies (else: whole blocks under a traced shift)."""
    strips = geo.block_q // geo.sub_q if geo.stream == "k" \
        else geo.block_k // geo.sub_k
    return len(shifts) * strips <= _MAX_DIAG_BODIES


@functools.lru_cache(maxsize=256)
def causal_geometry(sq: int, sk: int, blocks: Tuple[int, int],
                    sub: Tuple[int, int], q_offset: int = 0,
                    kv_offset: int = 0, stream: str = "k",
                    window: Optional[int] = None) -> FlashGeometry:
    """Geometry of one causal kernel call, counted per batch*head from
    what the kernel's set-up itself runs on (``_steps``, ``_strips``):
    ``pairs_needed`` (what ``benchmarks/ddbench/flops.py`` counts: s(s+1)/2
    at zero offsets), ``pairs_computed`` (interior blocks whole, diagonal
    blocks by their strips, dead blocks nothing), ``grid_steps`` and
    ``steps_fetching_dead``. Under a sliding ``window``, ``pairs_needed``
    is the window's (sum of min(i + 1, window) at zero offsets), and
    ``blocks_live`` the blocks of the grid holding a live pair."""
    (bq, bk), (tq, tk) = blocks, sub
    if window is None:
        needed = np.clip(q_offset + np.arange(sq) - kv_offset + 1, 0,
                         sk).sum()
    else:
        row = q_offset + np.arange(sq) - kv_offset      # in key positions
        needed = np.clip(np.minimum(row, sk - 1)
                         - np.maximum(row - window + 1, 0) + 1, 0, None).sum()
    return _counted(FlashGeometry(sq, sk, bq, bk, tq, tk, q_offset,
                                  kv_offset, stream, int(needed), 0, 0, 0,
                                  window=window))


@functools.lru_cache(maxsize=256)
def mask_geometry(mask: BlockDiffusion, blocks: Tuple[int, int],
                  sub: Tuple[int, int], stream: str = "k") -> FlashGeometry:
    """``causal_geometry`` of one kernel call under ``mask``, counted the
    same way from ``_enumerate_masked`` and ``_masked_strips``;
    ``pairs_needed`` is the mask's live pairs, ``half**2 + half * block``,
    ``grid_steps`` the blocks visited and ``blocks_live`` those that hold a
    live pair."""
    n = 2 * mask.half
    return _counted(FlashGeometry(
        n, n, *blocks, *sub, 0, 0, stream,
        mask.half * (mask.half + mask.block), 0, 0, 0, mask))


def _counted(geo):
    """``geo`` with what its kernel computes, steps over and fetches for
    nothing filled in, and under a mask or a window the blocks of its grid
    that hold a live pair."""
    bq, bk = geo.block_q, geo.block_k
    outer, inner, code, shifts = _steps(geo)
    what = code & (_FIRST - 1)
    if _static_diagonal(geo, shifts):
        in_strips = [sum((r.stop - r.start) * (c.stop - c.start)
                         for r, c, _ in _strips(geo, shift))
                     for shift in shifts]
    else:
        in_strips = [bq * bk] * len(shifts)
    computed = (what == _INTERIOR).sum() * bq * bk + sum(
        in_strips[v - _DIAGONAL] for v in what[what >= _DIAGONAL])
    # The pipeline issues a DMA when the streamed block's index changes;
    # the block then serves every step until the next change. A DMA is
    # spent on the dead when none of the steps it serves is live.
    dma = np.cumsum(np.concatenate([[True], inner[1:] != inner[:-1]]))
    serves_live = np.bincount(dma, weights=what != _NOTHING) > 0
    masked = geo.mask is not None or geo.window is not None
    return geo._replace(pairs_computed=int(computed),
                        grid_steps=len(code),
                        steps_fetching_dead=int((~serves_live[1:]).sum()),
                        blocks_live=int((what != _NOTHING).sum()) if masked
                        else 0)


def _dense_geometry(sq, sk, bq, bk, stream):
    """Non-causal: every block live, one unmasked body a step."""
    return FlashGeometry(sq, sk, bq, bk, bq, bk, 0, 0, stream, sq * sk,
                         sq * sk, (sq // bq) * (sk // bk), 0)


def _sub_tile(block: int, want: int) -> int:
    """Largest multiple of the lane width that divides ``block`` and is <=
    ``want``; the block itself when there is none."""
    for t in range(min(want, block) // _LANES * _LANES, 0, -_LANES):
        if block % t == 0:
            return t
    return block


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = False, q_offset: int = 0,
                  kv_offset: int = 0, scale: Optional[float] = None,
                  mask: Optional[BlockDiffusion] = None,
                  window: Optional[int] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Plain-XLA attention over (..., S, D); returns (out, lse in f32).
    ``mask``: the block-diffusion mask in its dense form, over ``S = 2
    half`` positions. ``window`` (causal only): a query also sees no key
    ``window`` or more positions before it.
    Grouped-query: ``k`` and ``v`` (..., H_kv, S, D) with ``H_kv`` dividing
    ``q``'s H; query head h attends to K/V head ``h // (H / H_kv)`` (K and
    V are repeated here: this is the reference)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if q.ndim >= 3 and k.shape[-3] != q.shape[-3]:
        group = q.shape[-3] // k.shape[-3]
        k, v = (jnp.repeat(t, group, axis=-3) for t in (k, v))
    s = jnp.einsum("...qd,...kd->...qk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if window is not None and not causal:
        raise ValueError("a sliding window is a causal mask's lower edge")
    if causal:
        qpos = q_offset + jnp.arange(q.shape[-2])[:, None]
        kpos = kv_offset + jnp.arange(k.shape[-2])[None, :]
        keep = kpos <= qpos
        if window is not None:
            keep &= kpos > qpos - window
        s = jnp.where(keep, s, NEG_INF)
    if mask is not None:
        s = jnp.where(block_diffusion_mask(mask), s, NEG_INF)
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


def _causal_mask(s, shift, keys_axis=1):
    """Keep ``s[r, c]`` where global key position <= query position:
    ``shift`` = first row's position - first column's. A :class:`_Band`
    for ``shift``: keep what the band keeps (the block-diffusion mask); an
    :class:`_Edges`: what lies between its edges (a sliding window).
    ``keys_axis=0``: ``s`` is transposed, keys on its rows."""
    col, row = (jax.lax.broadcasted_iota(jnp.int32, s.shape, axis)
                for axis in (keys_axis, 1 - keys_axis))
    if isinstance(shift, _Edges):
        col_minus_row = col - row
        keep = [col_minus_row <= shift.hi if shift.hi is not None else None,
                col_minus_row >= shift.lo if shift.lo is not None else None]
        keep = [k for k in keep if k is not None]
        return jnp.where(functools.reduce(jnp.logical_and, keep), s, NEG_INF)
    if isinstance(shift, _Band):
        log2b = jnp.int32(shift.log2b)
        across = (jax.lax.shift_right_logical(col, log2b)
                  - jax.lax.shift_right_logical(row, log2b))
        keep = across <= shift.hi
        if shift.lo is not None:
            keep &= across >= shift.lo
        return jnp.where(keep, s, NEG_INF)
    return jnp.where(col - row <= shift, s, NEG_INF)


def _traced_band(mask, q_lo, k_lo):
    """The band of a whole block from its traced first row and column in
    the sequence of both halves: ``_band`` where quadrant and shift are
    the device's to work out (more variants than bodies)."""
    log2b = mask.block.bit_length() - 1
    q_clean, k_clean = q_lo >= mask.half, k_lo >= mask.half
    d = ((q_lo - jnp.where(q_clean, mask.half, 0))
         - (k_lo - jnp.where(k_clean, mask.half, 0))) >> log2b
    hi = d - (k_clean & ~q_clean).astype(jnp.int32)
    return _Band(jnp.where(k_clean, -2 * mask.half, d), hi, log2b)


class _Step(NamedTuple):
    """One grid step as a kernel body sees it: whether it opens / closes
    its row of the accumulation, and whether it is its head's first / last
    step; ``inner``, the index of the block it streams; ``run(update,
    done=None)``, which calls ``update(rows, cols, shift)`` over what is
    live of the step's block (``shift`` None: no mask) and then
    ``done()``, once, inside the same body; and ``whole()``, the mask of
    the whole block (traced: the same for every step's body)."""
    first: jax.Array
    last: jax.Array
    head_first: jax.Array
    head_last: jax.Array
    inner: jax.Array
    run: Callable
    whole: Callable


_WHOLE = slice(None)


def _grid_kernel(kernel, causal, geo):
    """Adapts ``kernel(step, *refs)`` to the grid ``_call`` builds.

    Non-causal: the full ``(bh, outer, inner)`` grid, one unmasked body a
    step. Causal: the enumerated steps of ``_steps``, their block indices
    and codes read from the scalar-prefetched tables; an interior block
    runs that same single body, a block that straddles the diagonal runs
    the static strips of its position (``_strips``)."""
    if not causal:
        def dense(*refs):
            outer, n_outer = pl.program_id(1), pl.num_programs(1)
            inner, n = pl.program_id(2), pl.num_programs(2)

            def run(update, done=None):
                update(_WHOLE, _WHOLE, None)
                if done:
                    done()
            first, last = inner == 0, inner == n - 1
            kernel(_Step(first, last, first & (outer == 0),
                         last & (outer == n_outer - 1), inner, run,
                         lambda: None), *refs)
        return dense

    _, _, codes, shifts = _steps(geo)
    # No interior block, no interior body: a whole call fetched as one
    # diagonal block never lowers the block-wide scores.
    has_interior = bool(((codes & (_FIRST - 1)) == _INTERIOR).any())
    static = _static_diagonal(geo, shifts)

    def enumerated(outer_ref, inner_ref, code_ref, *refs):
        t = pl.program_id(1)
        code = code_ref[t]
        what = code & (_FIRST - 1)

        def whole():
            iq, ik = outer_ref[t], inner_ref[t]
            if geo.stream == "q":
                iq, ik = ik, iq
            if geo.mask is not None:
                return _traced_band(geo.mask, iq * geo.block_q,
                                    ik * geo.block_k)
            shift = (geo.q_offset + iq * geo.block_q
                     - geo.kv_offset - ik * geo.block_k)
            return shift if geo.window is None else _Edges(
                shift - geo.window + 1, shift)

        def run(update, done=None):
            def body(strips):
                for strip in strips:
                    update(*strip)
                if done:
                    done()

            if has_interior:
                pl.when(what == _INTERIOR)(
                    lambda: body([(_WHOLE, _WHOLE, None)]))
            if static:
                for v, shift in enumerate(shifts):
                    pl.when(what == _DIAGONAL + v)(functools.partial(
                        lambda shift: body(_strips(geo, shift)), shift))
            else:
                pl.when(what >= _DIAGONAL)(
                    lambda: body([(_WHOLE, _WHOLE, whole())]))

        kernel(_Step((code & _FIRST) != 0, (code & _LAST) != 0, t == 0,
                     t == pl.num_programs(1) - 1, inner_ref[t], run, whole),
               *refs)
    return enumerated


def _sub_mask(shift, ro, co):
    """The mask ``shift`` of a strip (``_causal_mask``'s argument) as seen
    from its sub-tile whose corner lies ``ro`` rows and ``co`` columns in
    (both a block's multiple under a :class:`_Band`)."""
    if shift is None:
        return None
    if isinstance(shift, _Band):
        d = (co >> shift.log2b) - (ro >> shift.log2b)
        return _Band(None if shift.lo is None else shift.lo - d,
                     shift.hi - d, shift.log2b)
    if isinstance(shift, _Edges):
        return _Edges(*(None if e is None else e - (co - ro)
                        for e in shift))
    return shift - (co - ro)


def _static_keep(shift, rows, cols):
    """What ``_causal_mask(s, shift)`` keeps of a ``(rows, cols)`` tile, as a
    numpy array; None where the mask is the device's to work out."""
    if shift is None:
        return np.ones((rows, cols), bool)
    lo, hi, log2b = (shift.lo, shift.hi, shift.log2b) \
        if isinstance(shift, _Band) else (
        (shift.lo, shift.hi, 0) if isinstance(shift, _Edges)
        else (None, shift, 0))
    if not all(e is None or isinstance(e, (int, np.integer))
               for e in (lo, hi)):
        return None
    across = (np.arange(cols)[None, :] >> log2b) \
        - (np.arange(rows)[:, None] >> log2b)
    keep = np.ones((rows, cols), bool)
    if hi is not None:
        keep &= across <= hi
    if lo is not None:
        keep &= across >= lo
    return keep


# The forward's one-pass body (``_flash_kernel``). A row group's scores
# are taken a sub-tile at a time and go from the MXU through exp, the row
# sum and the cast straight into the P.V product, against a reference
# known before they are: the row's running max of the earlier steps, or on
# its first the row max of one sub-tile in which each of the group's live
# rows has a live pair. The true max is reduced alongside, and the step
# commits, moved to it, where every row's sum against the reference is at
# most e**_TAU; a step where one is not (a score that far above the
# reference, an overflow, no finite reference) is taken again by the
# two-pass body. e**_TAU (1.4e12) times 64 steps of a row (S = 131,072 at
# the 2048-wide blocks) leaves f32 room for values up to |v| ~ 3e24.
_TAU = 28.0
_ONE_PASS_BOUND = math.exp(_TAU)


def _one_pass_tile(d, block_q, block_k):
    """Queries and keys of the forward's sub-tiles at head width ``d``,
    each width's own since the MXU's share differs. Chosen by the static
    schedule of the described v5e's compiler, bundles of a grid step that
    passes its guard (PERF.md section 6, PR 40): at width 64 the 512 x
    2048 interior takes 3,311 for the two-pass body's 5,213 (512 x 512;
    512 x 256 about 3,700, 256 x 128 about 6,900); at 128 4,524 for 5,203
    (512 x 256; 512 x 512 about 4,900); at 256 the 1024 x 1024 interior
    8,199 for 8,697 (1024 x 512)."""
    rows, cols = (512, 512) if d <= 64 else (512, 256) if d <= 128 \
        else (1024, 512)
    return min(rows, block_q), min(cols, block_k)


def _flash_kernel(step, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, fb_scr, m_new, l_new, acc_new, retry, *, scale,
                  tile):
    """The forward, in one pass. Its state lies transposed, queries on
    lanes: the running max, sum and fallback count ``(1, block_q)``, the
    numerator ``(D, block_q)``; scores are ``(keys, queries)`` tiles, so a
    row's statistics are sums and maxima over sublanes and ``m`` is a lane
    vector that broadcasts down a tile for nothing. A step's row groups
    write the new state of their rows to ``m_new``, ``l_new`` and
    ``acc_new``, which are copied in once every group of the step passed
    its guard; otherwise ``retry`` is set and the whole block is taken
    again by the two-pass body, one body for every kind of step."""
    @pl.when(step.first)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        fb_scr[:] = jnp.zeros_like(fb_scr)

    retry[0] = 0

    def scores_t(q_t, cols, shift):
        s = jnp.dot(k_ref[0, cols, :], q_t,
                    preferred_element_type=jnp.float32) * scale
        return s if shift is None else _causal_mask(s, shift, keys_axis=0)

    def pv_t(cols, p):
        # (D, queries): the keys' axis of v and of p contracted
        return jax.lax.dot_general(
            v_ref[0, cols, :], p.astype(v_ref.dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    def two_pass(rows, cols, shift, into):
        m_out, l_out, acc_out = into
        s = scores_t(q_ref[0, rows, :].T, cols, shift)      # (tk, tq)
        m_prev = m_scr[:, rows]                              # (1, tq)
        m = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        # rows with everything masked so far keep m = -inf; safe_m keeps
        # the subtraction finite and exp(-inf - 0) = 0 zeroes their p
        safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(s - safe_m)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
        l_out[:, rows] = l_scr[:, rows] * corr + jnp.sum(p, axis=0,
                                                         keepdims=True)
        acc_out[:, rows] = acc_scr[:, rows] * corr + pv_t(cols, p)
        m_out[:, rows] = m

    pending = []                        # (rows, guard) of this step's groups

    def one_pass(rows, cols, shift):
        r0, r1, _ = rows.indices(q_ref.shape[1])
        c0, c1, _ = cols.indices(k_ref.shape[1])
        for ro in range(r0, r1, tile[0]):
            group(slice(ro, min(ro + tile[0], r1)), c0, c1,
                  _sub_mask(shift, ro - r0, 0))

    def group(rows, c0, c1, shift):
        keep = _static_keep(shift, rows.stop - rows.start, c1 - c0)
        subs = []                       # (columns, mask, kept) live ones
        for co in range(0, c1 - c0, tile[1]):
            cw = min(tile[1], c1 - c0 - co)
            kept = None if keep is None else keep[:, co:co + cw]
            if kept is None or kept.any():
                subs.append((slice(c0 + co, c0 + co + cw),
                             None if kept is not None and kept.all()
                             else _sub_mask(shift, 0, co), kept))
        if keep is None:
            ref, dead_rows = 0, False   # a traced mask: the guard decides
        else:
            live = keep.any(axis=1)
            refs = [i for i, (_, _, kept) in enumerate(subs)
                    if (kept.any(axis=1) >= live).all()]
            if not refs:                # no sub-tile each live row sees
                two_pass(rows, slice(c0, c1), shift,
                         (m_new, l_new, acc_new))
                pending.append((rows, None))
                return
            ref, dead_rows = refs[0], not live.all()
        q_t = q_ref[0, rows, :].T                            # (D, tq)
        m_old = m_scr[:, rows]                               # (1, tq)
        s_ref = scores_t(q_t, subs[ref][0], subs[ref][1])
        m_ref = jnp.where(jnp.isfinite(m_old), m_old,
                          jnp.max(s_ref, axis=0, keepdims=True))
        # a row the step does not reach keeps m = -inf and adds nothing
        base = jnp.where(jnp.isfinite(m_ref), m_ref, 0.0) if dead_rows \
            else m_ref
        # the true max is reduced alongside; no exp waits on it
        l, acc, top = 0.0, 0.0, m_ref
        for i in [ref] + [i for i in range(len(subs)) if i != ref]:
            cols, mask, _ = subs[i]
            s = s_ref if i == ref else scores_t(q_t, cols, mask)
            top = jnp.maximum(top, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - base)
            l = l + jnp.sum(p, axis=0, keepdims=True)
            acc = acc + pv_t(cols, p)
        # The state moves to the true max: lse = m + log(l) with l summed
        # against the reference reads 2.3e-5 from the float32 reference on
        # the chip, against the true max 1e-6 (PERF.md section 6, PR 40).
        safe_top = jnp.where(jnp.isfinite(top), top, 0.0)
        corr = jnp.where(jnp.isfinite(top), jnp.exp(base - safe_top), 0.0)
        m_new[:, rows] = top
        l_new[:, rows] = (l_scr[:, rows] + l) * corr
        acc_new[:, rows] = (acc_scr[:, rows] + acc) * corr
        pending.append((rows, jnp.all(l <= _ONE_PASS_BOUND)))

    def done():
        def commit():
            for rows, _ in pending:
                m_scr[:, rows] = m_new[:, rows]
                l_scr[:, rows] = l_new[:, rows]
                acc_scr[:, rows] = acc_new[:, rows]

        guards = [ok for _, ok in pending if ok is not None]
        if guards:
            ok = functools.reduce(jnp.logical_and, guards)
            pl.when(ok)(commit)

            @pl.when(jnp.logical_not(ok))
            def _():
                retry[0] = 1
        else:
            commit()
        pending.clear()

    step.run(one_pass, done)

    @pl.when(retry[0] != 0)
    def _():
        two_pass(_WHOLE, _WHOLE, step.whole(), (m_scr, l_scr, acc_scr))
        fb_scr[:] = fb_scr[:] + 1.0

    @pl.when(step.last)
    def _():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).T.astype(o_ref.dtype)
        m = m_scr[:]
        lse = jnp.where(jnp.isfinite(m), m + jnp.log(l), NEG_INF)
        # lane 0 the lse, lane 1 the steps a row took the two-pass fallback
        shape = lse_ref.shape[:0:-1]                         # (128, tq)
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        lse_ref[0] = jnp.where(lane == 1, jnp.broadcast_to(fb_scr[:], shape),
                               jnp.broadcast_to(lse, shape)).T


def _call(kernel, name, causal, geo, operands, resident, out_shape, scratch,
          interpret, groups=None, heads=None, whole=None, vmem=None):
    """One flash ``pallas_call``. ``resident`` says per operand whether its
    block rides the outer grid axis (it stays in VMEM across a row; the
    outputs do) or the inner one (it is streamed); ``whole`` says per
    output whether its block is rather all of a head's rows, in VMEM from
    the head's first grid step to its last (the outer axis then carries an
    accumulation too, and is ``"arbitrary"``). ``vmem``: the call's VMEM
    limit in bytes (None: the compiler's default). ``groups`` says per
    operand how many of the grid's ``bh`` share one of its heads
    (grouped-query attention: query head ``bh`` reads K/V head ``bh //
    group``, so no repeated K or V is ever in HBM); default 1 throughout.

    Head-major (``heads`` None) every operand and output is ``(b h, S, .)``
    and a head is a leading entry. Sequence-major, ``heads`` is the query
    heads' count: q, k, v, ``do`` and the outputs of their kind are ``(b, S,
    h d)``, as a projection writes them, and a head is a column block of
    whole lanes, ``(bh // heads, row block, (bh % heads) // group)``; the
    128-lane statistics (``lse``, ``dta``) stay ``(b h, S, 128)``. A kernel
    body sees the same ``(1, rows, d)`` tile either way.

    Grid steps run one after another on the core at ~0.35 us each before
    any work, and a step whose streamed block is new pays its DMA, so the
    fetched block is large and, causal, only live blocks are steps at all
    (PERF.md section 6, PR 26, has what the sizes were chosen from).

    The ``pallas_call`` is built once per static configuration
    (``_pallas``), so the layers of a model that make the same call trace
    each kernel once."""
    if causal:
        operands = tuple(jnp.asarray(t) for t in _steps(geo)[:3]) \
            + tuple(operands)
    return _pallas(kernel.func, tuple(sorted(kernel.keywords.items())),
                   name, causal, geo,
                   tuple(x.shape for x in operands[3 if causal else 0:]),
                   tuple(resident), tuple(out_shape), tuple(scratch),
                   interpret, groups, heads,
                   tuple(whole or (False,) * len(out_shape)), vmem)(*operands)


@functools.lru_cache(maxsize=256)
def _pallas(fn, keywords, name, causal, geo, shapes, resident, out_shape,
            scratch, interpret, groups, heads, whole, vmem):
    """``_call``'s ``pallas_call`` of ``functools.partial(fn,
    **keywords)`` over operands of ``shapes`` (the step tables first,
    causal)."""
    bhs = shapes[0][0] * (heads or 1)     # the query side's b h
    q_side = geo.stream == "k"     # which side the outer axis walks

    def block(shape, on_outer, group=1, head=False):
        rows = shape[1] if head else \
            geo.block_q if on_outer == q_side else geo.block_k
        if heads is not None and shape[0] != bhs:
            # (b, S, h d): told from the statistics by the leading
            # dimension; where they agree (one head) so do the addresses
            width = shape[-1] * group // heads
            # bh is never negative: lax.div / lax.rem, since `//` and `%`
            # lower through sign handling that tripled these kernels'
            # lowering time (0.23 s a forward + backward call for 0.08)
            lead = lambda bh: jax.lax.div(bh, jnp.int32(heads))
            col = lambda bh: jax.lax.div(jax.lax.rem(bh, jnp.int32(heads)),
                                         jnp.int32(group))
        else:
            width = shape[-1]
            lead = (lambda bh: bh) if group == 1 else (lambda bh: bh // group)
            col = lambda bh: 0
        if head:
            def index(bh, *_):
                return lead(bh), 0, col(bh)
        elif causal:
            def index(bh, t, outer, inner, code):
                return lead(bh), (outer if on_outer else inner)[t], col(bh)
        else:
            def index(bh, o, i):
                return lead(bh), o if on_outer else i, col(bh)
        return pl.BlockSpec((1, rows, width), index)

    in_specs = [block(shape, on_outer, group)
                for shape, on_outer, group in zip(
                    shapes, resident, groups or (1,) * len(shapes))]
    out_specs = [block(o.shape, True, head=h) for o, h in zip(out_shape,
                                                                whole)]
    if causal:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(bhs, len(_steps(geo)[0])),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch)
        sems = ("parallel", "arbitrary")
    else:
        n_q, n_k = geo.sq // geo.block_q, geo.sk // geo.block_k
        grid_spec = pl.GridSpec(
            grid=(bhs,) + ((n_q, n_k) if q_side else (n_k, n_q)),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch)
        sems = ("parallel", "arbitrary" if any(whole) else "parallel",
                "arbitrary")
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(dimension_semantics=sems,
                                                vmem_limit_bytes=vmem)}
    return pl.pallas_call(
        _grid_kernel(functools.partial(fn, **dict(keywords)), causal, geo),
        name=name, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret, **params)


def _dims(q, k, seq_major):
    """``(b, h, h_kv, sq, sk, d)`` of a call's q and k: ``(B, S, H, D)``
    sequence-major, ``(B, H, S, D)`` head-major."""
    b, d = q.shape[0], q.shape[-1]
    if seq_major:
        (sq, h), (sk, h_kv) = q.shape[1:3], k.shape[1:3]
    else:
        (h, sq), (h_kv, sk) = q.shape[1:3], k.shape[1:3]
    return b, h, h_kv, sq, sk, d


def _flat(x, seq_major):
    """A kernel's view of a head tensor: ``(b, S, h d)`` or ``(b h, S,
    d)``, a reshape that moves nothing either way."""
    return x.reshape(x.shape[:2] + (-1,)) if seq_major \
        else x.reshape((-1,) + x.shape[2:])


def _fwd_impl(q, k, v, causal, scale, geo, interpret, seq_major):
    """Runs the forward kernel; returns (out as q lies, lse (b, h, S), the
    steps each row took the two-pass fallback (b, h, S))."""
    b, h, h_kv, sq, _, d = _dims(q, k, seq_major)
    bhs = b * h
    qf = _flat(q, seq_major)
    bq = geo.block_q
    kernel = functools.partial(_flash_kernel, scale=scale,
                               tile=_one_pass_tile(d, bq, geo.block_k))
    scratch = [pltpu.VMEM((1, bq), jnp.float32),         # running max
               pltpu.VMEM((1, bq), jnp.float32),         # running denom
               pltpu.VMEM((d, bq), jnp.float32),         # running numerator
               pltpu.VMEM((1, bq), jnp.float32),         # fallbacks
               # the step's new state, until its guard passes
               pltpu.VMEM((1, bq), jnp.float32),
               pltpu.VMEM((1, bq), jnp.float32),
               pltpu.VMEM((d, bq), jnp.float32),
               pltpu.SMEM((1,), jnp.int32)]              # retry the step
    out_f, lse_f = _call(
        kernel, "ddstore_flash_fwd",
        causal, geo, (qf, _flat(k, seq_major), _flat(v, seq_major)),
        (True, False, False),
        [jax.ShapeDtypeStruct(qf.shape, q.dtype),
         # lse carries a 128-lane dim so its block is (block_q, 128)-tile-
         # aligned for the TPU lowering; lane 0 is the value, lane 1 the
         # fallback count.
         jax.ShapeDtypeStruct((bhs, sq, 128), jnp.float32)], scratch,
        interpret, (1, h // h_kv, h // h_kv), h if seq_major else None)
    return (out_f.reshape(q.shape), lse_f[..., 0].reshape(b, h, sq),
            lse_f[..., 1].reshape(b, h, sq))


def _bwd_kernel(step, q_ref, k_ref, v_ref, do_ref, dta_ref, dq_ref, dk_ref,
                dv_ref, dq_acc, dk_acc, dv_acc, *, scale):
    """dq, dk and dv of one head, a k/v block at a time, streaming q blocks
    (the recompute-p flash backward): per live tile ``s = q k^T``, ``p`` and
    ``dp = do v^T`` are formed once and feed all three, five products where
    a dq kernel of its own would take ``q k^T`` and ``do v^T`` again.

    dk and dv accumulate in the k/v block's scratch over its row of steps.
    dq accumulates in ``dq_acc``, float32 and the head's every row, zeroed
    at the head's first grid step and written out at its last (``dq_ref``
    is the whole head's block, which stays in VMEM as long). The q-side
    streams (q, do, dta) re-fetch every grid step (their block index rides
    the innermost loop), so the per-row residual scalars come packed into
    one 128-lane ``dta`` (lane 0 c = delta - dlse with delta = rowsum(do *
    o); lane 1 lse): one streamed side input instead of two."""
    @pl.when(step.head_first)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(step.first)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    bq = q_ref.shape[1]
    q_lo = step.inner * bq              # the streamed block's first row

    def update(rows, cols, shift):
        q, k = q_ref[0, rows, :], k_ref[0, cols, :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if shift is not None:
            s = _causal_mask(s, shift)
        lse = dta_ref[0, rows, 1:2]                          # (tq, 1)
        # Fully-masked rows have lse = -inf; exp(s - safe_lse) is then
        # exp(-inf - big) = 0 for every column: no full-block select.
        p = jnp.exp(s - jnp.where(jnp.isfinite(lse), lse, 1e30))
        do = do_ref[0, rows, :]
        dv_acc[cols, :] = dv_acc[cols, :] + jnp.dot(
            p.astype(do.dtype).T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v_ref[0, cols, :].T,
                     preferred_element_type=jnp.float32)
        # ds = p * (dp - c) with c = delta - dlse packed in lane 0.
        ds = (p * (dp - dta_ref[0, rows, :1])).astype(q.dtype)
        dk_acc[cols, :] = dk_acc[cols, :] + jnp.dot(
            ds.T, q, preferred_element_type=jnp.float32) * scale
        r0, r1, _ = rows.indices(bq)
        at = pl.ds(pl.multiple_of(q_lo + r0, math.gcd(bq, r0)), r1 - r0)
        dq_acc[at, :] = dq_acc[at, :] + jnp.dot(
            ds, k, preferred_element_type=jnp.float32) * scale

    step.run(update)

    @pl.when(step.last)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(step.head_last)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, geos, interpret, seq_major):
    return _fwd_impl(q, k, v, causal, scale, geos[0], interpret,
                     seq_major)[:2]


def _flash_fwd(q, k, v, causal, scale, geos, interpret, seq_major):
    out, lse, _ = _fwd_impl(q, k, v, causal, scale, geos[0], interpret,
                            seq_major)
    # Named for a rematerialised caller: under
    # ``save_only_these_names("flash_out", "flash_lse")`` the backward
    # pass finds both saved and this kernel does not run a second time
    # (q, k and v are the caller's to recompute). Identity otherwise.
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    # Residual is the THIN (B, H, S) lse — the kernel's 128-lane output
    # is tile-alignment scaffolding and holding it across fwd→bwd would
    # cost 128x the activation memory (~1 GiB at the S=8192 LM config).
    return (out, lse), (q, k, v, out, lse)


def _flash_bwd(causal, scale, geos, interpret, seq_major, res, g):
    q, k, v, out, lse = res
    do, dlse = g
    # The backward streams the whole q side innermost, the forward k and
    # v: each takes its own block shapes.
    _, g_bwd = geos
    b, h, h_kv, sq, sk, d = _dims(q, k, seq_major)
    group = h // h_kv
    bhs = b * h
    # Per-row residual scalars packed into ONE 128-lane tensor: lane 0
    # carries c = delta - dlse (delta = rowsum(do*o); the lse cotangent
    # folds into the same term since ds = p*(dp - delta + dlse)), lane 1
    # carries lse. stack+pad lowers to a single fused 128-lane write —
    # per-lane .at[].set constructions each cost a full-tensor
    # dynamic-update-slice pass (~2 ms/layer on v5e, profiled).
    qf, kf, vf, dof = (_flat(t, seq_major) for t in (q, k, v, do))
    if seq_major:
        # what moves to the statistics' order is the (b, S, h) floats,
        # never do or out
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1).transpose(0, 2, 1).reshape(bhs, sq)
    else:
        delta = jnp.sum(dof.astype(jnp.float32)
                        * out.reshape(bhs, sq, d).astype(jnp.float32),
                        axis=-1)
    c = delta - dlse.reshape(bhs, sq).astype(jnp.float32)
    dta = jnp.pad(jnp.stack([c, lse.reshape(bhs, sq)], axis=-1),
                  ((0, 0), (0, 0), (0, 126)))
    operands = (qf, kf, vf, dof, dta)
    groups = (1, group, group, 1, 1)
    heads = h if seq_major else None

    # dk and dv come out a QUERY head (the grid's bh): a K/V head's are the
    # sum over its group's, taken outside the kernel in float32.
    at_q_heads = qf.shape[:1] + (sk, qf.shape[2])
    _, vmem = _bwd_vmem(sq, d, q.dtype)
    if vmem > _VMEM_CAP:
        raise ValueError(
            f"the flash backward holds a head's dq in VMEM: {sq} rows of "
            f"width {d} ask {vmem} bytes, more than {_VMEM_CAP}; split the "
            f"sequence over chips (``ring_attention``)")
    dq, dk, dv = _call(
        functools.partial(_bwd_kernel, scale=scale), "ddstore_flash_dkv",
        causal, g_bwd, operands, (False, True, True, False, False),
        [jax.ShapeDtypeStruct(qf.shape, q.dtype),
         jax.ShapeDtypeStruct(at_q_heads, k.dtype),
         jax.ShapeDtypeStruct(at_q_heads, v.dtype)],
        [pltpu.VMEM((sq, d), jnp.float32),          # the head's dq
         pltpu.VMEM((g_bwd.block_k, d), jnp.float32),
         pltpu.VMEM((g_bwd.block_k, d), jnp.float32)], interpret, groups,
        heads, whole=(True, False, False), vmem=vmem)
    if group > 1:
        grouped, axis = ((b, sk, h_kv, group, d), 3) if seq_major \
            else ((b, h_kv, group, sk, d), 2)
        dk, dv = (t.reshape(grouped).astype(jnp.float32).sum(axis=axis)
                  .astype(t.dtype) for t in (dk, dv))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


_flash.defvjp(_flash_fwd, _flash_bwd)


# VMEM: what the backward's blocks, k/v side and streamed, and the values
# of its body take at the most (twice the v5e compiler's default scoped
# limit; at 1024 x 1024 blocks they pass the default by up to 2.1 MB on the
# described chip), and the most a backward call asks for, of the v5e's 128
# MiB (``ops/moe_gmm.py`` and ``moe_combine.py`` ask as much).
_VMEM_BLOCKS = 32 * 1024 * 1024
_VMEM_CAP = 100 * 1024 * 1024


def _bwd_vmem(sq: int, d: int, dtype) -> Tuple[int, int]:
    """``(dq_vmem_bytes, vmem_limit)`` of the backward at ``sq`` query rows
    of head width ``d`` in ``dtype``: the head's float32 dq buffer, and the
    call's limit, the blocks' beside it and the whole-head dq block's two
    pipeline buffers."""
    resident = sq * d * 4
    return resident, (_VMEM_BLOCKS + resident
                      + 2 * sq * d * jnp.dtype(dtype).itemsize)


def _fit_block(block: int, s: int, grain: int = 8) -> int:
    """Largest multiple of ``grain`` (8: the sublane tile) that divides
    ``s`` and is <= ``block`` (0 if none — i.e. s is not a multiple of
    it)."""
    block = min(block, s)
    for b in range(block - block % grain, grain - 1, -grain):
        if s % b == 0:
            return b
    return 0


def _default_blocks(causal, sq, sk, d, q_offset, kv_offset, masked=False):
    """``((block_q, block_k) forward, (block_q, block_k) backward)`` from
    what a call can see; PERF.md section 6 has the v5e times they were
    chosen from. Heads wider than 128 take 1024 x 1024 throughout (the
    backward 3 to 58 % longer at 512 x 1024, 1024 x 512, 2048 x 1024 and
    1024 x 2048). Otherwise a causal call of at most 2048 x 2048, none of
    whose blocks would lie wholly in the past, is fetched whole by the
    backward and in up to 1024 rows by the
    forward (the whole 2048 rows put the forward within 1 % of the 16 MiB
    of VMEM a kernel may take): nothing runs as an interior body, whose
    block-wide scores would not fit VMEM at these sizes, and almost every
    grid step is work. Otherwise 512 x 2048 below S=8192; at it and beyond
    the non-causal forward takes 1024 x 1024 (2048-wide q blocks exceed
    VMEM there), the causal forward stays at 512 x 2048: its diagonal
    strips want the width; the backward takes 1024 x 2048 (2.0-2.4 % less
    time than 1024 x 1024 at widths 64 and 128, causal or not; 2048 x 2048
    exceeds VMEM). A call under a block-diffusion mask or a sliding window
    (``masked``) takes the causal sizes of its ``2 half`` (or S)
    positions, the short whole-call form apart, but for the backward's,
    which stay 1024 x 1024 there: 2048-wide key blocks meet the mask's
    edges at more positions than the kernel has static bodies for, and
    take 2.4 to 2.7 times as long."""
    if d > 128:
        # Chosen on the chip at width 256 (PERF.md section 6, PR 27): every
        # q/k/v/do/acc tile is twice as deep, 512 x 2048 and wider do not
        # fit VMEM at S=8192, and of what fits 1024 x 1024 is the fastest
        # for every kernel at S=2048 and S=8192 alike.
        return (1024, 1024), (1024, 1024)
    if causal and not masked and max(sq, sk) <= 2048:
        short = (min(sq, 1024), sk), (sq, sk)
        if not any(_INTERIOR in _enumerate(sq, sk, *blocks, q_offset,
                                           kv_offset, "k")[2] & (_FIRST - 1)
                   for blocks in short):
            return short
    if sq < 8192:
        return (512, 2048), (512, 2048)
    return ((512, 2048) if causal else (1024, 1024)), (
        (1024, 1024) if masked else (1024, 2048))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, q_offset: int = 0,
                    kv_offset: int = 0, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    bwd_blocks: Optional[Tuple[int, int]] = None,
                    interpret: Optional[bool] = None, layout: str = "bhsd",
                    mask: Optional[BlockDiffusion] = None,
                    window: Optional[int] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Pallas flash attention over (B, H, S, D); returns (out, lse).

    ``window`` (with ``causal``): a sliding window, query position i sees
    key positions ``i - window < j <= i`` (its own and the ``window - 1``
    before it). The kernels step over the blocks that hold such a
    pair and no others: a block wholly before the window is no grid step
    and fetches nothing, as one wholly after the diagonal, and only the
    strips the window's two edges cross are masked. A window no shorter
    than every query's causal reach is the causal call itself.

    ``mask``: the block-diffusion training mask as a description
    (:class:`BlockDiffusion`; q, k and v are then the ``2 half`` positions
    ``[noised ; clean]``, and ``causal`` and the offsets stay unset). The
    kernels step over the blocks that hold a live pair and no others,
    and mask the strips the mask's edges cross; blocks are fitted to the
    half, so none lies in two quadrants. The block length is a power of two
    of at most a lane tile, so every strip starts on a block's edge.

    ``layout`` says how the caller's q, k and v lie, as an einsum's
    subscripts would: ``"bhsd"``, or ``"bshd"`` for (B, S, H, D) as a
    projection writes them. The kernels then read each head as a column
    block of ``(B, S, H D)`` and write ``out`` and the three gradients the
    same way, so nothing is transposed on either side; that needs heads of
    whole lanes (``D % 128 == 0``: narrower, a head is no legal block of
    that array, and the caller transposes). ``lse`` is (B, H, S) in both.

    Grouped-query attention: ``k`` and ``v`` may have ``H_kv`` heads with
    ``H_kv`` dividing H; query head h reads K/V head ``h // (H / H_kv)``
    through the kernels' index maps (neither kernel sees a repeated K or
    V; the backward writes a query head's dk, dv, summed over each group
    outside the kernel).

    Differentiable: the backward pass is the recompute-p flash backward as
    one Pallas kernel (``ddstore_flash_dkv``) that streams Q blocks past
    each K/V block and writes dq, dk and dv, a head's dq accumulated in
    VMEM over all of its steps, so training never materializes S×S.
    Sequence
    lengths must be multiples of 8 (callers pad; the data layer's budgets
    already guarantee static shapes). On non-TPU backends the same
    kernels run in interpreter mode.

    block_q/block_k (forward) and ``bwd_blocks`` = (block_q, block_k) of
    the backward are upper bounds, fitted per call to the largest divisor
    of the sequence length that is a multiple of 8. The defaults come from
    the call's own shape (``_default_blocks``); the backward's follow an
    explicit block_q/block_k unless overridden. What a causal call then
    computes and fetches is ``causal_geometry``'s to say, and is recorded
    per kernel under ``utils.profile.counters()["flash_geometry"]``, with
    the forward's body (``one_pass`` or ``two_pass``), its sub-tile and
    ``_TAU``, and the backward's ``dq`` (``"resident"``), the bytes of its
    dq buffer (``dq_vmem_bytes``) and the VMEM limit it sets
    (``vmem_limit``).
    """
    return _flash(q, k, v, *_plan(
        q, k, v, causal=causal, q_offset=q_offset, kv_offset=kv_offset,
        scale=scale, block_q=block_q, block_k=block_k, bwd_blocks=bwd_blocks,
        interpret=interpret, layout=layout, mask=mask, window=window))


def forward_fallbacks(q: jax.Array, k: jax.Array, v: jax.Array,
                      **kwargs) -> jax.Array:
    """The forward kernel of ``flash_attention(q, k, v, **kwargs)``, and
    of it, per query row (B, H, S), how many of the row's grid steps its
    row group took the two-pass body again for (the one-pass body's guard
    failed): 0 on ordinary inputs."""
    causal, scale, geos, interpret, seq_major = _plan(q, k, v, **kwargs)
    return _fwd_impl(q, k, v, causal, scale, geos[0], interpret,
                     seq_major)[2]


def _plan(q, k, v, *, causal=False, q_offset=0, kv_offset=0, scale=None,
          block_q=None, block_k=None, bwd_blocks=None, interpret=None,
          layout="bhsd", mask=None, window=None):
    """``flash_attention``'s checks and geometry: the static arguments of
    ``_flash`` after q, k and v."""
    if layout not in ("bhsd", "bshd"):
        raise ValueError(f"layout {layout!r}: 'bhsd' or 'bshd'")
    seq_major = layout == "bshd"
    b, h, h_kv, sq, sk, d = _dims(q, k, seq_major)
    if seq_major and d % _LANES:
        raise ValueError(
            f"layout 'bshd' at head width {d}: a head must be whole lanes "
            f"(a multiple of {_LANES}) to be a block of (B, S, H D); "
            f"transpose to (B, H, S, D)")
    if h % h_kv or v.shape != k.shape:
        raise ValueError(f"{h} query heads over {h_kv} key and "
                         f"{v.shape[2 if seq_major else 1]} value heads: "
                         f"the K/V heads must be alike and divide the "
                         f"query heads")
    grain, fit_to = 8, (sq, sk)
    if window is not None:
        if not causal or mask is not None or window < 1:
            raise ValueError(f"window={window}: a sliding window is a "
                             f"causal call's lower edge, at least one key")
        if window >= q_offset + sq - kv_offset:
            window = None          # it cuts nothing: the causal call
    if mask is not None:
        if causal or q_offset or kv_offset:
            raise ValueError("a block-diffusion mask is not causal and "
                             "takes no offsets")
        if mask.block & (mask.block - 1) or not 0 < mask.block <= _LANES \
                or mask.half % mask.block or sq != sk or sq != 2 * mask.half:
            raise ValueError(
                f"{mask} over ({sq},{sk}) positions: the block length must "
                f"be a power of two of at most {_LANES} that divides the "
                f"half, and q and k both halves long")
        grain, fit_to = max(8, mask.block), (mask.half, mask.half)
    fwd, bwd = _default_blocks(causal or mask is not None, sq, sk, d,
                               q_offset, kv_offset,
                               mask is not None or window is not None)
    # An explicit block_q / block_k bounds both kernels, as ever.
    fwd = (block_q or fwd[0], block_k or fwd[1])
    if bwd_blocks is None:
        bwd_blocks = (block_q or bwd[0], block_k or bwd[1])
    elif len(bwd_blocks) != 2 or any(bl < 8 for bl in bwd_blocks):
        raise ValueError(f"bwd_blocks: (block_q, block_k), each >= 8 (TPU "
                         f"sublane tile), got {bwd_blocks}")
    # Block sizes are upper bounds: fit each to the largest multiple of 8
    # (Mosaic sublane tile) that divides the sequence. Any seq length
    # divisible by 8 therefore works with the big TPU-tuned defaults
    # (e.g. sq=640 fits block_q=320); a misaligned length fails with the
    # same error on every backend, not just at TPU lowering time.
    block_q, block_k = (_fit_block(bl, s_, grain)
                        for bl, s_ in zip(fwd, fit_to))
    bwd_blocks = tuple(_fit_block(bl, s_, grain) for bl, s_
                       in zip(bwd_blocks, fit_to))
    if not (block_q and block_k and all(bwd_blocks)):
        raise ValueError(f"seq lens {fit_to} must be multiples of {grain} "
                         f"(TPU tile alignment)")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = not on_chip()
    # One geometry a kernel, from what this call can see; the kernels'
    # set-up runs on it and the process's counters keep it per shape.
    geos = []
    for name, stream, bq, bk in (
            ("ddstore_flash_fwd", "k", block_q, block_k),
            ("ddstore_flash_dkv", "q") + bwd_blocks):
        kind, counted = "causal" if causal else "full", (
            "pairs_needed", "pairs_computed", "grid_steps",
            "steps_fetching_dead")
        if causal or mask is not None:
            # A strip is _STRIP of the side its accumulator lives on; the
            # other side is cut at the lane width, up to the diagonal.
            strip = (_STRIP_WIDE if d > 128 else _STRIP)[name]
            sub = (_sub_tile(bq, strip), _sub_tile(bk, _LANES)) \
                if stream == "k" else (
                _sub_tile(bq, _LANES), _sub_tile(bk, strip))
            if window is not None:
                geo = causal_geometry(sq, sk, (bq, bk), sub, q_offset,
                                      kv_offset, stream, window)
                kind = f"window{window}"
                counted += ("blocks_live",)
            elif mask is None:
                geo = causal_geometry(sq, sk, (bq, bk), sub, q_offset,
                                      kv_offset, stream)
            else:
                geo = mask_geometry(mask, (bq, bk), sub, stream)
                kind = f"blockdiff{mask.block}"
                counted += ("blocks_live",)
        else:
            geo = _dense_geometry(sq, sk, bq, bk, stream)
        counts = {f: getattr(geo, f) for f in counted}
        if name == "ddstore_flash_fwd":
            tile = _one_pass_tile(d, bq, bk)
            counts.update(body="one_pass", tau=_TAU,
                          tile=f"{tile[0]}x{tile[1]}")
        else:
            dq_bytes, vmem = _bwd_vmem(sq, d, q.dtype)
            counts.update(dq="resident", dq_vmem_bytes=dq_bytes,
                          vmem_limit=vmem)
        profile.count_geometry(
            name, f"{kind} bh{b * h} "
            f"q{sq}+{geo.q_offset} k{sk}+{geo.kv_offset} d{d} "
            f"blocks {bq}x{bk} sub {geo.sub_q}x{geo.sub_k} {layout} "
            f"kv{b * h_kv}", counts)
        geos.append(geo)
    # an enumerated grid, causal or under the mask: ``_call``'s one switch
    return causal or mask is not None, scale, tuple(geos), interpret, \
        seq_major
