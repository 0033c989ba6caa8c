"""Ring attention: exact attention over a sequence-parallel mesh axis.

Long-context support the reference lacks entirely (SURVEY §2.2 lists
SP/CP/ring attention as absent). Q, K, V are sharded along the sequence
dimension over the ``sp`` mesh axis; each device keeps its Q chunk
resident and the K/V chunks rotate around the ring with
``jax.lax.ppermute`` (XLA lowers this to ICI neighbor exchanges that
overlap with the per-step attention compute). Per-step partial results
combine with the same online-softmax algebra flash attention uses across
key blocks — each step yields ``(out_i, lse_i)`` and the running pair is
reweighted by ``exp(lse - m)`` — so the result is EXACT attention over the
full sequence, with O(S/n) memory per device and n ring steps.

**What lies where under a causal mask.** Contiguous chunks would give ring
position 0 one chunk to attend and position n-1 all n, and every position
would wait at each ``ppermute`` for the slowest. So a causal sequence lies
on the ring in :func:`balanced_order`: cut into 2n stripes of c = S/2n
tokens, position i holds stripe i followed by stripe 2n-1-i, an early and
a late one. The caller brings q, k, v in that order (the model permutes
its token ids, never an activation: ``models/transformer.py``); out and
lse come back in it. With ``src = (idx - step) % n`` the kv chunk at hand,
every (position, step) pair is then half a chunk-pair of work, 2c² pairs,
in calls whose shapes and masks are static:

* step 0, ``src == idx`` on every position: the causal mask over the 2c
  local rows is the global one (early sees early causally; late sees all
  of early and late causally), one plain causal call;
* every later step, ``src != idx``: two c x c stripe pairs are live and
  neither is masked. The local late stripe sees ``src``'s early one,
  whichever side ``src`` is on; and if ``src < idx`` the local early
  stripe sees ``src``'s early one too (both lie after it, its late stripe
  after both), else the local late stripe sees ``src``'s late one. One
  unmasked call takes both pairs as two batch entries, the traced ``src <
  idx`` selecting the second pair's operands and which stripe's
  accumulator its partial joins (:func:`causal_ring_step`).

So a layer's ring is 1 + (n - 1) kernel calls a pass and no ``cond``. On
TPU each is the Pallas flash kernel at zero offsets (O(block) memory;
without it a step materializes a chunk-by-chunk score matrix, capping
exactly the context length the sp axis exists to extend). Non-TPU backends
default to the same calls on
:func:`ddstore_tpu.ops.attention.mha_reference`. Without a mask every
pair is a whole chunk-pair on every position, and the order does not
matter. ``counters()["ring_geometry"]`` says what each position computes.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import on_chip
from ..ops.attention import flash_attention, mha_reference
from ..utils import profile

__all__ = ["balanced_order", "ring_attention", "ring_self_attention"]


def balanced_order(s: int, n: int) -> np.ndarray:
    """The order a causal sequence of ``s`` tokens lies in on a ring of
    ``n``: ``order[j]`` is the natural index of the token in slot j, so
    ``x[..., order]`` (``jnp.take(x, order, axis)``) lays a natural-order
    sequence out and ``np.argsort(order)`` brings it back. 2n stripes of
    s/2n tokens; position i's slots hold stripe i, then stripe 2n-1-i."""
    if s % (2 * n):
        raise ValueError(f"a balanced ring of {n} needs the sequence cut "
                         f"into {2 * n} equal stripes, got {s} tokens")
    stripes = np.arange(s, dtype=np.int32).reshape(2 * n, s // (2 * n))
    return np.stack([stripes[:n], stripes[n:][::-1]], axis=1).reshape(s)


def _ring_geometry(s: int, n: int, causal: bool, bh: int) -> dict:
    """What every ring position needs and what its calls compute, in
    query-key pairs over ``bh`` batch*heads: the host-side mirror of
    :func:`_ring_body`'s cases (a causal call counts the pairs under its
    mask, an unmasked call its whole rectangle)."""
    rows = s // n
    if causal:
        order = balanced_order(s, n).reshape(n, rows).astype(np.int64)
        needed = [int((order[i] + 1).sum()) for i in range(n)]
        # The causal call over the local rows, then n-1 unmasked calls of
        # two stripe pairs, each rows/2 x rows/2.
        computed = [rows * (rows + 1) // 2
                    + (n - 1) * 2 * (rows // 2) ** 2] * n
    else:
        needed = computed = [rows * s] * n
    return {"n": n, "chunk_rows": rows,
            "order": "balanced" if causal else "any",
            "pairs_needed": [bh * p for p in needed],
            "pairs_computed": [bh * p for p in computed],
            "max_over_mean": max(computed) * n / sum(computed)}


def _combine(*partials):
    """Merge normalized attention partials ``(out, lse)`` over the same
    queries (f32 math). A partial whose ``lse`` is -inf saw no key and
    leaves the others exactly as they are."""
    lses = [lse for _, lse in partials]
    m = functools.reduce(jnp.maximum, lses)
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    ws = [jnp.where(jnp.isfinite(lse), jnp.exp(lse - safe_m), 0.0)
          for lse in lses]
    denom = jnp.maximum(sum(ws), 1e-30)
    out = sum(o.astype(jnp.float32) * w[..., None]
              for (o, _), w in zip(partials, ws)) / denom[..., None]
    lse = jnp.where(jnp.isfinite(m), safe_m + jnp.log(denom), -jnp.inf)
    return out, lse


def causal_ring_step(attend, src_is_earlier, early, late, q, k, v):
    """One step of the causal ring after its first: the kv chunk of
    another ring position, ``src``, against the local q chunk, combined
    into the accumulators ``early`` and ``late``, an ``(out, lse)`` a local
    stripe. Two stripe pairs are live, both unmasked: the local late
    stripe sees ``src``'s early one whichever side ``src`` is on; and
    ``src``'s stripe of one kind is seen by the local stripe of the same
    kind, the early ones if ``src`` is earlier (traced: ``src < idx``),
    else the late ones. So ONE statically-shaped call of ``attend`` takes
    both pairs as two batch entries, the second chosen by a select, and its
    partial joins the accumulator of the other kind with ``lse`` = -inf:
    no ``cond``, nothing masked in the kernel, nothing computed twice."""
    b, c = q.shape[0], q.shape[2] // 2
    pairs = lambda t, first: jnp.concatenate(
        [first, jnp.where(src_is_earlier, t[:, :, :c], t[:, :, c:])], 0)
    out, lse = attend(pairs(q, q[:, :, c:]), pairs(k, k[:, :, :c]),
                      pairs(v, v[:, :, :c]))
    same, same_lse = out[b:], lse[b:]
    early = _combine(early, (same, jnp.where(src_is_earlier, same_lse,
                                             -jnp.inf)))
    late = _combine(late, (out[:b], lse[:b]),
                    (same, jnp.where(src_is_earlier, -jnp.inf, same_lse)))
    return early, late


def _ring_body(q, k, v, idx_chunk, *, axis: str, n: int, causal: bool,
               use_flash: bool):
    """shard_map body: local chunks (B, H, S/n, D), under ``causal`` the
    position's early stripe then its late one (:func:`balanced_order`).
    ``idx_chunk`` is this device's slice of an arange over the ring axis —
    the ring position. NOT ``jax.lax.axis_index``: its lowering computes
    the position from the full device id, which re-binds every mesh axis
    and breaks when this shard_map is nested inside another manual region
    (pp×sp)."""
    idx = idx_chunk[0]
    perm = [(j, (j + 1) % n) for j in range(n)]
    attend = flash_attention if use_flash else mha_reference

    for step in range(n):
        # One scope a ring step (attend, combine, rotate), so a device
        # trace sets the ring's own work apart from the block around it.
        with jax.named_scope("ring_step"):
            if step == 0:
                # src == idx on every position, statically: the local
                # chunk against itself, and the accumulator starts here
                # (under the mask one a stripe, updated apart).
                acc = attend(q, k, v, causal=causal)
                if causal:
                    acc = tuple(zip(*(jnp.split(t, 2, 2) for t in acc)))
            elif causal:
                # After `step` rotations this device holds the kv chunk
                # of ring position (idx - step) mod n, which is not idx.
                acc = causal_ring_step(attend, (idx - step) % n < idx,
                                       *acc, q, k, v)
            else:
                acc = _combine(acc, attend(q, k, v))
            if step < n - 1:
                k = jax.lax.ppermute(k, axis, perm)
                v = jax.lax.ppermute(v, axis, perm)
    if causal:
        acc = tuple(jnp.concatenate(t, 2) for t in zip(*acc))
    return acc[0].astype(q.dtype), acc[1]


@functools.lru_cache(maxsize=64)
def _eager_ring(mesh, bspec, hspec, axis, n, causal, use_flash):
    """Jitted ring shard_map for EAGER callers, cached on everything the
    trace depends on (shapes re-key inside jax.jit itself)."""
    body = functools.partial(_ring_body, axis=axis, n=n, causal=causal,
                             use_flash=use_flash)
    spec = P(bspec, hspec, axis, None)
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, P(axis)),
        out_specs=(spec, P(bspec, hspec, axis)),
        axis_names=frozenset(a for a in (axis, bspec, hspec)
                             if a is not None),
        check_vma=False,
    ))


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   mesh: Mesh, axis: str = "sp", causal: bool = False,
                   batch_axis: Optional[str] = None,
                   heads_axis: Optional[str] = None, impl: str = "auto"
                   ) -> Tuple[jax.Array, jax.Array]:
    """Exact attention over (B, H, S, D) with S sharded over ``axis``.

    ``causal=True`` states two things: the mask, and that the sequence
    dimension of q, k, v is in :func:`balanced_order` (module docstring) —
    ``out`` and ``lse`` come back in it. A caller with a natural-order
    sequence takes ``order = balanced_order(S, n)`` to its inputs and
    ``np.argsort(order)`` to the outputs; a model does better to lay out
    its token ids once (``models/transformer.py``). Without a mask any
    order does, the natural one included.

    Returns ``(out, lse)`` like the ops-level kernels. ``batch_axis``
    optionally shards B over a data-parallel mesh axis (defaults to "dp"
    when the mesh has one); ``heads_axis`` shards H over a tensor-parallel
    axis (sp×tp composition: heads are independent in attention, so each
    tp shard rings only its own heads and the two axes compose without
    any cross-communication). Callable inside jit: shard_map composes.

    impl: "flash" (Pallas kernel per ring step — O(block) memory),
    "xla" (mha_reference), or "auto" (flash on TPU, xla elsewhere). The
    flash kernel needs equal chunks (under ``causal``, stripes) that are
    multiples of 8 and raises otherwise, on "auto" as well.
    """
    n = mesh.shape[axis]
    if batch_axis is None and "dp" in mesh.shape:
        batch_axis = "dp"
    bspec = batch_axis if (batch_axis and mesh.shape.get(batch_axis, 1) > 1) \
        else None
    hspec = heads_axis if (heads_axis
                           and mesh.shape.get(heads_axis, 1) > 1) else None
    # Nesting (pp×sp): when called from inside another shard_map (e.g. a
    # pipeline stage manual over pp/dp), the inner shard_map must use the
    # CONTEXT abstract mesh, and axes that context already split manually
    # (dp inside the pipeline body) must drop out of the specs — the
    # arrays in hand are already local chunks along them.
    sm_mesh = mesh
    ctx = jax.sharding.get_abstract_mesh()
    if ctx is not None and not ctx.empty:
        Manual = jax.sharding.AxisType.Manual
        already = {name for name, t in zip(ctx.axis_names, ctx.axis_types)
                   if t == Manual}
        if already:
            if axis in already:
                raise ValueError(
                    f"ring axis {axis!r} is already manual in the "
                    f"enclosing shard_map; ring attention cannot re-split "
                    f"it")
            sm_mesh = ctx
            if bspec in already:
                bspec = None
            if hspec in already:
                hspec = None
    spec = P(bspec, hspec, axis, None)
    sq, sk = q.shape[2] // n, k.shape[2] // n
    if impl not in ("auto", "flash", "xla"):
        raise ValueError(f"unknown impl: {impl!r}")
    # "auto" is the flash kernel on TPU and the XLA path elsewhere (the
    # CPU test path). On the chip a chunk shape the kernel cannot take
    # raises like an explicit "flash" does — it never slides to the
    # (S/n)×(S/n) reference.
    use_flash = impl == "flash" or (impl == "auto" and on_chip())
    # What the kernel tiles: a chunk, or under the mask a stripe, half of
    # one.
    tile = sq // 2 if causal and n > 1 else sq
    if use_flash and (sq != sk or tile % 8):
        raise ValueError(f"ring attention's flash kernel needs equal "
                         f"tile-aligned chunks, got ({sq},{sk})"
                         + (f" in stripes of {tile}" if tile != sq else "")
                         + "; pad the sequence or pass impl='xla'")
    if n == 1:
        if use_flash:
            return flash_attention(q, k, v, causal=causal)
        return mha_reference(q, k, v, causal=causal)
    if causal and sq != sk:
        raise ValueError(f"a causal ring needs q and k of one length, got "
                         f"chunks of ({sq},{sk})")
    s = q.shape[2]   # under the mask _ring_geometry refuses unequal stripes
    bh = q.shape[0] * q.shape[1]
    profile.count_ring_geometry(
        f"{'causal' if causal else 'full'} bh{bh} s{s} d{q.shape[3]} "
        f"n{n}", _ring_geometry(s, n, causal, bh))
    body = functools.partial(_ring_body, axis=axis, n=n, causal=causal,
                             use_flash=use_flash)
    # Partial-manual: only the axes the ring actually uses are manual;
    # anything else (tp on the head dim, fsdp on params upstream) stays
    # with the compiler so the two compose.
    if isinstance(q, jax.core.Tracer):
        fn = jax.shard_map(
            body, mesh=sm_mesh,
            in_specs=(spec, spec, spec, P(axis)),
            out_specs=(spec, P(bspec, hspec, axis)),
            axis_names=frozenset(a for a in (axis, bspec, hspec)
                                 if a is not None),
            check_vma=False,
        )
    else:
        # Partial-manual shard_map (axis_names ⊂ mesh axes) only lowers
        # correctly under jit in current JAX — the eager path trips a
        # bogus "out_specs refers to <other axis>" check. Production
        # calls are always inside a jitted step; this keeps direct eager
        # use (model.init with a mesh-carrying model, notebooks) working
        # — through a CACHED jit wrapper, or a fresh jax.jit per call
        # would recompile every invocation.
        fn = _eager_ring(sm_mesh, bspec, hspec, axis, n, causal, use_flash)
    return fn(q, k, v, jnp.arange(n, dtype=jnp.int32))


def ring_self_attention(x_heads, *, mesh: Mesh, axis: str = "sp",
                        causal: bool = True) -> jax.Array:
    """Convenience: q = k = v = x_heads (B, H, S, D); returns out only."""
    out, _ = ring_attention(x_heads, x_heads, x_heads, mesh=mesh, axis=axis,
                            causal=causal)
    return out
