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

On TPU the per-step block computation is the Pallas flash kernel, so each
ring step is O(block) memory — without it each step materializes an
(S/n)×(S/n) score matrix, capping exactly the context length the sp axis
exists to extend. The kernel takes its causal offsets statically, while
the ring offsets are traced (``axis_index``); with equal chunks every
(device, step) pair is one of three STATIC cases — kv chunk fully in the
past (unmasked flash), the diagonal chunk (plain causal flash at zero
offset), or fully in the future (skipped) — so a ``lax.cond`` selects
between statically-configured kernels. Non-TPU backends default to the
XLA path (:func:`ddstore_tpu.ops.attention.mha_reference`).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import flash_attention, mha_reference

__all__ = ["ring_attention", "ring_self_attention"]


def _combine(acc_out, acc_lse, out_i, lse_i):
    """Merge two normalized attention partials (f32 math)."""
    m = jnp.maximum(acc_lse, lse_i)
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    w1 = jnp.where(jnp.isfinite(acc_lse), jnp.exp(acc_lse - safe_m), 0.0)
    w2 = jnp.where(jnp.isfinite(lse_i), jnp.exp(lse_i - safe_m), 0.0)
    denom = jnp.maximum(w1 + w2, 1e-30)
    out = (acc_out * w1[..., None] + out_i.astype(jnp.float32)
           * w2[..., None]) / denom[..., None]
    lse = jnp.where(jnp.isfinite(m), safe_m + jnp.log(denom), -jnp.inf)
    return out, lse


def _ring_body(q, k, v, idx_chunk, *, axis: str, n: int, causal: bool,
               use_flash: bool):
    """shard_map body: local chunks (B, H, S/n, D). ``idx_chunk`` is this
    device's slice of an arange over the ring axis — the ring position.
    NOT ``jax.lax.axis_index``: its lowering computes the position from
    the full device id, which re-binds every mesh axis and breaks when
    this shard_map is nested inside another manual region (pp×sp)."""
    idx = idx_chunk[0]
    sq, sk = q.shape[2], k.shape[2]
    q_off = idx * sq
    perm = [(j, (j + 1) % n) for j in range(n)]

    def masked(args):
        return (jnp.zeros(q.shape, q.dtype),
                jnp.full(q.shape[:3], -jnp.inf, jnp.float32))

    acc_out = jnp.zeros(q.shape, jnp.float32)
    acc_lse = jnp.full(q.shape[:3], -jnp.inf, jnp.float32)
    for step in range(n):
        # After `step` rotations this device holds the kv chunk originally
        # owned by (idx - step) mod n.
        src = (idx - step) % n
        kv_off = src * sk

        # One scope a ring step (attend, combine, rotate), so a device
        # trace sets the ring's own work apart from the block around it.
        with jax.named_scope("ring_step"):
            if use_flash:
                # The kernel's offsets are static; the traced ring position
                # reduces to three static mask shapes (module docstring).
                def attend_past(args):
                    qq, kk, vv = args
                    return flash_attention(qq, kk, vv, causal=False)

                def attend_diag(args):
                    qq, kk, vv = args
                    return flash_attention(qq, kk, vv, causal=True)

                if causal:
                    out_i, lse_i = jax.lax.cond(
                        src == idx, attend_diag,
                        lambda args: jax.lax.cond(src < idx, attend_past,
                                                  masked, args),
                        (q, k, v))
                else:
                    out_i, lse_i = attend_past((q, k, v))
            else:
                def attend(args):
                    qq, kk, vv = args
                    return mha_reference(qq, kk, vv, causal=causal,
                                         q_offset=q_off, kv_offset=kv_off)

                if causal:
                    # A kv chunk entirely in this q chunk's future is fully
                    # masked: skip its O(S²/n²) compute on devices where that
                    # holds (half of all (device, step) pairs — the ring-level
                    # twin of the flash kernel's per-block `live` predicate).
                    out_i, lse_i = jax.lax.cond(src <= idx, attend, masked,
                                                (q, k, v))
                else:
                    out_i, lse_i = attend((q, k, v))
            acc_out, acc_lse = _combine(acc_out, acc_lse, out_i, lse_i)
            if step < n - 1:
                k = jax.lax.ppermute(k, axis, perm)
                v = jax.lax.ppermute(v, axis, perm)
    return acc_out.astype(q.dtype), acc_lse


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

    Returns ``(out, lse)`` like the ops-level kernels. ``batch_axis``
    optionally shards B over a data-parallel mesh axis (defaults to "dp"
    when the mesh has one); ``heads_axis`` shards H over a tensor-parallel
    axis (sp×tp composition: heads are independent in attention, so each
    tp shard rings only its own heads and the two axes compose without
    any cross-communication). Callable inside jit: shard_map composes.

    impl: "flash" (Pallas kernel per ring step — O(block) memory),
    "xla" (mha_reference), or "auto" (flash on TPU, xla elsewhere). The
    flash kernel needs equal chunks that are multiples of 8 and raises
    otherwise, on "auto" as well.
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
    use_flash = impl == "flash" or (impl == "auto"
                                    and jax.default_backend() == "tpu")
    # The static three-case causal split needs aligned equal chunks.
    if use_flash and (sq != sk or sq % 8):
        raise ValueError(f"ring attention's flash kernel needs equal "
                         f"tile-aligned chunks, got ({sq},{sk}); pad the "
                         f"sequence or pass impl='xla'")
    if n == 1:
        if use_flash:
            return flash_attention(q, k, v, causal=causal)
        return mha_reference(q, k, v, causal=causal)
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
