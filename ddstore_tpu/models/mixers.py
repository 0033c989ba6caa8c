"""The mixers of a described architecture's layers, and the norm and rotary
step they share. A mixer is ``(block, x, positions) -> what it adds to x``
under the block's scope (its parameters are the block's), reading the
block's ``dim``, ``heads``, ``arch``, ``compute_dtype`` and ``layer``. A new
kind of layer is a function and a key of :data:`MIXERS`.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import on_chip
from ..ops.attention import BlockDiffusion, flash_attention, mha_reference
from ..ops.short_conv import gated_short_conv, short_conv
from ..ops.ssd import SCAN as SSD_SCAN, ssd
from ..utils import profile


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale`` in float32."""

    eps: float

    @nn.compact
    def __call__(self, x):
        x = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + self.eps) * scale


def rope(x, positions, theta: float):
    """Rotary positions on all of ``x``'s last dimension, ``x`` (B, S, H,
    D), ``positions`` (B, S) global indices. Pairs are the two halves
    (dimension i with i + D/2, the Hugging Face ``rotate_half`` layout); a
    checkpoint that pairs neighbours differs by a fixed permutation of the
    projection's columns."""
    half = x.shape[-1] // 2
    inv = jnp.exp(-math.log(theta) * jnp.arange(half) / half)
    ang = positions[..., None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _attend(q, k, v, mask=None, window=None):
    """Causal attention (under ``mask``, a
    :class:`~ddstore_tpu.ops.attention.BlockDiffusion`: that mask's; with
    ``window``, a sliding window of that many keys) over (B, S, H, D) heads
    as the projections write them, K and V perhaps fewer heads than Q
    (grouped-query): ``(out, layout)``, ``out`` (B, S, H, D) and the
    layout the kernels took their operands in. Heads of whole lanes go as
    they lie (``bshd``: nothing is transposed on the way in, out or back);
    a narrower head is no block of (B, S, H D), so those are transposed and
    go head-major (``bhsd``). On the chip the kernel is the only path, as
    in the dense ``Block``: a length it cannot tile raises in
    ``flash_attention`` rather than sliding to the S x S reference, which
    is what runs elsewhere (``reference``)."""
    chip = on_chip()
    how = {"causal": True} if mask is None else {"mask": mask}
    if window is not None:
        how["window"] = window
    if chip and q.shape[-1] % 128 == 0:
        return flash_attention(q, k, v, layout="bshd", **how)[0], "bshd"
    attend = flash_attention if chip else mha_reference
    out = attend(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)), **how)[0]
    return out.transpose(0, 2, 1, 3), "bhsd" if chip else "reference"


class _LatentKV(nn.Module):
    """``kv_b`` of latent attention: one ``(kv_lora_rank, H (nope + v))``
    kernel in the checkpoint's column order (a head's key columns, then its
    value columns), applied as two products: ``k_nope`` from the key
    columns and V from its own. The columns are cut on the weight's side,
    so V is written where attention reads it and is never a slice of a
    448-wide activation (nor its gradient half of a concatenation). The
    leaf, its initialisation and its name are ``nn.Dense``'s."""

    heads: int
    nope: int
    v_dim: int
    dtype: Any

    @nn.compact
    def __call__(self, ckv):
        nh, nope, vd = self.heads, self.nope, self.v_dim
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (ckv.shape[-1], nh * (nope + vd)))
        w = kernel.astype(self.dtype).reshape(-1, nh, nope + vd)
        return (ckv @ w[..., :nope].reshape(-1, nh * nope),
                ckv @ w[..., nope:].reshape(-1, nh * vd))


def _mla_mixer(blk: nn.Module, x, positions):
    """Multi-head latent attention computed uncompressed (training: per
    head q = [q_nope | q_rope], k = [k_nope | k_rope], the rotary key one
    vector a position shared by all heads), norm to output projection.
    Heads stay where the projections write them, (B, S, H, D), from the
    products through attention to ``proj``."""
    b, s, _ = x.shape
    a, dt, nh = blk.arch, blk.compute_dtype, blk.heads
    lin = lambda n, name: nn.Dense(n, use_bias=False, dtype=dt, name=name)
    norm = lambda name: RMSNorm(a.rms_norm_eps, name=name)
    nope, rot, vd = a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim
    if vd != nope + rot:
        raise NotImplementedError(
            f"v_head_dim={vd} beside a query/key width of {nope + rot}: "
            f"the flash kernels give K and V one width")
    h = norm("ln1")(x).astype(dt)
    with jax.named_scope("mix_in"):
        cq = lin(a.q_lora_rank, "q_a")(h)
        with jax.named_scope("mix_norm"):
            cq = norm("q_norm")(cq).astype(dt)
        q = lin(nh * (nope + rot), "q_b")(cq).reshape(b, s, nh, nope + rot)
        kva = lin(a.kv_lora_rank + rot, "kv_a")(h)
        with jax.named_scope("mix_norm"):
            ckv = norm("kv_norm")(kva[..., :a.kv_lora_rank]).astype(dt)
        k_nope, v = _LatentKV(nh, nope, vd, dt, name="kv_b")(ckv)
    k_rope = rope(kva[..., None, a.kv_lora_rank:], positions, a.rope_theta)
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], positions, a.rope_theta)],
        axis=-1)
    k = jnp.concatenate(
        [k_nope.reshape(b, s, nh, nope),
         jnp.broadcast_to(k_rope, (b, s, nh, rot))], axis=-1)
    out, layout = _attend(q, k, v.reshape(b, s, nh, vd))
    profile.count_mixer_layout("/".join(blk.path), kind="mla", heads=nh,
                               kv_heads=nh, tokens=b * s, layout=layout)
    with jax.named_scope("mix_out"):
        return lin(blk.dim, "proj")(out.reshape(b, s, nh * vd).astype(dt))


def _gqa_mixer(blk: nn.Module, x, positions, normed=None):
    """Grouped-query attention (``Lfm2MoeAttention``,
    ``NemotronHAttention``): ``heads`` query heads over
    ``num_key_value_heads`` K/V heads of the arch's ``head_dim`` (``dim /
    heads`` where it gives none); where the arch asks, q and k RMS-normed a
    head (``qk_norm``: one learned scale of the head's width each) and
    rotated on the whole width, as the arch's ``attention(layer)`` says,
    which also gives the layer's sliding window (such a layer's kernels run
    under the scope ``window``); K and V go to the kernels as they are.
    ``normed``: ``ln1(x)`` where the block has it already."""
    b, s, _ = x.shape
    a, dt, nh = blk.arch, blk.compute_dtype, blk.heads
    nkv, hd = a.num_key_value_heads, a.head_dim or blk.dim // blk.heads
    norm = lambda name: RMSNorm(a.rms_norm_eps, name=name)
    h = norm("ln1")(x).astype(dt) if normed is None else normed
    rotary, window = a.attention(blk.layer)
    # [W_q | W_k | W_v] as one product
    with jax.named_scope("mix_in"):
        qkv = nn.Dense((nh + 2 * nkv) * hd, use_bias=False, dtype=dt,
                       name="qkv")(h).reshape(b, s, nh + 2 * nkv, hd)
    q, k, v = jnp.split(qkv, (nh, nh + nkv), axis=2)

    def prepared(t, name):
        if a.qk_norm:
            with jax.named_scope("mix_norm"):
                t = norm(name)(t)
        if rotary:
            t = rope(t, positions, a.rope_theta)
        return t.astype(dt)

    q, k = prepared(q, "q_norm"), prepared(k, "k_norm")
    # a block-diffusion arch's sequence is [noised ; clean]
    blocks = a.block_length
    mask = BlockDiffusion(blocks, s // 2) if blocks else None
    extra = {"mask": f"block_diffusion {blocks}"} if blocks else {}
    if window is None:
        out, layout = _attend(q, k, v, mask)
    else:
        # what the kernels are handed, for whoever asks with
        # mutable=["intermediates"] (the benchmark's check of the window's
        # statistics); nothing otherwise
        blk.sow("intermediates", "window_qk", (q, k, v))
        with jax.named_scope("window"):
            out, layout = _attend(q, k, v, window=window)
    profile.count_mixer_layout(
        "/".join(blk.path), kind="full_attention", heads=nh, kv_heads=nkv,
        head_dim=hd, tokens=b * s, layout=layout, window=window,
        rotary=rotary, **extra)
    out = out.reshape(b, s, nh * hd).astype(dt)
    with jax.named_scope("mix_out"):
        return nn.Dense(blk.dim, use_bias=False, dtype=dt,
                        name="proj")(out)


def _conv_mixer(blk: nn.Module, x, positions):
    """Gated short convolution (``Lfm2ShortConv``): ``[Bg | Cg | u] = W_in
    h``, a causal depthwise convolution of ``conv_L_cache`` taps over ``Bg
    * u``, gated by ``Cg``, then ``W_out``
    (:func:`~ddstore_tpu.ops.short_conv.gated_short_conv`). ``conv_taps``
    is (taps, dim), the last row the current position's: the checkpoint's
    (dim, 1, taps) weight transposed."""
    a, dt = blk.arch, blk.compute_dtype
    b, s, _ = x.shape
    lin = lambda n, name: nn.Dense(n, use_bias=False, dtype=dt, name=name)
    profile.count_mixer_layout("/".join(blk.path), kind="conv",
                               taps=a.conv_L_cache, tokens=b * s)
    with jax.named_scope("conv_mixer"):
        h = RMSNorm(a.rms_norm_eps, name="ln1")(x).astype(dt)
        taps = blk.param(
            "conv_taps", nn.initializers.variance_scaling(
                1.0, "fan_in", "truncated_normal", in_axis=0, out_axis=1),
            (a.conv_L_cache, blk.dim))
        with jax.named_scope("mix_in"):
            bcu = lin(3 * blk.dim, "in_proj")(h)
        y = gated_short_conv(bcu, taps)
        with jax.named_scope("mix_out"):
            return lin(blk.dim, "out_proj")(y)


class _GatedGroupNorm(nn.Module):
    """``MambaRMSNormGated``, gate before norm: ``y * silu(z)``, RMS-normed
    over each of ``groups`` equal groups of channels, times one learned
    scale of all the channels; float32."""

    groups: int
    eps: float

    @nn.compact
    def __call__(self, y, z):
        f32 = jnp.float32
        y = y.astype(f32) * nn.silu(z.astype(f32))
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],))
        grouped = y.reshape(y.shape[:-1] + (self.groups, -1))
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, axis=-1, keepdims=True) + self.eps)
        return grouped.reshape(y.shape) * scale


def _dt_bias_init(a):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform between
    the arch's ``time_step_min`` and ``time_step_max``, floored at
    ``time_step_floor`` (Mamba-2's initialisation)."""
    def init(key, shape, dtype=jnp.float32):
        lo, hi = math.log(a.time_step_min), math.log(a.time_step_max)
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, dtype, lo, hi)), a.time_step_floor)
        return step + jnp.log(-jnp.expm1(-step))   # softplus's inverse
    return init


def _mamba2_mixer(blk: nn.Module, x, positions):
    """Mamba-2 (``NemotronHMamba2Mixer``): ``[z | xBC | dt] = W_in h``;
    ``xBC`` through a causal depthwise convolution of ``conv_kernel``
    biased taps and silu (:func:`~ddstore_tpu.ops.short_conv.short_conv`;
    ``conv_taps`` (taps, channels), the last row the current position's),
    then ``[x | B | C]``; the state-space scan over heads of
    ``mamba_head_dim`` with ``dt = softplus(dt + dt_bias)`` and ``A =
    -exp(A_log)`` (:func:`~ddstore_tpu.ops.ssd.ssd`); the gated group norm
    and ``W_out``. No position enters: the scan gives order."""
    a, dt = blk.arch, blk.compute_dtype
    b, s, _ = x.shape
    nh, hp, g, n = (a.mamba_num_heads, a.mamba_head_dim, a.n_groups,
                    a.ssm_state_size)
    inner, conv = nh * hp, nh * hp + 2 * g * n
    lin = lambda n, name: nn.Dense(n, use_bias=False, dtype=dt, name=name)
    profile.count_mixer_layout(
        "/".join(blk.path), kind="mamba2", heads=nh, head_dim=hp, state=n,
        groups=g, chunk=a.chunk_size, taps=a.conv_kernel, tokens=b * s,
        scan=SSD_SCAN)
    with jax.named_scope("mamba_mixer"):
        h = RMSNorm(a.rms_norm_eps, name="ln1")(x).astype(dt)
        with jax.named_scope("mix_in"):
            z, xbc, step = jnp.split(lin(inner + conv + nh, "in_proj")(h),
                                     (inner, inner + conv), axis=-1)
        taps = blk.param(
            "conv_taps", nn.initializers.variance_scaling(
                1.0, "fan_in", "truncated_normal", in_axis=0, out_axis=1),
            (a.conv_kernel, conv))
        bias = blk.param("conv_bias", nn.initializers.zeros, (conv,))
        with jax.named_scope("mamba_conv"):
            xbc = short_conv(xbc, taps, bias)
        xs, B, C = jnp.split(xbc, (inner, inner + g * n), axis=-1)
        step = nn.softplus(step.astype(jnp.float32) + blk.param(
            "dt_bias", _dt_bias_init(a), (nh,)))
        A = -jnp.exp(blk.param(
            "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                key, shape, jnp.float32, 1.0, 16.0)), (nh,)))
        D = blk.param("D", nn.initializers.ones, (nh,))
        y = ssd(xs.reshape(b, s, nh, hp), step, A, B.reshape(b, s, g, n),
                C.reshape(b, s, g, n), D, a.chunk_size)
        with jax.named_scope("mix_norm"):
            y = _GatedGroupNorm(g, a.rms_norm_eps, name="norm")(
                y.reshape(b, s, inner), z)
        with jax.named_scope("mix_out"):
            return lin(blk.dim, "out_proj")(y.astype(dt))


# A described layer's mixer, by the name ``arch.mixer(layer)`` gives it.
MIXERS = {"mla": _mla_mixer, "full_attention": _gqa_mixer,
           "sliding_attention": _gqa_mixer, "conv": _conv_mixer,
           "mamba2": _mamba2_mixer}
