"""Plain reference for the ``dense_lm`` family: a pre-LayerNorm decoder-only
transformer (multi-head causal attention, GELU MLP of width 4d, fixed
sinusoidal positions, final LayerNorm, untied head, mean next-token
cross-entropy) as a float32 ``jax.numpy`` forward pass at ``highest`` matmul
precision. No flax, no kernels, no bfloat16, no mesh; attention is computed in
blocks of query rows against the whole context with a causal mask, so S x S is
never held, and the head in blocks of tokens.

Departures from GPT-3 (arXiv:2005.14165), shared with the system and listed in
the configuration's ``assumed``: sinusoidal instead of learned positions, no
attention biases, dense attention in every layer, tanh-approximated GELU
(``flax.linen.gelu``'s default), LayerNorm eps 1e-6 (flax's default)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


def _ln(p, x):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(q, k, v, block):
    """q, k, v: (B, H, S, D) float32. Causal softmax(q k^T / sqrt D) v, one
    block of query rows at a time."""
    b, h, s, d = q.shape
    kpos = jnp.arange(s)

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=2)
        sc = jnp.einsum("bhqd,bhkd->bhqk", qi, k) / math.sqrt(d)
        qpos = i * block + jnp.arange(block)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(rows, jnp.arange(s // block))      # (n, B, H, blk, D)
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, s, d)


def loss(params, tokens, targets, positions, *, heads: int,
         attn_block: int = 512, head_block: int = 2048):
    """Mean cross-entropy over all (B, S) positions. ``params`` is the
    system's parameter tree, read by layer name only."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                               params["params"])
    b, s = tokens.shape
    dim = p["embed"]["tok"]["embedding"].shape[1]
    attn_block, head_block = min(attn_block, s), min(head_block, b * s)
    with jax.default_matmul_precision("highest"):
        half = dim // 2
        freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
        ang = positions[..., None].astype(jnp.float32) * freqs
        x = p["embed"]["tok"]["embedding"][tokens] + jnp.concatenate(
            [jnp.sin(ang), jnp.cos(ang)], axis=-1)
        layers = sum(1 for name in p if name.startswith("block"))
        for i in range(layers):
            blk = p[f"block{i}"]
            qkv = _ln(blk["ln1"], x) @ blk["qkv"]["kernel"]
            q, k, v = (t.reshape(b, s, heads, dim // heads).transpose(
                0, 2, 1, 3) for t in jnp.split(qkv, 3, axis=-1))
            a = _attention(q, k, v, attn_block)
            x = x + a.transpose(0, 2, 1, 3).reshape(b, s, dim) \
                @ blk["proj"]["kernel"]
            hdn = _gelu(_ln(blk["ln2"], x) @ blk["up"]["kernel"]
                        + blk["up"]["bias"])
            x = x + hdn @ blk["down"]["kernel"] + blk["down"]["bias"]
        feats = _ln(p["lmhead"]["lnf"], x).reshape(b * s, dim)
        w = p["lmhead"]["head"]["kernel"]
        tgt = targets.reshape(b * s)

        def nll(i):
            f = jax.lax.dynamic_slice_in_dim(feats, i * head_block,
                                             head_block)
            t = jax.lax.dynamic_slice_in_dim(tgt, i * head_block, head_block)
            logp = jax.nn.log_softmax(f @ w, axis=-1)
            return -jnp.take_along_axis(logp, t[:, None], axis=-1)[:, 0]

        return jax.lax.map(nll, jnp.arange(b * s // head_block)).mean()
