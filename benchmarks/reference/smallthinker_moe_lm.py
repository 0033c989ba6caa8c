"""Plain reference for the ``smallthinker_moe_lm`` family: a pre-RMSNorm
decoder of grouped-query attention, window and full mixed, over
softmax-routed ReGLU experts whose router reads the layer's input before
attention (PowerInfer SmallThinker-21BA3B-Instruct), as a float32
``jax.numpy`` forward pass at ``highest`` matmul precision. Loss = mean
next-token cross-entropy. ``jax.grad`` of :func:`loss` is the gradient
reference.

    h1 = RMSNorm_ln1(x);  r = W_router h1
    x = x + Attn_i(h1);   x = x + MoE(RMSNorm_ln2(x); r)
    attention:  no q/k norm; q and k rotated on the whole head width (halves
                paired) where rope_layout[i] is 1, nothing where it is 0;
                query head h on K/V head h // group; softmax at 1 / sqrt(head
                width) over the keys j with i - W < j <= i where
                sliding_window_layout[i] is 1, j <= i where it is 0
    experts:    the k largest of r chosen, weights a softmax over all of r
                renormalised over the chosen; expert e is
                down_e(relu(gate_e h) * up_e h); no shared expert
    head:       logits = RMSNorm(x) W_head (untied)

No flax, no kernels, no bfloat16, no sort and no grouped product:
attention is a written-out masked softmax over K and V repeated a group, one
block of query rows against every key at a time, the experts and the head
run in blocks of tokens, and each held expert is a dense product over every
token (a ``lax.scan`` over the held ones), weighted by what the router gave
it (zero where it was not chosen). Each layer and each block of rows or
tokens is a ``jax.checkpoint``: the backward pass computes it again and
keeps nothing of it, which changes no value. It imports nothing of
``ddstore_tpu`` and reads the system's parameter tree by layer name only
(``qkv`` one matrix ``[W_q | W_k | W_v]``; the router the block's own leaf,
``block<i>/router``, the experts ``block<i>/moe``); which kind a layer's
attention is, it reads from ``arch``'s two layouts.

**The share.** ``share = (which, of)``: the tree holds the ``n // of``
consecutive routed experts from ``which * n // of`` of the router's ``n``.
The router scores all ``n``; only the held experts' part of the result is
added, and that partial sum goes on to the next layer, as in the program.
``(0, 1)`` is the uncut layer. The vocabulary's slice is the embedding's and
the head's rows: ids, logits and the loss are over them.

``leave_out`` names parts of the mathematics to break, for the readings
that set a cell's limits (each must come out not correct): ``"wide_window"``
(a window one key wider), ``"rotary_full"`` (the full layers rotate q and
k too), ``"router_ln2"`` (the router reads ``ln2(x + attn)``, as most expert
layers' do), ``"silu"`` (SwiGLU for ReGLU); ``matrix_dtype`` rounds every
matrix (two or more dimensions) to that type first, e.g. ``float8_e4m3fn``,
and the gradient is the rounded matrices' own.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rms(p, x, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * p["scale"]


def _rope(x, positions, theta):
    """x (B, S, H, D): dimension i rotates with i + D/2 by the angle
    position * theta**(-2i/D)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None, None].astype(jnp.float32) * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _in_blocks(fn, block, *arrays):
    """``fn`` over blocks of the leading axis (a divisor of it), joined;
    the backward pass computes each block again."""
    fn = jax.checkpoint(fn)
    n = arrays[0].shape[0]
    block = min(block, n)
    while n % block:
        block -= 1
    out = jax.lax.map(lambda i: fn(*(jax.lax.dynamic_slice_in_dim(
        a, i * block, block) for a in arrays)), jnp.arange(n // block))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((n,) + o.shape[2:]), out)


def visible(qpos, kpos, window=None):
    """Boolean (len(qpos), len(kpos)): key j is seen by query i iff ``j <=
    i`` and, with a ``window``, ``j > i - window``."""
    qpos, kpos = qpos[:, None], kpos[None, :]
    seen = kpos <= qpos
    return seen if window is None else seen & (kpos > qpos - window)


def attention(q, k, v, window=None, block=256):
    """q (B, H, S, D), k and v (B, H_kv, S, D) float32: ``(out (B, H, S, D),
    lse (B, H, S))`` of softmax(q k^T / sqrt D) v over the keys
    :func:`visible` keeps, K and V repeated H / H_kv times, one block of
    query rows at a time."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = q.shape[2]
    kpos = jnp.arange(s)

    def rows(qi, qpos):
        sc = jnp.einsum("qbhd,bhkd->bhqk", qi, k) / math.sqrt(q.shape[-1])
        sc = jnp.where(visible(qpos, kpos, window), sc, -jnp.inf)
        lse = jax.nn.logsumexp(sc, -1)
        out = jnp.einsum("bhqk,bhkd->qbhd", jnp.exp(sc - lse[..., None]), v)
        return out, lse.transpose(2, 0, 1)

    out, lse = _in_blocks(rows, block, q.transpose(2, 0, 1, 3), kpos)
    return out.transpose(1, 2, 0, 3), lse.transpose(1, 2, 0)


def layer_window(arch, i, leave_out=()):
    """Layer ``i``'s window: None (causal) or the keys a query sees."""
    if not arch["sliding_window_layout"][i]:
        return None
    return arch["sliding_window"] + ("wide_window" in leave_out)


def qk(p, h, positions, arch, i, leave_out=()):
    """Layer ``i``'s ``(q (B, H, S, D), k, v (B, H_kv, S, D))`` from normed
    ``h`` (B, S, d)."""
    b, s, d = h.shape
    nh, nkv, hd = arch["heads"], arch["num_key_value_heads"], arch["head_dim"]
    qkv = (h @ p["qkv"]["kernel"]).reshape(b, s, nh + 2 * nkv, hd)
    q, k, v = qkv[:, :, :nh], qkv[:, :, nh:nh + nkv], qkv[:, :, nh + nkv:]
    if arch["rope_layout"][i] or "rotary_full" in leave_out:
        q = _rope(q, positions, arch["rope_theta"])
        k = _rope(k, positions, arch["rope_theta"])
    return tuple(t.transpose(0, 2, 1, 3) for t in (q, k, v))


def gqa(p, h, positions, arch, i, leave_out=()):
    """Layer ``i``'s grouped-query attention on normed ``h`` (B, S, d)."""
    b, s, _ = h.shape
    out, _ = attention(*qk(p, h, positions, arch, i, leave_out),
                       layer_window(arch, i, leave_out))
    return out.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["proj"]["kernel"]


def route(logits, top_k):
    """``(chosen (T, k), weights (T, k))``: the ``k`` largest of the
    softmax over all the router's outputs, renormalised over the chosen."""
    w, chosen = jax.lax.top_k(jax.nn.softmax(logits, -1), top_k)
    return chosen, w / w.sum(-1, keepdims=True)


def moe(p, h, logits, arch, share=None, leave_out=()):
    """One expert layer on tokens ``h`` (T, d) routed by ``logits`` (T, n):
    ``(y, chosen)``. Every held expert multiplies every token; the router's
    weight (zero for a token that did not choose it) picks its part."""
    which, of = share or arch["expert_share"]
    held = p["w_gate"].shape[0]
    first = which * held
    if logits.shape[1] != held * of:
        raise ValueError("the tree's experts are not this share's")
    chosen, w = route(logits, arch["num_experts_per_tok"])
    gate_fn = jax.nn.silu if "silu" in leave_out else jax.nn.relu

    def add(y, expert):
        e, gate, up, down = expert
        mine = (jnp.where(chosen == first + e, w, 0.0)).sum(-1)
        return y + mine[:, None] * ((gate_fn(h @ gate) * (h @ up)) @ down), \
            None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), (
        jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"]))
    return y, chosen


def block(p, x, positions, arch, i, token_block, leave_out=()):
    """Layer ``i``: ``(x, chosen)``."""
    b, s, d = x.shape
    eps = arch["rms_norm_eps"]
    h1 = _rms(p["ln1"], x, eps)
    x = x + gqa(p, h1, positions, arch, i, leave_out)
    h2 = _rms(p["ln2"], x, eps)
    at = h2 if "router_ln2" in leave_out else h1
    logits = at.reshape(b * s, d) @ p["router"]["kernel"]
    y, chosen = _in_blocks(
        lambda t, r: moe(p["moe"], t, r, arch, leave_out=leave_out),
        token_block, h2.reshape(b * s, d), logits)
    return x + y.reshape(b, s, d), chosen


def _leaves(params, matrix_dtype):
    def leaf(a):
        a = a.astype(jnp.float32)
        if matrix_dtype is not None and a.ndim >= 2:
            # The gradient is the rounded matrix's own: taken through the
            # conversions it would be rounded to ``matrix_dtype`` itself.
            low = jax.lax.optimization_barrier(a.astype(matrix_dtype))
            a = a + jax.lax.stop_gradient(low.astype(jnp.float32) - a)
        return a

    return jax.tree_util.tree_map(leaf, params["params"])


def forward(params, tokens, targets, positions, arch, *, token_block=2048,
            leave_out=(), matrix_dtype=None):
    """``(loss, [chosen (B S, k) of each layer])`` for the windows
    ``tokens`` (B, S) and their shifted ``targets``."""
    p = _leaves(params, matrix_dtype)
    b, s = tokens.shape
    with jax.default_matmul_precision("highest"):
        x, routed = p["embed"]["tok"]["embedding"][tokens], []
        for i in range(sum(1 for name in p if name.startswith("block"))):
            x, chosen = jax.checkpoint(
                lambda p, x, i=i: block(p, x, positions, arch, i,
                                        token_block, leave_out))(
                    p[f"block{i}"], x)
            routed.append(chosen)
        feats = _rms(p["lmhead"]["lnf"], x, arch["rms_norm_eps"])
        head = p["lmhead"]["head"]["kernel"]

        def rows(f, t):
            logp = jax.nn.log_softmax(f @ head, -1)
            return -jnp.take_along_axis(logp, t[:, None], -1)[:, 0]

        nll = _in_blocks(rows, token_block, feats.reshape(b * s, -1),
                         targets.reshape(b * s))
    return nll.mean(), routed


def loss(params, tokens, targets, positions, *, arch, token_block=2048,
         leave_out=(), matrix_dtype=None):
    """Mean cross-entropy over all (B, S) positions. ``arch``: ``heads``,
    ``num_key_value_heads``, ``head_dim``, ``num_experts_per_tok``,
    ``expert_share``, ``rope_theta``, ``rms_norm_eps``, ``rope_layout``,
    ``sliding_window_layout``, ``sliding_window``."""
    return forward(params, tokens, targets, positions, arch,
                   token_block=token_block, leave_out=leave_out,
                   matrix_dtype=matrix_dtype)[0]


def window_lse(q, k, window, leave_out=(), block=256):
    """The lse (B, H, S) of a windowed layer's attention on given ``q`` (B,
    H, S, D) and ``k`` (B, H_kv, S, D), in float32 at ``highest``
    precision, one block of query rows at a time: what the family holds the
    flash call's statistics to."""
    q, k = (t.astype(jnp.float32) for t in (q, k))
    with jax.default_matmul_precision("highest"):
        return attention(q, k, jnp.zeros_like(k[..., :1]),
                         window + ("wide_window" in leave_out), block)[1]
