"""Plain reference for the ``lfm2_moe_lm`` family: a pre-RMSNorm decoder
whose layers differ in kind (``model_type`` ``lfm2_moe``, LiquidAI
LFM2-8B-A1B): the mixer of a layer is a gated short convolution
(``Lfm2ShortConv``) or rotary grouped-query attention with RMS-normed q and
k, its MLP a dense SwiGLU (the leading layers) or bias-routed SwiGLU
experts with no shared expert (``Lfm2MoeSparseMoeBlock``), and the head is
the embedding's matrix. Loss = mean next-token cross-entropy, as a float32
``jax.numpy`` forward pass at ``highest`` matmul precision. ``jax.grad`` of
:func:`loss` is the gradient reference.

    x = x + Mixer_i(RMSNorm(x));  x = x + FFN_i(RMSNorm(x))
    conv:       [Bg | Cg | u] = W_in h;  z = Bg * u;
                c[t] = sum_j w[j] z[t - (L-1) + j]  (z = 0 before the start);
                out = W_out (Cg * c)
    attention:  q, k RMS-normed a head, rotary on the whole head width
                (halves paired), query head h on K/V head h // group,
                causal softmax at 1 / sqrt(head width)
    experts:    s = sigmoid(W_r h); the k largest of s + bias are chosen;
                weights s[chosen] / (sum s[chosen] + 1e-6) * scaling
    head:       logits = RMSNorm(x) E^T, E the embedding

No flax, no kernels, no bfloat16, no sort and no grouped product:
the convolution is ``L`` explicit shifted products on a zero-padded
sequence, attention a written-out masked softmax over K and V repeated a
group, one block of query rows at a time, MLPs and the head in blocks of
tokens, and each held expert a dense product over every token (a
``lax.scan`` over the held ones), weighted by what the router gave it (zero
where it was not chosen). Each layer and
each block of rows or tokens is a ``jax.checkpoint``: the backward pass
computes it again and keeps nothing of it, which changes no value and lets
the gradient of an 8,192-token window sit beside the system's state on the
chip. It imports nothing of ``ddstore_tpu`` and reads the system's
parameter tree by layer name only; which kind a layer is, it reads from the
layer's leaves.

**The share.** ``share = (which, of)``: the tree holds the ``n // of``
consecutive routed experts from ``which * n // of`` of the router's ``n``.
The router scores all ``n``; only the held experts' part of the result is
added, and that partial sum goes on to the next layer, as in the program.
``(0, 1)`` is the uncut layer. The vocabulary's slice is the embedding's
rows: ids, logits and the loss are over them.

Departures from the published description, shared with the system and
listed in the configuration's ``assumed``: the head is tied to the
embedding (the catalog's row has no tying key); the expert bias is a leaf
like any other here (the system gives it no gradient; its gradient here is
zero too, since it only steers a selection); rotary pairs are the two
halves of the head width; ``[W_q | W_k | W_v]`` and the taps are read from
the system's leaves (``qkv`` one matrix, ``conv_taps`` (L, d) with the last
row the current position's: the checkpoint's (d, 1, L) weight transposed).

``leave_out`` names parts of the mathematics to leave out, for the
readings that set a cell's limits (each must come out not correct):
``"rotary"`` (no rotary step), ``"older_taps"`` (the convolution keeps its
current position's tap alone), ``"experts"`` (the held experts add
nothing); ``matrix_dtype`` rounds every matrix (two or more dimensions) to
that type first, e.g. ``float8_e4m3fn``, and the gradient is the rounded
matrices' own.
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


def short_conv(p, h, leave_out=()):
    """The gated short convolution on normed ``h`` (B, S, d): (B, S, d)."""
    s, d = h.shape[1:]
    bg, cg, u = jnp.split(h @ p["in_proj"]["kernel"], 3, -1)
    taps = p["conv_taps"]
    n = taps.shape[0]
    z = jnp.pad(bg * u, ((0, 0), (n - 1, 0), (0, 0)))
    c = jnp.zeros_like(u)
    for j in range(n):
        if j < n - 1 and "older_taps" in leave_out:
            continue
        c = c + taps[j] * z[:, j:j + s]
    return (cg * c) @ p["out_proj"]["kernel"]


def attention(q, k, v, block=512):
    """q (B, H, S, D), k and v (B, H_kv, S, D) float32: causal softmax(q
    k^T / sqrt D) v with K and V repeated H / H_kv times, one block of
    query rows at a time."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = q.shape[2]
    kpos = jnp.arange(s)

    def rows(qi, qpos):
        sc = jnp.einsum("qbhd,bhkd->bhqk", qi, k) / math.sqrt(q.shape[-1])
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->qbhd", jax.nn.softmax(sc, -1), v)

    out = _in_blocks(rows, block, q.transpose(2, 0, 1, 3), jnp.arange(s))
    return out.transpose(1, 2, 0, 3)


def gqa(p, h, positions, arch, leave_out=()):
    """Grouped-query attention on normed ``h`` (B, S, d): (B, S, d)."""
    b, s, d = h.shape
    nh, nkv = arch["heads"], arch["num_key_value_heads"]
    hd, eps = d // nh, arch["rms_norm_eps"]
    qkv = (h @ p["qkv"]["kernel"]).reshape(b, s, nh + 2 * nkv, hd)
    q, k, v = qkv[:, :, :nh], qkv[:, :, nh:nh + nkv], qkv[:, :, nh + nkv:]
    q, k = _rms(p["q_norm"], q, eps), _rms(p["k_norm"], k, eps)
    if "rotary" not in leave_out:
        q = _rope(q, positions, arch["rope_theta"])
        k = _rope(k, positions, arch["rope_theta"])
    out = attention(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)))
    return out.transpose(0, 2, 1, 3).reshape(b, s, d) @ p["proj"]["kernel"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(p, h, top_k, scaling, eps=1e-6):
    """``(chosen (T, k), weights (T, k))``: the top ``k`` of sigmoid scores
    plus the expert bias; weights from the scores alone, normalised over
    the chosen (the sum + ``eps``), times ``scaling``."""
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])
    _, chosen = jax.lax.top_k(scores + p["router_bias"], top_k)
    w = jnp.take_along_axis(scores, chosen, -1)
    return chosen, w / (w.sum(-1, keepdims=True) + eps) * scaling


def moe(p, h, arch, share=None, leave_out=()):
    """One expert layer on tokens ``h`` (T, d): ``(y, chosen)``. Every held
    expert multiplies every token; the router's weight (zero for a token
    that did not choose it) picks its part. No shared expert."""
    which, of = share or arch["expert_share"]
    held = p["w_gate"].shape[0]
    first = which * held
    if p["router"]["kernel"].shape[1] != held * of:
        raise ValueError("the tree's experts are not this share's")
    chosen, w = route(p, h, arch["num_experts_per_tok"],
                      arch["routed_scaling_factor"])
    y = jnp.zeros_like(h)
    if "experts" in leave_out:
        return y, chosen

    def add(y, expert):
        e, gate, up, down = expert
        mine = (jnp.where(chosen == first + e, w, 0.0)).sum(-1)
        return y + mine[:, None] * _swiglu(h, gate, up, down), None

    y, _ = jax.lax.scan(add, y, (jnp.arange(held), p["w_gate"], p["w_up"],
                                 p["w_down"]))
    return y, chosen


def block(p, x, positions, arch, token_block, leave_out=()):
    """One decoder layer; the kind of its mixer and of its MLP are read
    from its leaves. Returns ``(x, chosen or None)``."""
    b, s, d = x.shape
    h = _rms(p["ln1"], x, arch["rms_norm_eps"])
    if "conv_taps" in p:
        x = x + short_conv(p, h, leave_out)
    else:
        x = x + gqa(p, h, positions, arch, leave_out)
    h = _rms(p["ln2"], x, arch["rms_norm_eps"]).reshape(b * s, d)
    if "moe" in p:
        y, chosen = _in_blocks(lambda t: moe(p["moe"], t, arch,
                                             leave_out=leave_out),
                               token_block, h)
    else:
        y, chosen = _in_blocks(lambda t: _swiglu(
            t, p["gate"]["kernel"], p["up"]["kernel"], p["down"]["kernel"]),
            token_block, h), None
    return x + y.reshape(b, s, d), chosen


def forward(params, tokens, targets, positions, arch, *, token_block=2048,
            leave_out=(), matrix_dtype=None):
    """``(loss, [chosen (B*S, k) of each expert layer])``."""
    def leaf(a):
        a = a.astype(jnp.float32)
        if matrix_dtype is not None and a.ndim >= 2:
            # The gradient is the rounded matrix's own: taken through the
            # conversions it would be rounded to ``matrix_dtype`` itself.
            # The barrier keeps the pair from being simplified away as
            # excess precision (XLA may; the compiled modules checked kept
            # it without).
            low = jax.lax.optimization_barrier(a.astype(matrix_dtype))
            a = a + jax.lax.stop_gradient(low.astype(jnp.float32) - a)
        return a

    p = jax.tree_util.tree_map(leaf, params["params"])
    b, s = tokens.shape
    with jax.default_matmul_precision("highest"):
        table = p["embed"]["tok"]["embedding"]
        x, routed = table[tokens], []
        for i in range(sum(1 for name in p if name.startswith("block"))):
            x, chosen = jax.checkpoint(
                lambda p, x: block(p, x, positions, arch, token_block,
                                   leave_out))(p[f"block{i}"], x)
            routed += [] if chosen is None else [chosen]
        feats = _rms(p["lmhead"]["lnf"], x, arch["rms_norm_eps"])

        def rows(f, t):
            logp = jax.nn.log_softmax(f @ table.T, -1)
            return -jnp.take_along_axis(logp, t[:, None], -1)[:, 0]

        nll = _in_blocks(rows, token_block, feats.reshape(b * s, -1),
                         targets.reshape(b * s))
    return nll.mean(), routed


def loss(params, tokens, targets, positions, *, arch, token_block=2048,
         leave_out=(), matrix_dtype=None):
    """Mean cross-entropy over all (B, S) positions. ``arch``: ``heads``,
    ``num_key_value_heads``, ``num_experts_per_tok``,
    ``routed_scaling_factor``, ``expert_share``, ``rope_theta``,
    ``rms_norm_eps``."""
    return forward(params, tokens, targets, positions, arch,
                   token_block=token_block, leave_out=leave_out,
                   matrix_dtype=matrix_dtype)[0]
