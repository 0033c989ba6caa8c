"""Plain reference for the ``sdar_moe_lm`` family: a pre-RMSNorm decoder of
Qwen3-MoE layers (``model_type`` ``sdar_moe``, JetLM SDAR-30B-A3B-Chat)
trained by block diffusion (SDAR, arXiv:2510.06303, with the objective and
the training mask of BD3-LM, arXiv:2503.09573), as a float32 ``jax.numpy``
forward pass at ``highest`` matmul precision. ``jax.grad`` of :func:`loss`
is the gradient reference.

    x = x + Attn(RMSNorm(x));  x = x + MoE(RMSNorm(x))
    attention:  q, k RMS-normed a head, rotary on the whole head width
                (halves paired), query head h on K/V head h // group,
                softmax at 1 / sqrt(head width) under the mask below
    experts:    p = softmax(W_r h) over all the router's outputs; the k
                largest chosen; weights p[chosen] / sum p[chosen]; expert e
                is down_e(silu(gate_e h) * up_e h); no shared expert
    objective:  x_t = mask token where masked, else x_0; the trunk on
                [x_t ; x_0], both halves at the window's positions; logits
                of the noised half's rows, each at its own position;
                loss = 1 / (B S) sum over masked i of CE_i / t[i // L]

**The mask**, from its three rules, with i, j positions inside their halves
and L the block length: a noised query i sees noised key j iff ``j // L ==
i // L``; a noised query i sees clean key j iff ``j // L < i // L``; a clean
query i sees clean key j iff ``j // L <= i // L``; no clean query sees a
noised key. It is built dense and boolean from those four lines, a block of
query rows at a time (32 heads x 16,384 x 16,384 float32 scores do not fit).

The window, the drawn mask bits and the blocks' t are data, given as the
tokens are: the reference draws nothing. No flax, no kernels, no bfloat16,
no sort and no grouped product: attention is a written-out masked softmax
over K and V repeated a group, the experts and the head run in blocks of
tokens, and each held expert is a dense product over every token (a
``lax.scan`` over the held ones), weighted by what the router gave it (zero
where it was not chosen). Each layer and each block of rows or tokens is a
``jax.checkpoint``: the backward pass computes it again and keeps nothing of
it, which changes no value. It imports nothing of ``ddstore_tpu`` and reads
the system's parameter tree by layer name only (``qkv`` one matrix ``[W_q |
W_k | W_v]``).

**The share.** ``share = (which, of)``: the tree holds the ``n // of``
consecutive routed experts from ``which * n // of`` of the router's ``n``.
The router scores all ``n``; only the held experts' part of the result is
added, and that partial sum goes on to the next layer, as in the program.
``(0, 1)`` is the uncut layer. The vocabulary's slice is the embedding's and
the head's rows: ids, logits and the loss are over them.

``leave_out`` names parts of the mathematics to break, for the readings that
set a cell's limits (each must come out not correct): ``"own_clean_block"``
(a noised query also sees its own clean block: ``<=`` for ``<``),
``"block_causal"`` (the clean half is token-causal, not block-causal),
``"weight"`` (no 1 / t), ``"softmax"`` (sigmoid scores for softmax),
``"rotary"`` (no rotary step); ``matrix_dtype`` rounds every matrix (two or
more dimensions) to that type first, e.g. ``float8_e4m3fn``, and the
gradient is the rounded matrices' own.
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


def visible(rows, window, length, leave_out=()):
    """The mask's rows ``rows`` (indices into the ``2 window`` positions
    ``[noised ; clean]``) against every key: boolean (len(rows), 2 window),
    from the module docstring's four lines."""
    cols = jnp.arange(2 * window)
    q_clean, k_clean = (rows >= window)[:, None], (cols >= window)[None, :]
    i, j = (rows % window)[:, None], (cols % window)[None, :]
    bi, bj = i // length, j // length
    noised_on_noised = bj == bi
    noised_on_clean = bj <= bi if "own_clean_block" in leave_out else bj < bi
    clean_on_clean = j <= i if "block_causal" in leave_out else bj <= bi
    return jnp.where(
        q_clean, k_clean & clean_on_clean,
        jnp.where(k_clean, noised_on_clean, noised_on_noised))


def attention(q, k, v, window, length, leave_out=(), block=256):
    """q (B, H, 2 window, D), k and v (B, H_kv, 2 window, D) float32:
    softmax(q k^T / sqrt D) v under the mask, K and V repeated H / H_kv
    times, one block of query rows at a time."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))

    def rows(qi, at):
        sc = jnp.einsum("qbhd,bhkd->bhqk", qi, k) / math.sqrt(q.shape[-1])
        sc = jnp.where(visible(at, window, length, leave_out), sc, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->qbhd", jax.nn.softmax(sc, -1), v)

    out = _in_blocks(rows, block, q.transpose(2, 0, 1, 3),
                     jnp.arange(2 * window))
    return out.transpose(1, 2, 0, 3)


def gqa(p, h, positions, arch, leave_out=()):
    """Grouped-query attention on normed ``h`` (B, 2 window, d)."""
    b, s, d = h.shape
    nh, nkv, hd = arch["heads"], arch["num_key_value_heads"], arch["head_dim"]
    eps = arch["rms_norm_eps"]
    qkv = (h @ p["qkv"]["kernel"]).reshape(b, s, nh + 2 * nkv, hd)
    q, k, v = qkv[:, :, :nh], qkv[:, :, nh:nh + nkv], qkv[:, :, nh + nkv:]
    q, k = _rms(p["q_norm"], q, eps), _rms(p["k_norm"], k, eps)
    if "rotary" not in leave_out:
        q = _rope(q, positions, arch["rope_theta"])
        k = _rope(k, positions, arch["rope_theta"])
    out = attention(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)), s // 2,
                    arch["block_length"], leave_out)
    return out.transpose(0, 2, 1, 3).reshape(b, s, nh * hd) \
        @ p["proj"]["kernel"]


def route(p, h, top_k, leave_out=()):
    """``(chosen (T, k), weights (T, k))``: the ``k`` largest of the
    softmax over all the router's outputs, renormalised over the chosen."""
    logits = h @ p["router"]["kernel"]
    probs = jax.nn.sigmoid(logits) if "softmax" in leave_out \
        else jax.nn.softmax(logits, -1)
    w, chosen = jax.lax.top_k(probs, top_k)
    return chosen, w / w.sum(-1, keepdims=True)


def moe(p, h, arch, share=None, leave_out=()):
    """One expert layer on tokens ``h`` (T, d): ``(y, chosen)``. Every held
    expert multiplies every token; the router's weight (zero for a token
    that did not choose it) picks its part."""
    which, of = share or arch["expert_share"]
    held = p["w_gate"].shape[0]
    first = which * held
    if p["router"]["kernel"].shape[1] != held * of:
        raise ValueError("the tree's experts are not this share's")
    chosen, w = route(p, h, arch["num_experts_per_tok"], leave_out)

    def add(y, expert):
        e, gate, up, down = expert
        mine = (jnp.where(chosen == first + e, w, 0.0)).sum(-1)
        return y + mine[:, None] * (
            (jax.nn.silu(h @ gate) * (h @ up)) @ down), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), (
        jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"]))
    return y, chosen


def block(p, x, positions, arch, token_block, leave_out=()):
    """One decoder layer: ``(x, chosen)``."""
    b, s, d = x.shape
    eps = arch["rms_norm_eps"]
    x = x + gqa(p, _rms(p["ln1"], x, eps), positions, arch, leave_out)
    h = _rms(p["ln2"], x, eps).reshape(b * s, d)
    y, chosen = _in_blocks(
        lambda t: moe(p["moe"], t, arch, leave_out=leave_out), token_block, h)
    return x + y.reshape(b, s, d), chosen


def forward(params, tokens, masked, t, positions, arch, *, token_block=2048,
            leave_out=(), matrix_dtype=None):
    """``(loss, [chosen (2 B S, k) of each layer])`` for the windows
    ``tokens`` (B, S), their drawn mask bits ``masked`` (B, S) and the
    blocks' ``t`` (B, S // block_length)."""
    def leaf(a):
        a = a.astype(jnp.float32)
        if matrix_dtype is not None and a.ndim >= 2:
            # The gradient is the rounded matrix's own: taken through the
            # conversions it would be rounded to ``matrix_dtype`` itself.
            low = jax.lax.optimization_barrier(a.astype(matrix_dtype))
            a = a + jax.lax.stop_gradient(low.astype(jnp.float32) - a)
        return a

    p = jax.tree_util.tree_map(leaf, params["params"])
    b, s = tokens.shape
    mask_token = arch["mask_token"]
    with jax.default_matmul_precision("highest"):
        table = p["embed"]["tok"]["embedding"]
        both = jnp.concatenate(
            [jnp.where(masked, mask_token, tokens), tokens], axis=1)
        at = jnp.concatenate([positions, positions], axis=1)
        x, routed = table[both], []
        for i in range(sum(1 for name in p if name.startswith("block"))):
            x, chosen = jax.checkpoint(
                lambda p, x: block(p, x, at, arch, token_block, leave_out))(
                    p[f"block{i}"], x)
            routed.append(chosen)
        feats = _rms(p["lmhead"]["lnf"], x[:, :s], arch["rms_norm_eps"])
        head = p["lmhead"]["head"]["kernel"]

        def rows(f, tgt):
            logp = jax.nn.log_softmax(f @ head, -1)
            return -jnp.take_along_axis(logp, tgt[:, None], -1)[:, 0]

        nll = _in_blocks(rows, token_block, feats.reshape(b * s, -1),
                         tokens.reshape(b * s)).reshape(b, s)
        weight = masked.astype(jnp.float32)
        if "weight" not in leave_out:
            weight = weight / jnp.repeat(t, arch["block_length"], axis=1)
    return (nll * weight).sum() / (b * s), routed


def loss(params, tokens, masked, t, positions, *, arch, token_block=2048,
         leave_out=(), matrix_dtype=None):
    """The block-diffusion loss of the windows. ``arch``: ``heads``,
    ``num_key_value_heads``, ``head_dim``, ``num_experts_per_tok``,
    ``expert_share``, ``rope_theta``, ``rms_norm_eps``, ``block_length``,
    ``mask_token`` (the id itself)."""
    return forward(params, tokens, masked, t, positions, arch,
                   token_block=token_block, leave_out=leave_out,
                   matrix_dtype=matrix_dtype)[0]
