"""Plain reference for the ``mla_moe_lm`` family: a pre-RMSNorm decoder with
multi-head latent attention (MLA), a leading dense SwiGLU layer, shared +
routed SwiGLU experts under ``noaux_tc`` sigmoid routing, and one
multi-token-prediction (MTP) module, as ``glm4_moe_lite`` / DeepSeek-V3
(arXiv:2412.19437 sections 2.1, 2.2) define them: loss = CE_main +
``mtp_loss_weight`` x CE_mtp, as a float32 ``jax.numpy`` forward pass at
``highest`` matmul precision. ``jax.grad`` of :func:`loss` is the gradient
reference.

No flax, no kernels, no bfloat16, no remat, no sort and no grouped product:
attention is computed in blocks of query rows against the whole context
with a causal mask, MLPs and the head in blocks of tokens, and each held
expert as a dense product over every token, weighted by what the router
gave it (zero where it was not chosen). It imports nothing of
``ddstore_tpu.models`` and reads the system's parameter tree by layer name
only.

**The share.** ``share = (which, of)``: the tree holds the ``n // of``
consecutive routed experts from ``which * n // of`` of the router's ``n``
(this chip of the ``of`` that divide each layer between them). The router
scores all ``n``; only the held experts' part of the result, plus the
shared expert, is added, and that partial sum goes on to the next layer,
as in the program. ``(0, 1)`` is the uncut layer.

Departures from the published description, shared with the system and
listed in the configuration's ``assumed``: ``mtp_loss_weight`` (not in
``config.json``); the correction bias is a leaf like any other here (the
system gives it no gradient; its gradient here is zero too, since it only
steers a selection); rotary pairs are the two halves of the 64 rotary
dimensions; ``eh_proj`` takes [embedding ; hidden] in that order.
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
    """``fn`` over blocks of the leading axis (a divisor of it), joined."""
    n = arrays[0].shape[0]
    block = min(block, n)
    while n % block:
        block -= 1
    out = jax.lax.map(lambda i: fn(*(jax.lax.dynamic_slice_in_dim(
        a, i * block, block) for a in arrays)), jnp.arange(n // block))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((n,) + o.shape[2:]), out)


def attention(q, k, v, block=512):
    """q, k (B, H, S, Dqk), v (B, H, S, Dv) float32: causal
    softmax(q k^T / sqrt Dqk) v, one block of query rows at a time."""
    s = q.shape[2]
    kpos = jnp.arange(s)

    def rows(qi, qpos):
        sc = jnp.einsum("qbhd,bhkd->bhqk", qi, k) / math.sqrt(q.shape[-1])
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->qbhd", jax.nn.softmax(sc, -1), v)

    out = _in_blocks(rows, block, q.transpose(2, 0, 1, 3), jnp.arange(s))
    return out.transpose(1, 2, 0, 3)


def mla(p, x, positions, arch):
    """Multi-head latent attention, uncompressed: (B, S, d) -> (B, S, d)
    (the residual is the caller's)."""
    b, s, _ = x.shape
    nh, eps = arch["heads"], arch["rms_norm_eps"]
    nope, rot = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"]
    vd, lora = arch["v_head_dim"], arch["kv_lora_rank"]
    h = _rms(p["ln1"], x, eps)
    q = (_rms(p["q_norm"], h @ p["q_a"]["kernel"], eps)
         @ p["q_b"]["kernel"]).reshape(b, s, nh, nope + rot)
    kva = h @ p["kv_a"]["kernel"]
    kv = (_rms(p["kv_norm"], kva[..., :lora], eps)
          @ p["kv_b"]["kernel"]).reshape(b, s, nh, nope + vd)
    k_rope = _rope(kva[:, :, None, lora:], positions, arch["rope_theta"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], positions,
                                              arch["rope_theta"])], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (b, s, nh, rot))], -1)
    out = attention(*(t.transpose(0, 2, 1, 3)
                      for t in (q, k, kv[..., nope:])))
    return out.transpose(0, 2, 1, 3).reshape(b, s, nh * vd) \
        @ p["proj"]["kernel"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(p, h, top_k, scaling):
    """``(chosen (T, k), weights (T, k))``: the top ``k`` of sigmoid scores
    plus the correction bias; weights from the scores alone, normalised
    over the chosen, times ``scaling``."""
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])
    _, chosen = jax.lax.top_k(scores + p["router_bias"], top_k)
    w = jnp.take_along_axis(scores, chosen, -1)
    return chosen, w / w.sum(-1, keepdims=True) * scaling


def moe(p, h, arch, share=None, shared=True):
    """One expert layer on tokens ``h`` (T, d): ``(y, chosen)``. Every held
    expert multiplies every token; the router's weight (zero for a token
    that did not choose it) picks its part."""
    which, of = share or arch["expert_share"]
    held = p["w_gate"].shape[0]
    first = which * held
    if p["router"]["kernel"].shape[1] != held * of:
        raise ValueError("the tree's experts are not this share's")
    chosen, w = route(p, h, arch["num_experts_per_tok"],
                      arch["routed_scaling_factor"])
    y = jnp.zeros_like(h)
    for e in range(held):
        mine = (jnp.where(chosen == first + e, w, 0.0)).sum(-1)
        y = y + mine[:, None] * _swiglu(h, p["w_gate"][e], p["w_up"][e],
                                        p["w_down"][e])
    if shared:
        y = y + _swiglu(h, p["shared_gate"]["kernel"],
                        p["shared_up"]["kernel"], p["shared_down"]["kernel"])
    return y, chosen


def block(p, x, positions, arch, token_block):
    """One decoder layer; the kind of its MLP is read from its leaves.
    Returns ``(x, chosen or None)``."""
    b, s, d = x.shape
    x = x + mla(p, x, positions, arch)
    h = _rms(p["ln2"], x, arch["rms_norm_eps"]).reshape(b * s, d)
    if "moe" in p:
        y, chosen = _in_blocks(lambda t: moe(p["moe"], t, arch),
                               token_block, h)
    else:
        y, chosen = _in_blocks(lambda t: _swiglu(
            t, p["gate"]["kernel"], p["up"]["kernel"], p["down"]["kernel"]),
            token_block, h), None
    return x + y.reshape(b, s, d), chosen


def _nll(feats, w, targets, token_block):
    def rows(f, t):
        logp = jax.nn.log_softmax(f @ w, -1)
        return -jnp.take_along_axis(logp, t[:, None], -1)[:, 0]

    return _in_blocks(rows, token_block, feats, targets)


def forward(params, tokens, targets, positions, arch, *, token_block=2048):
    """``(loss, [chosen (B*S, k) of each expert layer, MTP's last])``."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                               params["params"])
    b, s = tokens.shape
    eps = arch["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        table = p["embed"]["tok"]["embedding"]
        x, routed = table[tokens], []
        for i in range(sum(1 for name in p if name.startswith("block"))):
            x, chosen = block(p[f"block{i}"], x, positions, arch, token_block)
            routed += [] if chosen is None else [chosen]
        w = p["lmhead"]["head"]["kernel"]
        flat = lambda a: a.reshape((b * s,) + a.shape[2:])
        loss = _nll(flat(_rms(p["lmhead"]["lnf"], x, eps)), w,
                    flat(targets), token_block).mean()
        if "mtp" in p:
            m = p["mtp"]
            # Position i: the main model's h_i (before its final norm) and
            # the embedding of token i + 1 predict token i + 2.
            y = jnp.concatenate([_rms(m["enorm"], table[targets], eps),
                                 _rms(m["hnorm"], x, eps)], -1) \
                @ m["eh_proj"]["kernel"]
            y, chosen = block(m["block"], y, positions, arch, token_block)
            routed.append(chosen)
            after = jnp.concatenate(
                [targets[:, 1:], jnp.zeros_like(targets[:, :1])], 1)
            nll = _nll(flat(_rms(m["norm"], y, eps)), w, flat(after),
                       token_block).reshape(b, s)[:, :-1]
            loss = loss + arch["mtp_loss_weight"] * nll.mean()
    return loss, routed


def loss(params, tokens, targets, positions, *, arch, token_block=2048):
    """CE_main + ``mtp_loss_weight`` x CE_mtp over all (B, S) positions
    (MTP: all but each window's last). ``arch``: ``heads``,
    ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
    ``v_head_dim``, ``num_experts_per_tok``, ``routed_scaling_factor``,
    ``expert_share``, ``rope_theta``, ``rms_norm_eps``,
    ``mtp_loss_weight``."""
    return forward(params, tokens, targets, positions, arch,
                   token_block=token_block)[0]
