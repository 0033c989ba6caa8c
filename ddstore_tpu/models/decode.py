"""Incremental (KV-cached) decoding for :class:`TransformerLM`.

Training attends causally over the full sequence; generation wants one
token at a time against cached K/V — O(S) work per token instead of
O(S^2) re-prefill. The per-layer math here is applied through the SAME
flax submodules the training ``Block`` composes (LayerNorm/Dense applied
with the training param subtrees), so decode cannot drift from what
trained; the teacher-forcing oracle test pins every position's logits to
the full forward pass.

The reference has no text model and no inference path at all (its model
surface is the example VAE, /root/reference/examples/vae/vae-ddp.py:
174-200); this module is part of the LM family the TPU framework adds.

TPU notes: static shapes throughout — the cache is allocated at
``max_len`` up front and masked by position, generation is a
``lax.scan`` over time steps, every matmul keeps the (B, H) batch dims
so the MXU stays busy even at S=1.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .transformer import EmbedPE, LMHead, TransformerLM

Cache = Dict[str, jax.Array]

NEG_INF = float("-inf")


def init_cache(model: TransformerLM, batch: int, max_len: int) -> Cache:
    """Zeroed K/V cache: ``{"k","v"}`` of shape (layers, B, H, L, hd)."""
    hd = model.dim // model.heads
    shape = (model.layers, batch, model.heads, max_len, hd)
    return {"k": jnp.zeros(shape, model.compute_dtype),
            "v": jnp.zeros(shape, model.compute_dtype)}


def decode_step(model: TransformerLM, params, cache: Cache, pos,
                tokens, *, slot=None,
                live_mask: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Cache]:
    """One incremental step: ``tokens`` (B, 1) at position ``pos`` (a
    traced scalar — or a (B,) array of PER-ROW positions for padded
    variable-length batches) -> (logits (B, 1, V), updated cache).

    ``slot`` is the cache slot written this step; it defaults to ``pos``
    and must be a scalar (every row writes the same slot — with per-row
    positions, callers pass the uniform buffer slot and per-row
    ``live_mask``). ``live_mask`` (B, max_len) overrides the default
    "slots <= pos are attendable" rule, which is how padded prompts keep
    their dead padding slots invisible forever.

    ``slot`` must be < the cache's ``max_len`` — a concrete out-of-range
    value raises; a traced one is the caller's contract (generate never
    violates it). The layer math is deliberately written against the
    training param subtrees rather than refactoring Block around a cache
    argument; the teacher-forcing oracle (tests/test_decode.py) turns
    any drift between the two into a loud test failure.

    MoE blocks decode with DROPLESS per-token top-k routing (k =
    ``model.moe_top_k``): each token goes to its k best experts, no
    capacity clipping (a single decoded token cannot meaningfully
    compete for sequence-level capacity). Gates match training: raw
    router probability at k=1 (Switch), renormalized over the chosen k
    otherwise (GShard). Identical to the training forward wherever
    training dropped nothing; positions training clipped to zero-output
    get their experts applied instead — the standard train/infer
    asymmetry of capacity-factor MoE layers."""
    p = params["params"]
    dt = model.compute_dtype
    b = tokens.shape[0]
    hd = model.dim // model.heads
    max_len = cache["k"].shape[3]
    if slot is None:
        slot = pos
    if not isinstance(slot, jax.core.Tracer):
        islot = int(slot)
        if islot < 0 or islot >= max_len:
            raise ValueError(f"slot {islot} outside cache [0, {max_len}): "
                             "dynamic_update_slice would silently clamp "
                             "and corrupt a boundary slot")
    scale = 1.0 / math.sqrt(hd)

    positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                                 (b,))[:, None]
    x = EmbedPE(model.vocab, model.dim, dt).apply(
        {"params": p["embed"]}, tokens, positions)

    ln = nn.LayerNorm(dtype=jnp.float32)
    # Slot mask, same for every layer: by default cache slots <= slot are
    # live; a caller-supplied (B, max_len) mask handles padded batches.
    if live_mask is None:
        live = (jnp.arange(max_len) <= slot)[None, None, None, :]
    else:
        live = live_mask[:, None, None, :]
    # Update the stacked 5-D cache in place (dynamic_update_slice on the
    # scan carry — XLA aliases it; a per-layer slice + stack would copy
    # the whole cache every generated token).
    ck_all, cv_all = cache["k"], cache["v"]
    for i in range(model.layers):
        bp = p[f"block{i}"]
        h = ln.apply({"params": bp["ln1"]}, x).astype(dt)
        qkv = nn.Dense(3 * model.dim, use_bias=False, dtype=dt).apply(
            {"params": bp["qkv"]}, h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        to_heads = lambda t: t.reshape(b, 1, model.heads, hd).transpose(
            0, 2, 1, 3)  # (B, H, 1, hd)
        q, k, v = to_heads(q), to_heads(k), to_heads(v)
        ck_all = jax.lax.dynamic_update_slice(ck_all, k[None],
                                              (i, 0, 0, slot, 0))
        cv_all = jax.lax.dynamic_update_slice(cv_all, v[None],
                                              (i, 0, 0, slot, 0))

        s = jnp.einsum("bhqd,bhkd->bhqk", q, ck_all[i],
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(live, s, NEG_INF)
        a = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", a.astype(dt), cv_all[i],
                         preferred_element_type=jnp.float32)
        out = out.transpose(0, 2, 1, 3).reshape(b, 1, model.dim).astype(dt)
        x = x + nn.Dense(model.dim, use_bias=False, dtype=dt).apply(
            {"params": bp["proj"]}, out)

        h = ln.apply({"params": bp["ln2"]}, x).astype(dt)
        if model.n_experts > 0:
            mp = bp["moe"]
            h2 = h.reshape(b, model.dim)
            rl = jnp.einsum("bd,de->be", h2.astype(jnp.float32),
                            mp["router"]["kernel"])
            probs = jax.nn.softmax(rl, axis=-1)
            kk = model.moe_top_k
            topv, topi = jax.lax.top_k(probs, kk)             # (B, k)
            gates = topv if kk == 1 else \
                topv / jnp.sum(topv, axis=-1, keepdims=True)
            oh = jax.nn.one_hot(topi, model.n_experts,
                                dtype=jnp.float32)            # (B, k, E)
            # All-expert compute then one-hot combine: E× the FLOPs of
            # one expert, but static shapes and trivially small at S=1.
            he = jnp.einsum("bd,edh->beh", h2.astype(dt),
                            mp["w1"].astype(dt))
            he = nn.relu(he + mp["b1"][None].astype(dt))
            oe = jnp.einsum("beh,ehd->bed", he, mp["w2"].astype(dt))
            oe = oe + mp["b2"][None].astype(dt)
            y = jnp.einsum("bed,bke,bk->bd", oe.astype(jnp.float32),
                           oh, gates).astype(dt)
            x = x + y.reshape(b, 1, model.dim)
        else:
            h = nn.Dense(model.mlp_ratio * model.dim, dtype=dt).apply(
                {"params": bp["up"]}, h)
            h = nn.gelu(h)
            x = x + nn.Dense(model.dim, dtype=dt).apply(
                {"params": bp["down"]}, h)

    logits = LMHead(model.vocab).apply({"params": p["lmhead"]}, x)
    return logits, {"k": ck_all, "v": cv_all}


def filter_logits(lg: jax.Array, top_k: Optional[int] = None,
                  top_p: Optional[float] = None) -> jax.Array:
    """Nucleus / top-k filtering of (B, V) f32 logits: everything
    outside the kept set goes to -inf, so sampling never picks it.

    top_k keeps the k highest-logit tokens per row. top_p (nucleus)
    keeps the smallest prefix of the probability-sorted vocabulary whose
    mass reaches p (the highest-probability token always survives, so
    the distribution can never become empty). Both may be combined; the
    masks intersect."""
    lg = lg.astype(jnp.float32)
    if top_k is not None:
        if top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        kth = jax.lax.top_k(lg, top_k)[0][:, -1:]
        lg = jnp.where(lg < kth, NEG_INF, lg)
    if top_p is not None:
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_p is not None and top_p < 1.0:
        # (top_p == 1.0 is the identity; running it through the cumsum
        # would drop tokens whose probability rounds below f32 eps.)
        sorted_lg = jnp.sort(lg, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_lg, axis=-1)
        # exclusive cumulative mass BEFORE each token: the first token
        # whose preceding mass already reaches p is the first dropped.
        cum = jnp.cumsum(probs, axis=-1) - probs
        keep_sorted = cum < top_p
        # Per-row threshold logit: the smallest logit still kept.
        thresh = jnp.min(jnp.where(keep_sorted, sorted_lg, jnp.inf),
                         axis=-1, keepdims=True)
        lg = jnp.where(lg < thresh, NEG_INF, lg)
    return lg


def generate(model: TransformerLM, params, prompt: jax.Array,
             max_new_tokens: int, temperature: float = 0.0,
             key: Optional[jax.Array] = None,
             top_k: Optional[int] = None, top_p: Optional[float] = None,
             prompt_lengths: Optional[jax.Array] = None,
             prefill_mesh=None) -> jax.Array:
    """Autoregressive continuation of ``prompt`` (B, P) int32.

    Returns (B, P + max_new_tokens). ``temperature == 0`` is greedy;
    otherwise samples from softmax(logits / temperature) using ``key``,
    optionally filtered by ``top_k`` / ``top_p`` (nucleus) — see
    :func:`filter_logits`. The prompt prefills in ONE full forward pass
    (the blocks ``sow`` their K/V heads, which seed the cache) — O(1)
    sequential steps for the prompt instead of O(P) — then a
    ``lax.scan`` of cached steps decodes the new tokens. Shapes are
    static: each distinct (prompt length, max_new_tokens) pair compiles
    once.

    **Variable-length batches**: pass right-padded prompts plus
    ``prompt_lengths`` (B,) — row b's real tokens are
    ``prompt[b, :len_b]``; the pad values are arbitrary. Their cache
    slots are masked dead forever, every row's generated token j is
    embedded at ITS position ``len_b + j``, and all rows' new tokens
    land in slots/columns ``[P, P + max_new_tokens)``. Row b's full
    sequence is ``prompt[b, :len_b] ++ out[b, P:]``.

    **Long prompts**: ``prefill_mesh`` runs the one-pass prefill with
    the model's ring attention over that mesh's ``sp`` axis (sequence
    sharded, K/V rotating over ICI), for prompts a single device's
    memory can't hold; the decode scan itself stays data-parallel. The
    prompt's length is then a multiple of twice the ``sp`` size (the
    ring's two stripes a position); the blocks hand the cache their K/V
    in natural order.
    """
    if temperature > 0 and key is None:
        raise ValueError("sampling (temperature > 0) needs `key`")
    if max_new_tokens <= 0:
        return prompt
    b, plen = prompt.shape
    if plen < 1:
        raise ValueError("prompt must hold at least one token (the first "
                         "new token is conditioned on it)")
    total = plen + max_new_tokens
    cache = init_cache(model, b, total)
    keys = jax.random.split(key, total) if temperature > 0 else None
    if prompt_lengths is not None:
        lengths = jnp.asarray(prompt_lengths, jnp.int32)
        if lengths.shape != (b,):
            raise ValueError(f"prompt_lengths shape {lengths.shape} != "
                             f"({b},)")
        if not isinstance(lengths, jax.core.Tracer):
            lv = np.asarray(lengths)
            if (lv < 1).any() or (lv > plen).any():
                # 0 would make (lengths-1) clamp to the wrong feature
                # and > plen would mark phantom columns live — garbage
                # continuations with no error.
                raise ValueError(f"prompt_lengths must be in [1, {plen}]"
                                 f", got {lv.tolist()}")
    else:
        lengths = None

    def pick(lg, t):
        lg = filter_logits(lg, top_k, top_p)
        if temperature > 0:
            nxt = jax.random.categorical(keys[t], lg / temperature,
                                         axis=-1)
        else:
            nxt = jnp.argmax(lg, axis=-1)
        return nxt.astype(prompt.dtype)[:, None]

    # Prefill: one full forward over the prompt; blocks sow per-layer K/V
    # (B, H, plen, hd) which seed the cache, and the last position's
    # features produce the first new token (the head applies to that one
    # position only — the (B, plen, vocab) logits never materialize).
    # For dense models this is numerically the same stream as stepping
    # the prompt token by token (the greedy-vs-naive oracle pins it);
    # for MoE models the prefill applies TRAINING routing (capacity
    # clipping over the whole prompt), then cached steps are dropless —
    # the same train/infer asymmetry decode_step documents. With
    # prompt_lengths, pad positions are masked OUT of expert dispatch
    # (token_mask below) so they consume no capacity, and — when the
    # lengths are concrete — the per-expert capacity is computed from
    # the REAL token count: routing is then invariant to the pad amount
    # and matches the unpadded batch exactly. Traced lengths keep the
    # padded-count capacity (capacity must be static), which is merely
    # more generous; pads still cannot evict real tokens.
    clone_kw = dict(mesh=prefill_mesh, remat=False, sow_kv=True)
    tmask = None
    if lengths is not None and model.n_experts > 0:
        tmask = jnp.arange(plen)[None, :] < lengths[:, None]
        if model.moe_capacity is None and \
                not isinstance(lengths, jax.core.Tracer):
            from .moe import default_capacity

            nvalid = int(np.asarray(lengths).sum())
            clone_kw["moe_capacity"] = default_capacity(
                nvalid, model.n_experts, model.moe_top_k)
    pm = model.clone(**clone_kw)
    positions = jnp.tile(jnp.arange(plen, dtype=jnp.int32), (b, 1))
    feats, inter = pm.apply(params, prompt, positions, True,
                            mutable=("intermediates",),
                            token_mask=tmask)
    ks, vs = [], []
    for i in range(model.layers):
        (k, v), = inter["intermediates"][f"block{i}"]["kv"]
        ks.append(k.astype(model.compute_dtype))
        vs.append(v.astype(model.compute_dtype))
    cache = {
        "k": cache["k"].at[:, :, :, :plen, :].set(jnp.stack(ks)),
        "v": cache["v"].at[:, :, :, :plen, :].set(jnp.stack(vs)),
    }
    # feats are already post-lnf (features_only applies the LayerNorm);
    # apply ONLY the vocab projection — LMHead.apply here would LayerNorm
    # a second time, invisible at init (scale=1, bias=0 makes LN o LN a
    # no-op) but wrong for any trained model. With per-row lengths the
    # first new token conditions on each row's LAST REAL position (the
    # padding features beyond it are causal garbage and never read).
    w = params["params"]["lmhead"]["head"]["kernel"]
    last_feats = feats[:, -1, :] if lengths is None else \
        jnp.take_along_axis(feats, (lengths - 1)[:, None, None],
                            axis=1)[:, 0, :]
    last_logits = last_feats.astype(jnp.float32) @ w.astype(jnp.float32)
    first = pick(last_logits, plen - 1)
    toks = jnp.concatenate(
        [prompt, first, jnp.zeros((b, max_new_tokens - 1), prompt.dtype)],
        axis=1)
    col = jnp.arange(total)
    prompt_live = None if lengths is None else col[None, :] < \
        lengths[:, None]

    def body(carry, s):
        # Cache slot s holds the token at column s for EVERY row; with
        # per-row lengths its embedded position is the row's own
        # lengths + (s - plen), and dead padding slots [len_b, plen)
        # stay masked out of attention forever.
        cache, toks = carry
        cur = jax.lax.dynamic_slice(toks, (0, s), (b, 1))
        if lengths is None:
            logits, cache = decode_step(model, params, cache, s, cur)
        else:
            pos = lengths + (s - plen)
            live = prompt_live | ((col[None, :] >= plen)
                                  & (col[None, :] <= s))
            logits, cache = decode_step(model, params, cache, pos, cur,
                                        slot=s, live_mask=live)
        nxt = pick(logits[:, 0, :], s)
        toks = jax.lax.dynamic_update_slice(toks, nxt, (0, s + 1))
        return (cache, toks), None

    (_, toks), _ = jax.lax.scan(body, (cache, toks),
                                jnp.arange(plen, total - 1))
    return toks
