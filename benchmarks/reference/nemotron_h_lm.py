"""Plain reference for the ``nemotron_h_lm`` family: a pre-RMSNorm decoder
every layer of which is one branch alone (``model_type`` ``nemotron_h``,
NVIDIA-Nemotron-3-Nano-30B-A3B): a Mamba-2 mixer, rotary-free
grouped-query attention, or a shared + routed expert MLP of ungated relu²
experts, and an untied head. Loss = mean next-token cross-entropy, as a
float32 ``jax.numpy`` forward pass at ``highest`` matmul precision.
``jax.grad`` of :func:`loss` is the gradient reference.

    x = x + Branch_i(RMSNorm(x))            one norm, one branch, no other
    Mamba-2:    [z | xBC | dt] = W_in h
                xBC = silu(b + sum_j w[j] xBC[t - (K-1) + j])   (0 before the start)
                [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
                S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_{g,t}^T   (S_0 = 0)
                y_t = S_t C_{g,t} + D_h x_t        head h reads group h // (H / G)
                y = RMSNorm over each of the G groups of (y * silu(z)), one scale
                out = W_out y
    attention:  query head h on K/V head h // group at the model's own
                head width, causal softmax at 1 / sqrt(head width); no
                rotary step, no q/k norm
    experts:    s = sigmoid(W_r h); the k largest of s + bias are chosen;
                weights s[chosen] / (sum s[chosen] + eps) * scaling;
                expert(h) = W_down relu(W_up h)^2; the shared expert the
                same form at its own width, added for every token
    head:       logits = W_head RMSNorm(x)

No flax, no kernels, no bfloat16, no sort, no grouped product and **no
chunked algebra**: the state-space scan is the recurrence as written, a
position at a time (``lax.scan`` over positions, in blocks of positions
each a ``jax.checkpoint`` so that the backward pass of 8,192 positions
keeps one state a block and not one a position); the convolution is ``K``
explicit shifted products on a zero-padded sequence; attention a
written-out masked softmax over K and V repeated a group, a block of query
rows at a time; MLPs and the head in blocks of tokens; each held expert a
dense product over every token (a ``lax.scan`` over the held ones),
weighted by what the router gave it (zero where it was not chosen). Each
layer is a ``jax.checkpoint``. It imports nothing of ``ddstore_tpu`` and
reads the system's parameter tree by layer name only; which kind a layer
is, it reads from the layer's leaves.

**The share.** ``share = (which, of)``: the tree holds the ``n // of``
consecutive routed experts from ``which * n // of`` of the router's ``n``.
The router scores all ``n``; only the held experts' part is added, with
the shared expert (which every chip computes alike), and that partial sum
goes on to the next layer, as in the program. ``(0, 1)`` is the uncut
layer. The vocabulary's slice is the embedding's and the head's rows.

Departures from the published description, shared with the system and
listed in the configuration's ``assumed``: the correction bias is a leaf
like any other here (its gradient is zero: it only steers a selection);
``[W_q | W_k | W_v]`` is one matrix ``qkv``; the taps are ``conv_taps``
(K, channels), the last row the current position's (the checkpoint's
(channels, 1, K) weight transposed); a mixer's norm is ``ln1``, an expert
layer's ``ln2``.

``leave_out`` names parts of the mathematics to leave out, for the
readings that set a cell's limits (each must come out not correct):
``"decay"`` (``A = 0``: the state forgets nothing), ``"older_taps"`` (the
convolution keeps its current position's tap alone), ``"experts"`` (the
held experts add nothing), ``"relu2"`` (the experts' activation read as
relu), ``"norm_groups"`` (the gated norm over all channels as one group);
``matrix_dtype`` rounds every matrix (two or more dimensions) to that type
first, e.g. ``float8_e4m3fn``, and the gradient is the rounded matrices'
own.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rms(p, x, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * p["scale"]


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


def scan_recurrence(x, dt, A, B, C, D, block=128):
    """The state-space recurrence a position at a time. ``x`` (b, S, H, P),
    ``dt`` (b, S, H), ``A`` and ``D`` (H,), ``B`` and ``C`` (b, S, G, N):
    ``y`` (b, S, H, P). Head h = (g, r) reads group g's ``B`` and ``C``;
    ``block`` positions are one ``jax.checkpoint``."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    r = h // g
    A = A.reshape(g, r)

    def position(state, at_t):
        x_t, dt_t, b_t, c_t = at_t         # (b,g,r,p) (b,g,r) (b,g,n) (b,g,n)
        state = jnp.exp(dt_t * A)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :]
        return state, (state * c_t[:, :, None, None, :]).sum(-1)

    block = min(block, s)
    while s % block:
        block -= 1

    @jax.checkpoint
    def positions(state, at):
        return jax.lax.scan(position, state, at)

    blocks = lambda t: t.swapaxes(0, 1).reshape(
        (s // block, block) + t.shape[:1] + t.shape[2:])
    _, y = jax.lax.scan(
        positions, jnp.zeros((b, g, r, p, n), jnp.float32),
        tuple(blocks(t) for t in (x.reshape(b, s, g, r, p),
                                  dt.reshape(b, s, g, r), B, C)))
    return y.reshape(s, b, h, p).swapaxes(0, 1) + D[:, None] * x


def mamba2(p, h, arch, leave_out=()):
    """The Mamba-2 mixer on normed ``h`` (B, S, d): (B, S, d)."""
    b, s, _ = h.shape
    nh, hp = arch["mamba_num_heads"], arch["mamba_head_dim"]
    g, n = arch["n_groups"], arch["ssm_state_size"]
    inner = nh * hp
    z, xbc, dt = jnp.split(h @ p["in_proj"]["kernel"],
                           (inner, 2 * inner + 2 * g * n), -1)
    taps = p["conv_taps"]
    k = taps.shape[0]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    c = jnp.zeros_like(xbc) + p["conv_bias"]
    for j in range(k):
        if j < k - 1 and "older_taps" in leave_out:
            continue
        c = c + taps[j] * padded[:, j:j + s]
    x, B, C = jnp.split(jax.nn.silu(c), (inner, inner + g * n), -1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    if "decay" in leave_out:
        A = jnp.zeros_like(A)
    y = scan_recurrence(x.reshape(b, s, nh, hp), dt, A,
                        B.reshape(b, s, g, n), C.reshape(b, s, g, n), p["D"])
    y = y.reshape(b, s, inner) * jax.nn.silu(z)
    groups = 1 if "norm_groups" in leave_out else g
    y = y.reshape(b, s, groups, inner // groups)
    y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True)
                          + arch["rms_norm_eps"])
    return (y.reshape(b, s, inner) * p["norm"]["scale"]) \
        @ p["out_proj"]["kernel"]


def attention(q, k, v, block=256):
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


def gqa(p, h, arch):
    """Rotary-free grouped-query attention on normed ``h``: (B, S, d)."""
    b, s, _ = h.shape
    nh, nkv, hd = arch["heads"], arch["num_key_value_heads"], \
        arch["head_dim"]
    qkv = (h @ p["qkv"]["kernel"]).reshape(b, s, nh + 2 * nkv, hd)
    q, k, v = qkv[:, :, :nh], qkv[:, :, nh:nh + nkv], qkv[:, :, nh + nkv:]
    out = attention(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)))
    return out.transpose(0, 2, 1, 3).reshape(b, s, nh * hd) \
        @ p["proj"]["kernel"]


def _expert(x, up, down, leave_out=()):
    a = jax.nn.relu(x @ up)
    return (a if "relu2" in leave_out else a * a) @ down


def route(p, h, top_k, scaling, eps):
    """``(chosen (T, k), weights (T, k))``: the top ``k`` of sigmoid scores
    plus the correction bias; weights from the scores alone, normalised
    over the chosen (the sum + ``eps``), times ``scaling``."""
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])
    _, chosen = jax.lax.top_k(scores + p["router_bias"], top_k)
    w = jnp.take_along_axis(scores, chosen, -1)
    return chosen, w / (w.sum(-1, keepdims=True) + eps) * scaling


def moe(p, h, arch, share=None, leave_out=()):
    """One expert layer on tokens ``h`` (T, d): ``(y, chosen)``. Every held
    expert multiplies every token; the router's weight (zero for a token
    that did not choose it) picks its part; the shared expert is added for
    every token."""
    which, of = share or arch["expert_share"]
    held = p["w_up"].shape[0]
    first = which * held
    if p["router"]["kernel"].shape[1] != held * of:
        raise ValueError("the tree's experts are not this share's")
    chosen, w = route(p, h, arch["num_experts_per_tok"],
                      arch["routed_scaling_factor"], arch["route_eps"])
    y = _expert(h, p["shared_up"]["kernel"], p["shared_down"]["kernel"],
                leave_out)
    if "experts" in leave_out:
        return y, chosen

    def add(y, expert):
        e, up, down = expert
        mine = (jnp.where(chosen == first + e, w, 0.0)).sum(-1)
        return y + mine[:, None] * _expert(h, up, down, leave_out), None

    y, _ = jax.lax.scan(add, y, (jnp.arange(held), p["w_up"], p["w_down"]))
    return y, chosen


def block(p, x, positions, arch, token_block, leave_out=()):
    """One layer, one branch; which, is read from its leaves. Returns
    ``(x, chosen or None)``."""
    b, s, d = x.shape
    eps = arch["rms_norm_eps"]
    if "moe" in p:
        h = _rms(p["ln2"], x, eps).reshape(b * s, d)
        y, chosen = _in_blocks(lambda t: moe(p["moe"], t, arch,
                                             leave_out=leave_out),
                               token_block, h)
        return x + y.reshape(b, s, d), chosen
    h = _rms(p["ln1"], x, eps)
    if "A_log" in p:
        return x + mamba2(p, h, arch, leave_out), None
    return x + gqa(p, h, arch), None


def forward(params, tokens, targets, positions, arch, *, token_block=2048,
            leave_out=(), matrix_dtype=None):
    """``(loss, [chosen (B*S, k) of each expert layer])``."""
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
    with jax.default_matmul_precision("highest"):
        x, routed = p["embed"]["tok"]["embedding"][tokens], []
        for i in range(sum(1 for name in p if name.startswith("block"))):
            x, chosen = jax.checkpoint(
                lambda p, x: block(p, x, positions, arch, token_block,
                                   leave_out))(p[f"block{i}"], x)
            routed += [] if chosen is None else [chosen]
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
    ``head_dim``, ``num_key_value_heads``, ``mamba_num_heads``,
    ``mamba_head_dim``, ``ssm_state_size``, ``n_groups``,
    ``num_experts_per_tok``, ``routed_scaling_factor``, ``route_eps``,
    ``expert_share``, ``rms_norm_eps``."""
    return forward(params, tokens, targets, positions, arch,
                   token_block=token_block, leave_out=leave_out,
                   matrix_dtype=matrix_dtype)[0]
