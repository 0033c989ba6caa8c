"""Mixture-of-experts layer with expert parallelism over the ``ep`` axis.

Top-k routing (Switch top-1 by default, GShard-style top-2+ optional)
with a fixed per-expert capacity: tokens are dispatched to expert
buffers with one-hot einsums (static shapes — no gather/scatter with
data-dependent sizes), the expert FFNs are batched einsums over a
leading expert dimension, and sharding that dimension over ``ep``
(``parallel.tp.expert_rules``) makes XLA insert the all-to-alls of
classic expert parallelism. Load balancing uses the standard Switch aux
loss (fraction-routed × mean-router-prob, scaled by E; ==1 at uniform).

Routing details:

* ``top_k > 1``: each token is dispatched to its k highest-probability
  experts with gates renormalized over the chosen k (``top_k=1`` keeps
  the raw Switch gate, preserving the original top-1 numerics).
  Capacity claims are CHOICE-MAJOR: every token's first choice is
  placed before any token's second choice, so overflow drops
  second-choice assignments first — the standard GShard priority.
* ``capacity``: explicit per-expert buffer size overriding the
  cf·k·T/E formula. ``capacity >= T`` makes routing dropless (each
  token sends at most one assignment per expert, so no overflow is
  possible). The one-pass MoE prefill (models/decode.py) uses this to
  compute capacity from the REAL token count of a padded batch, so the
  routing is invariant to how much padding the batch carries.
* ``valid`` (optional (T,) bool): tokens marked False are excluded
  from dispatch entirely — they consume no expert capacity, produce a
  zero output row, and drop out of the aux-loss statistics. This is
  how padded prompt positions are kept from evicting real tokens
  during one-pass MoE prefill (models/decode.py).

(EP is absent in the reference — SURVEY §2.2; with this module the
framework covers the full dp/tp/pp/sp/ep set.)

:class:`SharedRoutedMoe` is the other expert layer, the one a published
width can instantiate: shared + routed experts (SwiGLU, ReGLU, or ungated
relu²) under ``noaux_tc`` sigmoid routing with no dropped token, as one
expert-parallel chip's share (told which experts it holds, it routes over all of them and
computes its own part). ``MoeMlp`` stays what ``decode.py``,
``parallel/pipeline.py`` and ``parallel/tp.py`` build.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import moe_combine, moe_gmm
from ..utils import profile

# The router's correction bias is a seeded, non-zero leaf: its trained
# values and update speed are not in a published config, and a zero bias
# would leave the selection by ``scores + bias`` untested.
ROUTER_BIAS_STD = 0.1


def default_capacity(tokens: int, n_experts: int, top_k: int,
                     capacity_factor: float = 2.0) -> int:
    """THE per-expert buffer size rule: cf·k·T/E slots (k assignments
    per token), capped at T (beyond that extra slots can never fill —
    each token contributes at most one assignment per expert). Shared
    by :class:`MoeMlp` and the prefill path so the two cannot drift."""
    return min(tokens, max(1, int(capacity_factor * top_k * tokens
                                  / n_experts)))


class MoeMlp(nn.Module):
    """Drop-in MLP replacement: ``(T, d) -> ((T, d), aux_loss)``."""

    n_experts: int
    hidden: int
    capacity_factor: float = 2.0
    top_k: int = 1
    capacity: Optional[int] = None   # explicit override; >= T = dropless
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, valid: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
        t, d = x.shape
        e = self.n_experts
        k = self.top_k
        if not 1 <= k <= e:
            raise ValueError(f"top_k={k} must be in [1, n_experts={e}]")
        if self.capacity is not None and self.capacity < 1:
            # cap=0 would silently zero every token's output.
            raise ValueError(f"capacity={self.capacity} must be >= 1")
        cap = min(t, self.capacity) if self.capacity is not None else \
            default_capacity(t, e, k, self.capacity_factor)
        dt = self.compute_dtype

        # Router in f32 (tiny matmul; numerics matter more than speed).
        logits = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                          name="router")(x.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)             # (T, E)
        topv, topi = jax.lax.top_k(probs, k)                # (T, k)
        # top_k=1 keeps the raw router probability as the gate (Switch);
        # k>1 renormalizes over the chosen experts (GShard).
        gates = topv if k == 1 else \
            topv / jnp.sum(topv, axis=-1, keepdims=True)

        oh = jax.nn.one_hot(topi, e, dtype=jnp.float32)     # (T, k, E)
        if valid is not None:
            oh = oh * valid.astype(jnp.float32)[:, None, None]
        # Choice-major arrival order: flatten (k, T) with choice as the
        # slow axis, so all first choices claim capacity before any
        # second choice; 1-indexed position within each expert, tokens
        # past capacity are dropped (standard overflow).
        ohm = oh.transpose(1, 0, 2).reshape(k * t, e)
        pos = jnp.cumsum(ohm, axis=0) * ohm
        keep = (pos > 0) & (pos <= cap)
        dm = (keep[..., None] * jax.nn.one_hot(             # (k, T, E, C)
            (pos - 1).astype(jnp.int32), cap,
            dtype=jnp.float32)).reshape(k, t, e, cap)

        w1 = self.param("w1", nn.initializers.lecun_normal(),
                        (e, d, self.hidden))
        b1 = self.param("b1", nn.initializers.zeros, (e, self.hidden))
        w2 = self.param("w2", nn.initializers.lecun_normal(),
                        (e, self.hidden, d))
        b2 = self.param("b2", nn.initializers.zeros, (e, d))

        xin = jnp.einsum("ktec,td->ecd", dm, x.astype(jnp.float32))
        h = jnp.einsum("ecd,edh->ech", xin.astype(dt), w1.astype(dt))
        h = nn.relu(h + b1[:, None, :].astype(dt))
        out = jnp.einsum("ech,ehd->ecd", h, w2.astype(dt))
        out = out + b2[:, None, :].astype(dt)
        combine = jnp.einsum("ktec,tk->tec", dm, gates)
        y = jnp.einsum("tec,ecd->td", combine,
                       out.astype(jnp.float32))

        # Load-balancing loss: E · Σ_e f_e · p̄_e over VALID tokens,
        # f_e counting all k assignments (==1 at uniform for any k).
        if valid is None:
            nvalid = jnp.float32(t)
            mean_prob = probs.mean(axis=0)
        else:
            v = valid.astype(jnp.float32)
            nvalid = jnp.maximum(v.sum(), 1.0)
            mean_prob = (probs * v[:, None]).sum(axis=0) / nvalid
        frac = oh.sum(axis=(0, 1)) / (nvalid * k)
        aux = e * jnp.sum(frac * mean_prob)
        return y.astype(x.dtype), aux


def route_noaux_tc(scores: jax.Array, bias: jax.Array, top_k: int,
                   scaling: float, eps: float = 0.0
                   ) -> Tuple[jax.Array, jax.Array]:
    """``noaux_tc`` routing without a group limit (DeepSeek-V3, GLM-4 MoE,
    LFM2's ``use_expert_bias``): the ``top_k`` experts of ``scores + bias``
    are chosen, the weights come from the scores alone, normalised over the
    chosen and scaled: ``scores[chosen] / (sum scores[chosen] + eps) *
    scaling``. ``eps`` is the normaliser's epsilon as the family publishes
    it (0 for ``glm4_moe_lite``, 1e-6 for ``lfm2_moe``; static, and at 0
    the sum is divided by as it is). The bias only steers the choice, so no
    gradient reaches it. ``scores`` (T, E) float32 sigmoid outputs; returns
    ``(chosen (T, k) int32, weights (T, k) float32)``."""
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    total = jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w / (total + eps if eps else total) * scaling


def route_softmax(logits: jax.Array, top_k: int) -> Tuple[jax.Array,
                                                         jax.Array]:
    """Qwen3-MoE's routing (``norm_topk_prob`` true; SDAR's): a softmax
    over all the router's ``logits`` (T, E) in float32, the ``top_k``
    largest chosen, their probabilities renormalised over the chosen. No
    bias, no scaling. Returns ``(chosen (T, k) int32, weights (T, k)
    float32)``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, chosen = jax.lax.top_k(probs, top_k)
    return chosen, w / jnp.sum(w, axis=-1, keepdims=True)


def routed_chunk(tokens: int, top_k: int, held: int, n_routed: int) -> int:
    """The sorted rows :class:`SharedRoutedMoe` runs its routed experts over
    at a time: the rows even routing sends to this chip's ``held`` of
    ``n_routed`` experts and half as many again, in whole 1024s, and at
    most all ``tokens * top_k``. A layer that holds every expert takes all
    its rows at once."""
    pairs = tokens * top_k
    return min(pairs, -(-3 * pairs * held // (2 * n_routed * 1024)) * 1024)


@jax.custom_vjp
def _to_experts(x, head, rank, sizes):
    """Rows of ``x`` (T, d) laid out for the grouped product: row r is the
    token of pair ``head[r]`` (pairs are (token, choice), k a token; a
    ``head`` past the last pair is padding). ``rank`` (T, k) is the inverse
    (the row of each pair, counted from ``head``'s first); ``sizes`` the
    rows of each held expert among these, the first ``sizes.sum()`` rows
    the pairs whose experts are held here. The transpose is
    ``ops/moe_combine.py``'s kernel with unit weights, not autodiff's
    scatter-add, and reads only the held pairs' rows: the others'
    cotangents are not the grouped product's to define."""
    return x.at[head // rank.shape[1]].get(mode="clip")


def _to_experts_fwd(x, head, rank, sizes):
    return _to_experts(x, head, rank, sizes), (rank, sizes)


def _to_experts_bwd(res, dxs):
    rank, sizes = res
    return (moe_combine.moe_combine(dxs, rank, sizes, dtype=dxs.dtype),
            None, None, None)


_to_experts.defvjp(_to_experts_fwd, _to_experts_bwd)


@jax.custom_vjp
def _from_experts(ys, w, head, rank, sizes):
    """``y[t] = sum_j w[t, j] ys[rank[t, j]]`` over the held pairs among
    these rows, float32: the weighted way back from the grouped product's
    rows (same layout as :func:`_to_experts`), ``ops/moe_combine.py``'s
    kernel, which reads the held pairs' rows and no others. The weights'
    cotangent is taken row by row in the sorted layout, where only
    ``len(head)`` rows are."""
    return moe_combine.moe_combine(ys, rank, sizes, w)


def _from_experts_fwd(ys, w, head, rank, sizes):
    return _from_experts(ys, w, head, rank, sizes), (ys, w, head, rank, sizes)


def _from_experts_bwd(res, dy):
    ys, w, head, rank, sizes = res
    held = jnp.arange(len(head)) < sizes.sum()
    dyr = dy.at[head // rank.shape[1]].get(mode="clip")
    dys = jnp.where(
        held[:, None],
        dyr * w.reshape(-1).at[head].get(mode="clip")[:, None], 0)
    # a row's weight cotangent to its pair: rows are distinct pairs (the
    # padding past the last pair is dropped), and a scatter of C scalars
    # is cheaper than a gather of T k through rank
    dws = jnp.where(held, (ys.astype(jnp.float32) * dyr).sum(axis=-1), 0)
    dw = jnp.zeros(w.size, jnp.float32).at[head].set(
        dws, unique_indices=True, mode="drop")
    return (dys.astype(ys.dtype), dw.reshape(w.shape).astype(w.dtype), None,
            None, None)


_from_experts.defvjp(_from_experts_fwd, _from_experts_bwd)


# A gated expert's gate: ``down(gate(w_gate x) * w_up x)``.
_GATES = {"swiglu": nn.silu, "reglu": nn.relu}


def routed_rows(rows, start, x, weights, order, rank, sizes, ws,
                activation="swiglu"):
    """What the sorted rows ``[start, start + rows)`` (``rows`` static) add
    to the routed experts' part of the layer, (T, d) float32: the weighted
    sum over each token's held pairs among them. ``x`` (T, d) in the
    compute dtype, ``weights`` (T, k), ``order`` the pairs sorted by held
    expert (at least ``start + rows`` long: padded past the last pair),
    ``rank`` (T, k) its inverse, ``sizes`` (held,) the rows of each held
    expert, ``ws`` the expert weights as the parameters are: ``(w_gate,
    w_up, w_down)`` of gated experts (``activation`` ``swiglu``, ``silu(gate)
    * up``, or ``reglu``, ``relu(gate) * up``), or ``(w_up, w_down)`` of
    ungated ones (``relu2``), ``relu(up)^2``. One trip of
    :func:`_routed`."""
    with jax.named_scope("moe_dispatch"):
        ends = jnp.cumsum(sizes)
        inside = lambda row: jnp.clip(row, start, start + rows)
        here = inside(ends) - inside(ends - sizes)  # of each held expert
        head = jax.lax.dynamic_slice_in_dim(order, start, rows)
        local = rank - start
        xs = _to_experts(x, head, local, here)
        with jax.named_scope("moe_experts"):
            *w_in, w_down = (w.astype(x.dtype) for w in ws)
            # the experts' width in whole lane tiles, on the copies in the
            # compute type: zero columns in, zero rows out (both
            # activations keep 0 at 0, so ``h``'s extra columns are zero)
            extra = moe_gmm.padded(w_down.shape[1]) - w_down.shape[1]
            if extra:
                w_in = [jnp.pad(w, ((0, 0), (0, 0), (0, extra)))
                        for w in w_in]
                w_down = jnp.pad(w_down, ((0, 0), (0, extra), (0, 0)))
            # one walk over the groups' rows for the trip's products
            product = functools.partial(
                moe_gmm.moe_gmm, sizes=here,
                steps=moe_gmm.row_steps(here, rows))
            if len(w_in) == 2:
                h = _GATES[activation](product(xs, w_in[0])) \
                    * product(xs, w_in[1])
            else:
                h = jnp.square(nn.relu(product(xs, w_in[0])))
            ys = product(h, w_down)
        return _from_experts(ys, weights, head, local, here)


def _over_live_rows(rows, order, sizes, trip):
    """The sum of ``trip(order, start)`` (a tree of arrays, added in their
    own types) over the starts 0, ``rows``, 2 ``rows`` ... below
    ``sizes.sum()``, the live rows: the first trip always and in line, the
    others (none, under any routing near even) in a loop whose count the
    device reads off the routing. ``trip`` gets ``order`` padded to whole
    trips."""
    pairs = len(order)
    if rows >= pairs:
        return trip(order, 0)
    order = jnp.concatenate(
        [order, pairs + jnp.arange(-pairs % rows, dtype=order.dtype)])
    with jax.named_scope("moe_dispatch"):
        trips = -(-sizes.sum() // rows)

        def more(carry):
            i, total = carry
            return i + 1, jax.tree_util.tree_map(
                jnp.add, total, trip(order, i * rows))

        return jax.lax.while_loop(lambda carry: carry[0] < trips, more,
                                  (jnp.int32(1), trip(order, 0)))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _routed(rows, activation, x, weights, order, rank, sizes, ws):
    """The routed experts' part of the layer, (T, d) float32, over as many
    sorted rows as this step's routing fills: :func:`routed_rows` in trips
    of ``rows`` (static) until every live row is done, so every held pair
    is computed whatever the routing and a step pays for the trips it
    needs. Both rules walk the rows themselves and keep nothing but the
    inputs, so nothing ``T k`` rows long is ever built; the backward rule
    computes each trip's forward again, as ``nn.remat`` around the block
    does for everything else."""
    return _over_live_rows(rows, order, sizes, lambda order, start: (
        routed_rows(rows, start, x, weights, order, rank, sizes, ws,
                    activation)))


def _routed_fwd(rows, activation, *args):
    return _routed(rows, activation, *args), args


def _routed_bwd(rows, activation, args, dy):
    x, weights, order, rank, sizes, ws = args

    def back(order, start):
        # float32 for the weights, as they are; x's in x's own type. The
        # trip's forward again is the pass ``recompute`` in a trace
        # (``profile.describe``), its transposes the backward's.
        with jax.named_scope(profile.RECOMPUTE):
            pull = jax.vjp(
                lambda x, weights, ws: routed_rows(
                    rows, start, x, weights, order, rank, sizes, ws,
                    activation),
                x, weights, ws)[1]
        return pull(dy)

    dx, dweights, dws = _over_live_rows(rows, order, sizes, back)
    return (dx, dweights, None, None, None, dws)


_routed.defvjp(_routed_fwd, _routed_bwd)


class SharedRoutedMoe(nn.Module):
    """Shared + routed experts with no dropped token, as one
    expert-parallel chip's share: ``(T, d) -> ((T, d), load (n_routed,))``.
    ``n_shared = 0`` is a layer of routed experts alone: no shared
    parameters and no shared products. ``activation`` is the experts' form,
    routed and shared alike: ``swiglu`` (three matrices an expert,
    ``down(silu(gate x) * up x)``), ``reglu`` (three, ``down(relu(gate x) *
    up x)``) or ``relu2`` (two and no gate, ``down(relu(up x)^2)``: no
    ``w_gate`` / ``shared_gate`` leaves). The
    shared expert is ``n_shared * hidden`` wide, or ``shared_hidden`` where
    its width is a key of its own.

    ``share = (which, of)``: this chip is number ``which`` of ``of`` that
    divide the layer's ``n_routed`` experts between them, and holds the
    consecutive ``n_routed // of`` from ``which * n_routed // of``. It
    routes over all ``n_routed`` (``scoring`` ``sigmoid``: sigmoid scores
    and a correction bias, :func:`route_noaux_tc`; ``softmax``:
    :func:`route_softmax`, no ``router_bias`` leaf, ``scaling`` and
    ``route_eps`` unread), computes every (token, chosen expert) pair whose expert it holds, and
    adds the shared expert, which every chip computes alike. What the other
    chips' experts would add is not here and nothing stands in for it: on
    one chip the layer runs without its exchange. ``load`` counts the
    tokens routed to each of the ``n_routed`` experts (int32).

    Every (token, chosen expert) pair is sorted by held expert, the pairs
    of absent experts behind them, and the held rows are multiplied by
    ``ops/moe_gmm.py``'s Pallas kernels (``ddstore_moe_gmm``, and in the
    backward its transposed form and ``ddstore_moe_tgmm``; ``lax.ragged_dot``'s
    contract, the work of the rows each expert got and no more, on copies
    of the held matrices in the compute type whose experts' width is
    padded with zeros to whole lane tiles: PERF.md section 6, PR 36).
    Shapes are static, and the gathers and SwiGLU around the products cost
    what the buffer's length is, not what the routing fills (the way back
    to the tokens, ``ops/moe_combine.py``'s kernel, reads the held pairs'
    rows alone); so the routed part (:func:`_routed`) walks
    the sorted rows in trips of :func:`routed_chunk` rows, as many as the
    step's held pairs need, counted on the device: one under routing near
    even, all ``T k`` rows when every pair lands here. No pair is ever
    dropped, whatever the routing; a layer that holds every expert takes
    its rows at once.

    ``logits`` (T, n_routed) float32, where given: the router's, taken by
    the caller from another input than the rows multiplied (a router placed
    before attention); the layer then has no ``router`` leaf of its own.
    """

    n_routed: int
    top_k: int
    hidden: int
    share: Tuple[int, int] = (0, 1)
    scaling: float = 1.0
    n_shared: int = 1
    route_eps: float = 0.0     # route_noaux_tc's normaliser epsilon
    compute_dtype: Any = jnp.bfloat16
    activation: str = "swiglu"
    shared_hidden: Optional[int] = None
    scoring: str = "sigmoid"

    @nn.compact
    def __call__(self, x, logits: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
        t, d = x.shape
        e, k, dt = self.n_routed, self.top_k, self.compute_dtype
        which, of = self.share
        if e % of or not 0 <= which < of:
            raise ValueError(f"share {self.share} does not divide "
                             f"{e} routed experts")
        if self.activation not in ("swiglu", "reglu", "relu2"):
            raise ValueError(f"activation {self.activation!r} is not built "
                             f"here (only 'swiglu', 'reglu' and 'relu2')")
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring {self.scoring!r} is not built here "
                             f"(only 'sigmoid' and 'softmax')")
        gated = self.activation in _GATES
        held = e // of
        first = which * held
        rows = routed_chunk(t, k, held, e)
        wide = moe_gmm.padded(self.hidden)
        profile.count_moe_layout(
            "/".join(self.path), held=held, of=e, first=first, top_k=k,
            tokens=t, rows=rows, products=moe_gmm.PRODUCTS,
            combine=moe_combine.COMBINE,
            scoring=self.scoring, activation=self.activation,
            early_router=logits is not None,
            tiles=moe_gmm.layer_tiles(rows, d, wide, dt),
            **({"padded_to": wide} if wide != self.hidden else {}))

        experts = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
            batch_axis=(0,))
        ws = tuple(self.param(name, experts, shape) for name, shape in (
            ("w_gate", (held, d, self.hidden)),
            ("w_up", (held, d, self.hidden)),
            ("w_down", (held, self.hidden, d)))[0 if gated else 1:])

        with jax.named_scope("moe_dispatch"):
            # Router and its statistics in float32.
            if logits is None:
                logits = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                                  name="router")(x.astype(jnp.float32))
            if self.scoring == "softmax":
                chosen, weights = route_softmax(logits, k)
            else:
                bias = self.param(
                    "router_bias", nn.initializers.normal(ROUTER_BIAS_STD),
                    (e,))
                chosen, weights = route_noaux_tc(
                    jax.nn.sigmoid(logits), bias, k, self.scaling,
                    self.route_eps)
            # For whoever asks with mutable=["intermediates"] (chip_smoke's
            # count of near-tie tokens); nothing otherwise.
            self.sow("intermediates", "chosen", chosen)
            flat = chosen.reshape(t * k)
            load = (flat[:, None] == jnp.arange(e)).sum(0, dtype=jnp.int32)
            # Pairs sorted by held expert; the pairs of absent experts
            # sort behind them and the grouped product never reads them.
            local = (flat >= first) & (flat < first + held)
            order = jnp.argsort(jnp.where(local, flat - first, held),
                                stable=True).astype(jnp.int32)
            rank = jnp.argsort(order).astype(jnp.int32).reshape(t, k)
            sizes = jax.lax.dynamic_slice_in_dim(load, first, held)
        y = _routed(rows, self.activation, x.astype(dt), weights, order,
                    rank, sizes, ws)

        if not self.n_shared:
            return y.astype(x.dtype), load
        # The shared expert: the block's dense MLP work, on every chip.
        wide = self.shared_hidden or self.n_shared * self.hidden
        lin = lambda n, name: nn.Dense(n, use_bias=False, dtype=dt,
                                       name=name)
        with jax.named_scope("shared_expert"):
            if gated:
                hs = _GATES[self.activation](lin(wide, "shared_gate")(x)) \
                    * lin(wide, "shared_up")(x)
            else:
                hs = jnp.square(nn.relu(lin(wide, "shared_up")(x)))
            ys = lin(d, "shared_down")(hs).astype(jnp.float32)
        return (y + ys).astype(x.dtype), load
