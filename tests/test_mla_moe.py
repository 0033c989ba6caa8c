"""The latent-attention, shared + routed expert, MTP architecture
(``TransformerLM(arch=...)``) against its plain reference
(``benchmarks/reference/mla_moe_lm.py``) on seeded weights at a small size,
and the pieces one by one: MLA, the router, the no-drop dispatch, the
expert-parallel share, MTP's targets, the bias leaf, the description."""

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddstore_tpu.models import mixers, moe, transformer as T
from ddstore_tpu.utils import profile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "ref_mla_moe_lm", os.path.join(ROOT, "benchmarks", "reference",
                                   "mla_moe_lm.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# One dense layer, two expert layers and the MTP module; 2 of 16 routed
# experts held (chip 1 of 8), 4 a token.
DESC = dict(
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
    qk_rope_head_dim=4, v_head_dim=16, intermediate_size=96,
    moe_intermediate_size=24, n_routed_experts=2, num_experts_per_tok=4,
    n_shared_experts=1, routed_scaling_factor=1.8, first_k_dense_replace=1,
    rope_theta=1e6, rms_norm_eps=1e-5, num_nextn_predict_layers=1,
    vocab_size=128, hidden_size=32, num_attention_heads=4,
    num_hidden_layers=3, expert_parallel={"chips": 8, "chip": 1})
B, S = 4, 16


def ref_arch(model):
    a = model.arch
    return dict(a._asdict(), heads=model.heads)


def batch(seed=0, vocab=128):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, vocab, (B, S)).astype(np.int32)
    tgt = rng.integers(0, vocab, (B, S)).astype(np.int32)
    return tok, tgt, np.tile(np.arange(S, dtype=np.int32), (B, 1))


@pytest.fixture(scope="module")
def built():
    model = T.lm_from_description(DESC, compute_dtype=jnp.float32)
    state, tx = T.create_train_state(jax.random.key(3), model, lr=1e-3)
    return model, state, tx


def _leaves_agree(grads, want_grads, atol=2e-4):
    """Every leaf of ``grads`` against the same leaf of ``want_grads``, in
    units of the latter's largest entry; returns the number of leaves."""
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    wflat = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    assert len(flat) == len(wflat)
    for path, g in flat:
        w = wflat[path]
        scale = max(float(jnp.abs(w).max()), 1e-6)
        np.testing.assert_allclose(
            np.asarray(g) / scale, np.asarray(w) / scale, atol=atol,
            err_msg=jax.tree_util.keystr(path))
    return len(flat)


def test_loss_and_every_gradient_leaf_match_the_reference(built):
    model, state, _ = built
    tok, tgt, pos = batch()
    with jax.default_matmul_precision("highest"):
        (loss, loads), grads = jax.value_and_grad(
            lambda p: T.lm_loss(model, p, tok, tgt, pos), has_aux=True)(
                state.params)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tok, tgt, pos, arch=ref_arch(model)))(
            state.params)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    assert loads.shape == (3, 16) and loads.dtype == jnp.int32
    assert (np.asarray(loads).sum(1) == B * S * 4).all()
    assert _leaves_agree(grads, want_grads) > 60


def test_the_fused_head_gives_the_same_loss(built):
    model, state, _ = built
    tok, tgt, pos = batch(1)
    plain, _ = T.lm_loss(model, state.params, tok, tgt, pos,
                         fused_xent=False)
    fused, _ = T.lm_loss(model, state.params, tok, tgt, pos,
                         fused_xent=True, xent_block=48)
    np.testing.assert_allclose(fused, plain, rtol=1e-5)


def test_mla_alone_against_a_written_out_softmax():
    arch = T.MlaMoeArch(q_lora_rank=12, kv_lora_rank=8, qk_nope_head_dim=6,
                        qk_rope_head_dim=4, v_head_dim=10,
                        intermediate_size=16, moe_intermediate_size=8,
                        n_routed_experts=4, num_experts_per_tok=2,
                        rope_theta=1e4)
    blk = T.DecoderBlock(16, 2, arch, "dense", jnp.float32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 5, 16)), jnp.float32)
    pos = jnp.asarray([[3, 4, 5, 6, 7]], jnp.int32)
    params = blk.init(jax.random.key(0), x, pos)
    p = params["params"]
    # Undo the MLP: zero its output projection, so the block is x + MLA.
    p = dict(p, down={"kernel": jnp.zeros_like(p["down"]["kernel"])})
    got = blk.apply({"params": p}, x, pos) - x

    rms = lambda v, g: v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5) * g
    n = lambda name: np.asarray(p[name]["kernel"], np.float64)
    xs = np.asarray(x[0], np.float64)
    h = rms(xs, np.asarray(p["ln1"]["scale"]))
    q = (rms(h @ n("q_a"), np.asarray(p["q_norm"]["scale"]))
         @ n("q_b")).reshape(5, 2, 10)
    kva = h @ n("kv_a")
    kv = (rms(kva[:, :8], np.asarray(p["kv_norm"]["scale"]))
          @ n("kv_b")).reshape(5, 2, 16)

    def rot(v, t):
        out = v.copy()
        for i in range(2):
            ang = t * 1e4 ** (-i / 2)
            a, b = v[..., i], v[..., i + 2]
            out[..., i] = a * math.cos(ang) - b * math.sin(ang)
            out[..., i + 2] = b * math.cos(ang) + a * math.sin(ang)
        return out

    heads = []
    for hd in range(2):
        rows = []
        for i in range(5):
            qi = np.concatenate([q[i, hd, :6], rot(q[i, hd, 6:], 3 + i)])
            sc = []
            for j in range(i + 1):
                kj = np.concatenate([kv[j, hd, :6], rot(kva[j, 8:], 3 + j)])
                sc.append(qi @ kj / math.sqrt(10))
            w = np.exp(np.array(sc) - max(sc))
            w /= w.sum()
            rows.append(sum(w[j] * kv[j, hd, 6:] for j in range(i + 1)))
        heads.append(np.stack(rows))
    want = np.concatenate(heads, -1) @ n("proj")
    np.testing.assert_allclose(got[0], want, atol=2e-5)


# A latent-attention mixer's leaves, as a ``glm4_moe_lite`` checkpoint names
# and shapes them (``kv_b`` one matrix, a head's key columns then its value
# columns), at DESC's widths: 4 heads of 12 | 4 and 16.
_MIXER_LEAVES = {
    "ln1/scale": (32,), "q_a/kernel": (32, 24), "q_norm/scale": (24,),
    "q_b/kernel": (24, 64), "kv_a/kernel": (32, 20), "kv_norm/scale": (16,),
    "kv_b/kernel": (16, 112), "proj/kernel": (64, 32)}
_DENSE_LEAVES = {"ln2/scale": (32,), "gate/kernel": (32, 96),
                 "up/kernel": (32, 96), "down/kernel": (96, 32)}
_EXPERT_LEAVES = {
    "ln2/scale": (32,), "moe/router/kernel": (32, 16),
    "moe/router_bias": (16,), "moe/shared_gate/kernel": (32, 24),
    "moe/shared_up/kernel": (32, 24), "moe/shared_down/kernel": (24, 32),
    "moe/w_gate": (2, 32, 24), "moe/w_up": (2, 32, 24),
    "moe/w_down": (2, 24, 32)}


@pytest.mark.parametrize("block,rest", [("block0", _DENSE_LEAVES),
                                        ("block1", _EXPERT_LEAVES),
                                        ("block2", _EXPERT_LEAVES),
                                        ("mtp/block", _EXPERT_LEAVES)])
def test_a_blocks_parameter_tree_is_the_written_one(built, block, rest):
    """Paths and shapes of every leaf of a block against the written list:
    taking V from its own product changed how ``kv_b`` is applied, not
    what is stored (a checkpoint, Adam's state and the reference's mapping
    see the same tree)."""
    _, state, _ = built
    tree = state.params["params"]
    for name in block.split("/"):
        tree = tree[name]
    got = {"/".join(k.key for k in path): leaf.shape for path, leaf in
           jax.tree_util.tree_leaves_with_path(tree)}
    assert got == {**_MIXER_LEAVES, **rest}
    assert all(leaf.dtype == jnp.float32
               for leaf in jax.tree_util.tree_leaves(tree))


def test_kv_b_is_the_leaf_a_dense_layer_would_draw():
    """``_LatentKV`` under the name ``kv_b`` draws the kernel ``nn.Dense``
    draws there, and its two products are that layer's output, the key
    columns and the value columns of each head."""
    import flax.linen as nn

    class Split(nn.Module):
        @nn.compact
        def __call__(self, c):
            return mixers._LatentKV(4, 12, 16, jnp.float32, name="kv_b")(c)

    class Whole(nn.Module):
        @nn.compact
        def __call__(self, c):
            return nn.Dense(112, use_bias=False, name="kv_b")(c)

    c = jax.random.normal(jax.random.key(1), (2, 5, 16), jnp.float32)
    split, whole = (m.init(jax.random.key(0), c) for m in (Split(), Whole()))
    np.testing.assert_array_equal(split["params"]["kv_b"]["kernel"],
                                  whole["params"]["kv_b"]["kernel"])
    with jax.default_matmul_precision("highest"):
        k_nope, v = Split().apply(split, c)
        kv = Whole().apply(whole, c).reshape(2, 5, 4, 28)
    np.testing.assert_allclose(k_nope.reshape(2, 5, 4, 12), kv[..., :12],
                               atol=1e-6)
    np.testing.assert_allclose(v.reshape(2, 5, 4, 16), kv[..., 12:],
                               atol=1e-6)


def test_router_selects_by_score_plus_bias_and_weighs_by_score():
    scores = jnp.asarray([[0.9, 0.8, 0.7, 0.1], [0.2, 0.6, 0.5, 0.4]])
    chosen, w = moe.route_noaux_tc(scores, jnp.zeros(4), 2, 1.8)
    assert chosen.tolist() == [[0, 1], [1, 2]]
    np.testing.assert_allclose(w, [[1.8 * 0.9 / 1.7, 1.8 * 0.8 / 1.7],
                                   [1.8 * 0.6 / 1.1, 1.8 * 0.5 / 1.1]],
                               rtol=1e-6)
    # The bias changes who is chosen, and never a weight.
    chosen, w = moe.route_noaux_tc(scores, jnp.asarray([0., 0., 0., 0.75]),
                                   2, 1.8)
    assert chosen.tolist() == [[0, 3], [3, 1]]
    np.testing.assert_allclose(w, [[1.8 * 0.9 / 1.0, 1.8 * 0.1 / 1.0],
                                   [1.8 * 0.4 / 1.0, 1.8 * 0.6 / 1.0]],
                               rtol=1e-6)


def _layer(share, n_routed=16, top_k=4):
    return moe.SharedRoutedMoe(n_routed, top_k, 24, share=share,
                               scaling=1.8, compute_dtype=jnp.float32)


def _arch(share):
    return dict(num_experts_per_tok=4, routed_scaling_factor=1.8,
                expert_share=share)


def test_no_token_is_dropped_when_every_token_goes_to_the_held_experts():
    """A bias that sends every token to experts 0-3, all held here: every
    one of the T x k pairs is computed (both chunks of the sorted rows run,
    where even routing fills half of the first)."""
    layer = _layer((0, 4))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 32)),
                    jnp.float32)
    p = layer.init(jax.random.key(1), x)["params"]
    p = dict(p, router_bias=jnp.where(jnp.arange(16) < 4, 10.0, 0.0))
    with jax.default_matmul_precision("highest"):
        y, load = layer.apply({"params": p}, x)
    assert load.tolist() == [64] * 4 + [0] * 12
    want, _ = ref.moe(p, x, _arch((0, 4)))
    np.testing.assert_allclose(y, want, atol=1e-5)
    # and when none is held here, only the shared expert answers
    p = dict(p, router_bias=jnp.where(jnp.arange(16) >= 12, 10.0, 0.0))
    with jax.default_matmul_precision("highest"):
        y, load = layer.apply({"params": p}, x)
    assert load.tolist() == [0] * 12 + [64] * 4
    want, _ = ref.moe(p, x, _arch((0, 4)), shared=True)
    np.testing.assert_allclose(y, want, atol=1e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Routed parts of all eight chips' shares + the shared expert once ==
    the reference's uncut 16-expert layer."""
    x = jnp.asarray(np.random.default_rng(2).normal(size=(48, 32)),
                    jnp.float32)
    whole = _layer((0, 1)).init(jax.random.key(5), x)["params"]
    want, _ = ref.moe(whole, x, _arch((0, 1)))
    shared = ref._swiglu(x, whole["shared_gate"]["kernel"],
                         whole["shared_up"]["kernel"],
                         whole["shared_down"]["kernel"])
    total, loads = shared, []
    for which in range(8):
        cut = dict(whole, **{k: whole[k][2 * which:2 * which + 2]
                             for k in ("w_gate", "w_up", "w_down")})
        with jax.default_matmul_precision("highest"):
            y, load = _layer((which, 8)).apply({"params": cut}, x)
        total = total + (y - shared)
        loads.append(load)
    np.testing.assert_allclose(total, want, atol=2e-5)
    # every chip routes over all 16 alike
    assert all((ld == loads[0]).all() for ld in loads)
    assert int(loads[0].sum()) == 48 * 4


def test_moe_layout_counter_says_what_is_held(built):
    layout = profile.counters()["moe_layout"]
    mine = layout["block1/moe"]
    assert {k: mine[k] for k in ("held", "of", "first", "top_k")} == dict(
        held=2, of=16, first=2, top_k=4)
    # the sorted rows of one trip: at this size, every pair
    assert mine["rows"] == mine["tokens"] * 4
    assert set(layout) >= {"block1/moe", "block2/moe", "mtp/block/moe"}
    from test_transformer import assert_the_layout_names_the_products
    model = built[0]
    for layer in ("block1/moe", "block2/moe", "mtp/block/moe"):
        assert_the_layout_names_the_products(
            layout[layer], model.dim, model.arch.moe_intermediate_size)


@pytest.mark.parametrize("tokens,top_k,held,n_routed,want", [
    (16384, 4, 8, 64, 12288),       # the benchmark's expert cells
    (1024, 4, 2, 16, 1024),         # TRIP_T below
    (1024, 4, 16, 16, 4096),        # every expert held: all rows at once
    (64, 4, 4, 16, 256),            # 1024 rows hold every pair
    (4096, 4, 8, 64, 3072),
    (2048, 8, 32, 64, 12288)])
def test_the_rows_of_a_trip_follow_from_the_shapes(tokens, top_k, held,
                                                   n_routed, want):
    got = moe.routed_chunk(tokens, top_k, held, n_routed)
    assert got == want
    # even routing and half again, and never more than every pair
    pairs = tokens * top_k
    assert min(pairs, 1.5 * pairs * held / n_routed) <= got <= pairs


# Tokens of the tests of the trips: 4,096 pairs, of which even routing
# sends 512 to the 2 held experts of 16, so a trip is 1,024 rows.
TRIP_T = 1024


def _rows_of_a_bare_layer():
    # a layer applied on its own has the empty path
    return profile.counters()["moe_layout"][""]["rows"]


def _steered(case):
    """Parameters and tokens of a (0, 8) share whose first two features
    steer the two held experts: feature 0 at +1 sends a token to both (-1
    to neither), feature 1 to expert 0 alone. Returns ``(params, x, live)``
    with ``live`` the pairs that land on the held experts."""
    rng = np.random.default_rng(11)
    x = rng.normal(size=(TRIP_T, 32)).astype(np.float32)
    x[:, :2] = 0.0
    p = _layer((0, 8)).init(jax.random.key(7), jnp.asarray(x))["params"]
    if case == "one_trip":                  # even routing
        live = None
    elif case == "two_trips":               # every token to both held
        p = dict(p, router_bias=jnp.where(jnp.arange(16) < 2, 10.0, 0.0))
        live = 2 * TRIP_T
    else:
        steer = np.zeros((32, 16), np.float32)
        steer[0, :2] = 40.0
        steer[1, 0], steer[1, 1] = 40.0, -40.0
        kernel = np.asarray(p["router"]["kernel"]).copy()
        kernel[:2] = steer[:2]
        # no bias: a steered score of 1 or 0 is then past every other
        p = dict(p, router={"kernel": jnp.asarray(kernel)},
                 router_bias=jnp.zeros(16))
        x[:, 0] = -1.0
        x[:512, 0] = 1.0                    # 1,024 pairs: the trip is full
        live = 1024
        if case == "one_over":
            x[512, :2] = 0.0, 1.0           # and one pair more
            live = 1025
    return p, jnp.asarray(x), live


@pytest.mark.parametrize("case", ["one_trip", "two_trips", "full",
                                  "one_over"])
def test_any_number_of_trips_matches_the_reference_and_drops_no_pair(case):
    """Output and the gradient of every leaf (``x``, the router's kernel
    through the weights, the expert weights, the shared expert) against the
    uncut float32 reference: in one trip over the sorted rows, in two, and
    with the first trip exactly full and one pair over."""
    p, x, live = _steered(case)
    layer = _layer((0, 8))
    dy = jnp.asarray(np.random.default_rng(12).normal(size=x.shape),
                     jnp.float32)

    def mine(p, x):
        y, load = layer.apply({"params": p}, x)
        return (y * dy).sum(), (y, load)

    def theirs(p, x):
        y, _ = ref.moe(p, x, _arch((0, 8)))
        return (y * dy).sum(), y

    with jax.default_matmul_precision("highest"):
        (_, (y, load)), grads = jax.jit(jax.value_and_grad(
            mine, argnums=(0, 1), has_aux=True))(p, x)
        (_, want), want_grads = jax.jit(jax.value_and_grad(
            theirs, argnums=(0, 1), has_aux=True))(p, x)
    assert _rows_of_a_bare_layer() == 1024
    got_live = int(load[:2].sum())
    if live is None:
        assert 0 < got_live < 1024
    else:
        assert got_live == live
    np.testing.assert_allclose(y, want, atol=2e-5)
    assert _leaves_agree(grads, want_grads) == 9
    assert not np.asarray(grads[0]["router_bias"]).any()


def _routing(chosen, first, held):
    """The layer's routing vectors from ``chosen`` (T, k), in numpy."""
    t, k = chosen.shape
    flat = chosen.reshape(-1)
    local = (flat >= first) & (flat < first + held)
    order = np.argsort(np.where(local, flat - first, held), kind="stable")
    rank = np.argsort(order).reshape(t, k)
    sizes = np.bincount(flat[local] - first, minlength=held)
    return (jnp.asarray(order, jnp.int32), jnp.asarray(rank, jnp.int32),
            jnp.asarray(sizes, jnp.int32))


@pytest.mark.parametrize("live_share", [0.0, 0.12, 0.25, 0.6])
def test_trips_of_any_length_add_up_to_the_same(live_share):
    """The trip function over all 4,096 rows at once, and as four trips of
    1,024: output and the five gradients (a sum's order is all that may
    differ), on routings that fill none, a part, or several of the trips."""
    rng = np.random.default_rng(int(live_share * 100))
    t, k, d, hid, held = TRIP_T, 4, 32, 24, 2
    chosen = np.stack([rng.permutation(14)[:k] + held for _ in range(t)])
    pick = rng.random((t, k)) < live_share      # these go to a held expert
    chosen = np.where(pick, rng.integers(0, held, (t, 1)), chosen)
    chosen[:, 1:][chosen[:, 1:] == chosen[:, :1]] = 15  # one pair an expert
    order, rank, sizes = _routing(chosen, 0, held)
    live = int(sizes.sum())
    assert (live == 0) == (live_share == 0) and (live > 1024) == (
        live_share > 0.25)
    x, dy = (jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
             for _ in range(2))
    weights = jnp.asarray(rng.random((t, k)), jnp.float32)
    ws = [jnp.asarray(rng.normal(size=shape) / 5, jnp.float32)
          for shape in ((held, d, hid), (held, d, hid), (held, hid, d))]

    def run(rows):
        def f(x, weights, *ws):
            y = sum(moe.routed_rows(rows, start, x, weights, order, rank,
                                    sizes, ws)
                    for start in range(0, t * k, rows))
            return (y * dy).sum(), y
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                f, argnums=(0, 1, 2, 3, 4), has_aux=True))(x, weights, *ws)

    (_, parts), part_grads = run(1024)
    (_, whole), whole_grads = run(4096)
    np.testing.assert_allclose(parts, whole, atol=2e-6)
    for a, b in zip(part_grads, whole_grads):
        np.testing.assert_allclose(a, b, atol=2e-5)
    assert bool(np.asarray(whole).any()) == (live_share > 0)


def _loops_outside_kernels(jaxpr) -> int:
    """The ``while`` loops of a traced program, those inside a kernel's body
    (``ops/moe_combine.py`` walks its runs in loops) left out."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "while"
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _loops_outside_kernels(inner)
    return n


def test_a_layer_that_holds_every_expert_takes_its_rows_at_once():
    x = jnp.zeros((TRIP_T, 32), jnp.float32)
    layer = _layer((0, 1))
    p = jax.eval_shape(layer.init, jax.random.key(0), x)
    # the traced program: a kernel is one equation there (lowered for the
    # interpreter, its grid is a loop too)
    whole = jax.make_jaxpr(layer.apply)(p, x)
    assert _rows_of_a_bare_layer() == 4096
    assert "ddstore_moe_gmm" in str(whole)
    assert "ddstore_moe_combine" in str(whole)
    assert _loops_outside_kernels(whole.jaxpr) == 0
    cut = jax.make_jaxpr(_layer((0, 8)).apply)(
        jax.eval_shape(_layer((0, 8)).init, jax.random.key(0), x), x)
    assert _rows_of_a_bare_layer() == 1024
    assert _loops_outside_kernels(cut.jaxpr) == 1


def test_trips_take_no_more_temporaries_than_every_row_at_once(monkeypatch):
    """The dry-run widths as one of eight chips' share, one window of 1,024
    tokens: the compiled step's temporaries in trips of 1,024 rows against
    the same step over all 4,096 rows at once."""
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm47-flash-ep8.json")) as f:
        desc = json.load(f)
    desc.update(desc["dry_run"])
    tok = jnp.zeros((1, TRIP_T), jnp.int32)

    def temporaries():
        model = T.lm_from_description(desc, compute_dtype=jnp.float32)
        state = jax.eval_shape(
            lambda key: T.create_train_state(key, model)[0],
            jax.random.key(0))
        step = T.make_train_step(model, optax.adam(3e-4), donate=False)
        stats = step.lower(state, tok, tok, tok).compile().memory_analysis()
        rows = profile.counters()["moe_layout"]["block1/moe"]["rows"]
        return rows, getattr(stats, "temp_size_in_bytes", None)

    rows, trips = temporaries()
    assert rows == 1024
    monkeypatch.setattr(moe, "routed_chunk", lambda t, k, held, of: t * k)
    rows, at_once = temporaries()
    assert rows == 4096
    if not trips or not at_once:
        pytest.skip("this backend reports no temporaries")
    assert trips <= at_once


def test_mtp_targets_and_mask():
    tgt = jnp.asarray([[5, 6, 7, 8], [1, 2, 3, 4]])
    after, mask = T.mtp_targets(tgt)
    assert after.tolist() == [[6, 7, 8, 0], [2, 3, 4, 0]]
    assert mask.tolist() == [[True, True, True, False]] * 2


def test_mtp_term_is_the_weighted_second_head(built):
    model, state, _ = built
    tok, tgt, pos = batch(2)
    both, _ = T.lm_loss(model, state.params, tok, tgt, pos)
    no_mtp = model.clone(arch=model.arch._replace(mtp_loss_weight=0.0))
    main, _ = T.lm_loss(no_mtp, state.params, tok, tgt, pos)
    arch = ref_arch(model)
    want = ref.loss(state.params, tok, tgt, pos,
                    arch=dict(arch, mtp_loss_weight=1.0)) \
        - ref.loss(state.params, tok, tgt, pos,
                   arch=dict(arch, mtp_loss_weight=0.0))
    np.testing.assert_allclose(float(both) - float(main), 0.3 * want,
                               rtol=1e-4)
    feats = model.apply(state.params, tok, pos, True, next_tokens=tgt)[1]
    assert feats.shape == (B, S, 32)
    assert model.apply(state.params, tok, pos, True)[1] is None


def test_the_bias_leaf_takes_no_gradient_and_no_update(built):
    model, state, tx = built
    tok, tgt, pos = batch(3)
    grads = jax.grad(lambda p: T.lm_loss(model, p, tok, tgt, pos)[0])(
        state.params)
    for name in ("block1", "block2"):
        assert not np.asarray(
            grads["params"][name]["moe"]["router_bias"]).any()
        assert np.asarray(
            grads["params"][name]["moe"]["router"]["kernel"]).any()
    step = T.make_train_step(model, tx, donate=False)
    new, (loss, loads) = step(state, tok, tgt, pos)
    for name in ("block1", "block2"):
        np.testing.assert_array_equal(
            new.params["params"][name]["moe"]["router_bias"],
            state.params["params"][name]["moe"]["router_bias"])
    mb = new.params["params"]["mtp"]["block"]["moe"]
    np.testing.assert_array_equal(
        mb["router_bias"],
        state.params["params"]["mtp"]["block"]["moe"]["router_bias"])
    assert np.asarray(mb["router_bias"]).any()      # seeded, non-zero
    assert np.isfinite(float(loss)) and loads.shape == (3, 16)


def test_the_noaux_tc_rule_moves_each_bias_toward_balance(built):
    model, state, tx = built
    loads = jnp.asarray(np.tile(np.arange(16, dtype=np.int32), (3, 1)))
    before = T._router_biases(model, state.params)
    after = T._router_biases(model, T.update_router_bias(
        model, state.params, loads, 0.01))
    want = np.where(np.arange(16) < 7.5, 0.01, -0.01)
    np.testing.assert_allclose(after - before, np.tile(want, (3, 1)),
                               atol=1e-7)
    # the step applies it after the optimizer, when the model has a speed
    tok, tgt, pos = batch(5)
    fast = model.clone(arch=model.arch._replace(bias_update_speed=0.01))
    new, (_, loads) = T.make_train_step(fast, tx, donate=False)(
        state, tok, tgt, pos)
    np.testing.assert_allclose(
        T._router_biases(fast, new.params) - before,
        0.01 * np.sign(B * S * 4 / 16 - np.asarray(loads)), atol=1e-7)


def test_balancing_the_biases_in_set_up_evens_the_loads(built):
    model, state, _ = built
    rng = np.random.default_rng(6)
    # a skewed vocabulary, as the benchmark's traffic
    tok = (rng.integers(0, 128, (3, B, S)) ** 3 // 128 ** 2).astype(np.int32)
    tgt = np.roll(tok, -1, -1)
    pos = np.tile(np.arange(S, dtype=np.int32), (B, 1))
    # largest over mean load, mean over the batches it ran on and the layers
    uneven = lambda st: float(np.mean([
        np.asarray(ld.max(-1) / ld.mean(-1)) for ld in (
            T.lm_loss(model, st.params, tok[i], tgt[i], pos)[1]
            for i in range(2))]))
    balanced = T.balance_router_bias(model, state, tok[:2], tgt[:2], pos)
    assert uneven(balanced) < 0.85 * uneven(state)
    same = lambda a, b: all(
        (x == y).all() for (kx, x), (ky, y) in zip(
            jax.tree_util.tree_flatten_with_path(a.params)[0],
            jax.tree_util.tree_flatten_with_path(b.params)[0])
        if "router_bias" not in jax.tree_util.keystr(kx))
    assert same(balanced, state)


@pytest.mark.parametrize("variant", ["remat_names", "remat_full", "accum2"])
def test_remat_and_accumulation_change_memory_not_the_update(built, variant):
    model, state, tx = built
    tok, tgt, pos = batch(4)
    base, (loss0, loads0) = T.make_train_step(model, tx, donate=False)(
        state, tok, tgt, pos)
    kw = {}
    if variant == "remat_names":
        model = model.clone(remat=True,
                            remat_policy="names:flash_out,flash_lse")
    elif variant == "remat_full":
        model = model.clone(remat=True)
    else:
        kw = dict(accum_steps=2)
    new, (loss, loads) = T.make_train_step(model, tx, donate=False, **kw)(
        state, tok, tgt, pos)
    np.testing.assert_allclose(loss, loss0, rtol=2e-6)
    np.testing.assert_array_equal(loads, loads0)
    for a, b in zip(jax.tree_util.tree_leaves(new.params),
                    jax.tree_util.tree_leaves(base.params)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_one_description_builds_either_architecture():
    dense = T.lm_from_description(dict(vocab=64, dim=32, heads=2, layers=1))
    assert dense.arch is None and dense.mlp_ratio == 4
    old_moe = T.lm_from_description(
        dict(vocab=64, dim=32, heads=2, layers=1, experts=4, moe_top_k=2))
    assert old_moe.n_experts == 4 and old_moe.moe_top_k == 2
    model = T.lm_from_description(dict(DESC, remat_policy="names:flash_out"))
    assert model.arch.n_routed_experts == 16          # the router's width
    assert model.arch.expert_share == (1, 8)
    assert (model.vocab, model.dim, model.heads, model.layers) \
        == (128, 32, 4, 3)
    assert model.remat and model.remat_policy == "names:flash_out"
    with pytest.raises(ValueError, match="n_group"):
        T.lm_from_description(dict(DESC, n_group=2))
    with pytest.raises(ValueError, match="remat_policy"):
        T._remat_policy("no_such_policy")


@pytest.mark.parametrize("vocab,block,want", [
    (32768, 8192, 8192), (19360, 8192, 6528), (512, 8192, 8192),
    (16384, 8192, 8192), (20000, 4096, 4096)])
def test_balanced_block(vocab, block, want):
    got = T._balanced_block(vocab, block)
    assert got == want
    assert got % 128 == 0 and -(-vocab // got) == -(-vocab // block)


@pytest.mark.parametrize("s", [2048, 3072])
def test_flash_at_head_width_256_matches_the_reference(s):
    """Interpret mode, one block row of 1024 and several: forward and the
    three gradients at the width the MLA block calls the kernels with."""
    from ddstore_tpu.ops.attention import flash_attention, mha_reference

    ks = jax.random.split(jax.random.key(s), 4)
    q, k, v, do = (jax.random.normal(kk, (1, 2, s, 256), jnp.float32)
                   for kk in ks)

    def run(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v, causal=True)
            return (out * do).sum() + lse.sum() * 1e-3
        return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        got, ggrads = run(flash_attention)
        want, wgrads = run(mha_reference)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(ggrads, wgrads):
        np.testing.assert_allclose(g, w, atol=5e-4 * float(jnp.abs(w).max()))
    geo = profile.counters()["flash_geometry"]["ddstore_flash_dkv"]
    call = next(c for c in geo if f"q{s}+0" in c and "d256" in c)
    assert "blocks 1024x1024 sub 128x256" in call


# -- the step by kind of work and by pass (ISSUE 35) ------------------------


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_step_by_kind_of_work_and_pass(monkeypatch, remat):
    """Latent attention's projection chain, its latent norms, the dense
    layer's MLP and the shared expert under names of their own, in every
    pass; the routed part's replay of its forward under ``recompute`` even
    where no block is rematerialised."""
    from test_transformer import (EMITS, assert_the_products_kernels_passes,
                                  passes_of, replayed_products, step_names)

    kw = dict(remat=True, remat_policy="names:flash_out,flash_lse") \
        if remat else {}
    model = T.lm_from_description(DESC, compute_dtype=jnp.float32, **kw)
    found, entered, op_names = step_names(monkeypatch, model, B, S)
    assert entered == EMITS["mla_moe"]
    again = {"recompute"} if remat else set()
    for scope in ("mix_in", "mix_norm", "mix_out", "dense_mlp",
                  "shared_expert"):
        assert passes_of(found, scope) == {"forward", "backward"} | again, \
            scope
    every = {"forward", "recompute", "backward"}
    assert passes_of(found, "moe_experts") == every
    # (unrematerialised, XLA shares the forward's products and gathers with
    # the replay and only the replay's activation stays under the marker)
    assert passes_of(found, "moe_dispatch") == every - (
        set() if remat else {"recompute"})
    assert replayed_products(op_names) or not remat
    assert_the_products_kernels_passes(found, replayed=remat)
    counted = profile.counters()["remat"]
    assert set(counted) >= {"block0", "block1", "block2", "mtp/block"}
    assert counted["mtp/block"] == counted["block0"] == {
        "remat": remat, "policy": kw.get("remat_policy"),
        "saved": ["flash_out", "flash_lse"] if remat else []}
