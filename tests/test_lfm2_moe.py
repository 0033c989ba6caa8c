"""The short-convolution / grouped-query / bias-routed expert architecture
(``TransformerLM(arch=Lfm2MoeArch)``) against its plain reference
(``benchmarks/reference/lfm2_moe_lm.py``) on seeded weights at a small
size, and the pieces one by one: the gated short convolution, grouped-query
attention through the flash kernels, the expert layer without a shared
expert and its shares, the tied head, the description, the counter."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddstore_tpu.models import moe, transformer as T
from ddstore_tpu.ops.attention import flash_attention, mha_reference
from ddstore_tpu.ops.short_conv import gated_short_conv
from ddstore_tpu.utils import profile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "ref_lfm2_moe_lm", os.path.join(ROOT, "benchmarks", "reference",
                                    "lfm2_moe_lm.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# The benchmark's five-layer pattern (a dense conv layer, then attention
# and three conv layers with experts); 2 of 8 routed experts held (chip 1
# of 4), 4 a token; 8 query heads on 2 K/V heads.
DESC = dict(
    model_type="lfm2_moe", conv_L_cache=3, conv_bias=False, hidden_size=32,
    intermediate_size=96,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    moe_intermediate_size=24, norm_eps=1e-5, norm_topk_prob=True,
    num_attention_heads=8, num_key_value_heads=2, num_dense_layers=1,
    num_experts=2, num_experts_per_tok=4, num_hidden_layers=5,
    rope_theta=1000000, routed_scaling_factor=1, use_expert_bias=True,
    vocab_size=128, expert_parallel={"chips": 4, "chip": 1})
B, S = 4, 16


def ref_arch(model):
    return dict(model.arch._asdict(), heads=model.heads)


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
    assert loads.shape == (4, 8) and loads.dtype == jnp.int32
    assert (np.asarray(loads).sum(1) == B * S * 4).all()
    assert _leaves_agree(grads, want_grads) > 30


def test_the_tree_is_the_five_layer_pattern_with_one_tied_matrix(built):
    _, state, _ = built
    p = state.params["params"]
    assert set(p) == {"embed", "lmhead"} | {f"block{i}" for i in range(5)}
    assert set(p["lmhead"]) == {"lnf"}           # no head kernel of its own
    conv = {"ln1", "ln2", "in_proj", "conv_taps", "out_proj"}
    attn = {"ln1", "ln2", "qkv", "q_norm", "k_norm", "proj"}
    assert set(p["block0"]) == conv | {"gate", "up", "down"}
    assert set(p["block1"]) == attn | {"moe"}
    for i in (2, 3, 4):
        assert set(p[f"block{i}"]) == conv | {"moe"}
    assert p["block1"]["qkv"]["kernel"].shape == (32, (8 + 2 + 2) * 4)
    assert p["block1"]["q_norm"]["scale"].shape == (4,)
    assert p["block0"]["conv_taps"].shape == (3, 32)
    assert set(p["block1"]["moe"]) == {"router", "router_bias", "w_gate",
                                       "w_up", "w_down"}
    assert p["block1"]["moe"]["router"]["kernel"].shape == (32, 8)
    assert p["block1"]["moe"]["w_gate"].shape == (2, 32, 24)


def test_the_fused_head_gives_the_plain_heads_loss(built):
    model, state, _ = built
    tok, tgt, pos = batch(1)
    plain, _ = T.lm_loss(model, state.params, tok, tgt, pos,
                         fused_xent=False)
    fused, _ = T.lm_loss(model, state.params, tok, tgt, pos,
                         fused_xent=True, xent_block=48)
    np.testing.assert_allclose(fused, plain, rtol=1e-5)


@pytest.mark.parametrize("fused", [False, True])
def test_the_tied_matrix_gradient_is_the_sum_of_both_uses(built, fused):
    """The embedding's gradient with the head given a copy of the matrix
    (two leaves, in the reference) is the lookup's part; the copy's is the
    head's; the system's one leaf gets their sum."""
    model, state, _ = built
    tok, tgt, pos = batch(2)
    arch = ref_arch(model)

    def two_leaves(table, head):
        p = dict(state.params["params"], embed={"tok": {"embedding": table}})
        logp = jax.nn.log_softmax(_features(p, tok, pos, arch) @ head.T, -1)
        return -jnp.take_along_axis(
            logp, tgt.reshape(-1)[:, None], -1).mean()

    def _features(p, tok, pos, arch):
        x = p["embed"]["tok"]["embedding"][tok]
        for i in range(5):
            x, _ = ref.block(p[f"block{i}"], x, pos, arch, 64)
        return ref._rms(p["lmhead"]["lnf"], x,
                        arch["rms_norm_eps"]).reshape(B * S, -1)

    table = state.params["params"]["embed"]["tok"]["embedding"]
    with jax.default_matmul_precision("highest"):
        g_lookup, g_head = jax.grad(two_leaves, argnums=(0, 1))(table, table)
        grads = jax.grad(lambda p: T.lm_loss(
            model, p, tok, tgt, pos, fused_xent=fused, xent_block=48)[0])(
                state.params)
    got = grads["params"]["embed"]["tok"]["embedding"]
    assert float(jnp.abs(g_lookup).max()) > 0 < float(jnp.abs(g_head).max())
    np.testing.assert_allclose(got, g_lookup + g_head,
                               atol=2e-4 * float(jnp.abs(got).max()))


# ---------------------------------------------------------------------------
# The gated short convolution.
# ---------------------------------------------------------------------------


def gated_short_conv_xla(bcu, taps):
    """The same as plain shifted products, for XLA to fuse: the form the
    kernels were measured against on the chip (PERF.md section 6, PR 31)
    and their oracle here."""
    s, n = bcu.shape[1], taps.shape[0]
    bg, cg, u = (t.astype(jnp.float32) for t in jnp.split(bcu, 3, -1))
    taps = taps.astype(jnp.float32)
    z = jnp.pad(bg * u, ((0, 0), (n - 1, 0), (0, 0)))
    c = sum(taps[j] * jax.lax.slice_in_dim(z, j, j + s, axis=1)
            for j in range(n))
    return (cg * c).astype(bcu.dtype)


def _conv_loop(bcu, taps):
    """y[b, t, c] written out: a loop over positions and taps."""
    bcu, taps = np.asarray(bcu, np.float64), np.asarray(taps, np.float64)
    b, s, c3 = bcu.shape
    c, n = c3 // 3, len(taps)
    bg, cg, u = bcu[..., :c], bcu[..., c:2 * c], bcu[..., 2 * c:]
    z = bg * u
    y = np.zeros((b, s, c))
    for t in range(s):
        acc = np.zeros((b, c))
        for j in range(n):
            src = t - (n - 1) + j
            if src >= 0:
                acc += taps[j] * z[:, src]
        y[:, t] = cg[:, t] * acc
    return y


def _conv_inputs(seed=0, b=2, s=24, c=16, n=3):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(b, s, 3 * c)), jnp.float32),
            jnp.asarray(rng.normal(size=(n, c)), jnp.float32))


@pytest.mark.parametrize("conv", [gated_short_conv, gated_short_conv_xla],
                         ids=["kernels", "xla"])
def test_short_conv_against_a_written_out_loop(conv):
    bcu, taps = _conv_inputs()
    np.testing.assert_allclose(conv(bcu, taps), _conv_loop(bcu, taps),
                               atol=1e-5)


@pytest.mark.parametrize("b,s,c,n,rows", [
    (2, 24, 16, 3, 8),        # three blocks a sequence: the carried rows
    (1, 64, 1024, 3, 16),     # two strips of 512 lanes
    (2, 32, 16, 4, 8),        # four taps
    (1, 16, 8, 1, 8)])        # one tap: gates alone
def test_short_conv_kernels_across_blocks_match_the_xla_form(
        monkeypatch, b, s, c, n, rows):
    """Output and both gradients, with the sequence cut into several
    blocks of rows: what a block carries from the one before (forward and
    backward) and fetches from the one after (backward) is right."""
    from ddstore_tpu.ops import short_conv

    monkeypatch.setattr(short_conv, "_ROWS", rows)
    bcu, taps = _conv_inputs(7, b, s, c, n)
    dy = jnp.asarray(np.random.default_rng(8).normal(size=(b, s, c)),
                     jnp.float32)

    def run(fn):
        return jax.value_and_grad(
            lambda x, w: (fn(x, w) * dy).sum(), argnums=(0, 1))(bcu, taps)

    got, (gx, gw) = run(gated_short_conv)
    want, (wx, ww) = run(gated_short_conv_xla)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(gx, wx, atol=2e-5)
    np.testing.assert_allclose(gw, ww, atol=2e-4)


def test_short_conv_refuses_a_length_it_cannot_tile():
    bcu, taps = _conv_inputs(0, s=20)
    with pytest.raises(ValueError, match="multiple of 8"):
        gated_short_conv(bcu, taps)


def test_short_conv_refuses_more_taps_than_its_halo_holds():
    bcu, taps = _conv_inputs(0, s=16, n=9)
    with pytest.raises(ValueError, match="9 taps"):
        gated_short_conv(bcu, taps)


def test_short_conv_gradients_against_finite_differences_of_the_loop():
    bcu, taps = _conv_inputs(1, b=1, s=8, c=4)
    rng = np.random.default_rng(5)
    dy = rng.normal(size=(1, 8, 4))
    f = lambda bcu, taps: (gated_short_conv(bcu, taps) * dy).sum()
    g_bcu, g_taps = jax.grad(f, argnums=(0, 1))(bcu, taps)
    loop = lambda bcu, taps: (_conv_loop(bcu, taps) * dy).sum()
    eps = 1e-5
    for got, which in ((g_bcu, 0), (g_taps, 1)):
        args = [np.asarray(bcu, np.float64), np.asarray(taps, np.float64)]
        want = np.zeros(args[which].shape)
        for idx in np.ndindex(want.shape):
            hi, lo = [a.copy() for a in args], [a.copy() for a in args]
            hi[which][idx] += eps
            lo[which][idx] -= eps
            want[idx] = (loop(*hi) - loop(*lo)) / (2 * eps)
        np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("t", [0, 5, 23])
def test_short_conv_is_causal_and_three_wide(t):
    """Perturbing the input at position t changes the outputs at t, t + 1
    and t + 2 and no other."""
    bcu, taps = _conv_inputs(2)
    other = bcu.at[:, t].add(1.0)
    moved = np.asarray(jnp.abs(gated_short_conv(other, taps)
                               - gated_short_conv(bcu, taps)).max((0, 2)))
    want = [i for i in (t, t + 1, t + 2) if i < bcu.shape[1]]
    assert np.flatnonzero(moved > 0).tolist() == want


def test_short_conv_in_bfloat16_accumulates_in_float32():
    bcu, taps = _conv_inputs(3)
    got = gated_short_conv(bcu.astype(jnp.bfloat16), taps)
    assert got.dtype == jnp.bfloat16
    want = _conv_loop(bcu.astype(jnp.bfloat16).astype(jnp.float32), taps)
    # one rounding, of the result
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2 ** -7, atol=1e-6)


# ---------------------------------------------------------------------------
# Grouped-query attention through the flash kernels.
# ---------------------------------------------------------------------------


def _gqa_inputs(group, h=8, s=256, d=32):
    rng = np.random.default_rng(group)
    mk = lambda heads: jnp.asarray(rng.normal(size=(2, heads, s, d)),
                                   jnp.float32)
    return mk(h), mk(h // group), mk(h // group), mk(h)


@pytest.mark.parametrize("group", [1, 4, 8])
def test_gqa_through_interpreted_flash_matches_repeated_kv(group):
    q, k, v, do = _gqa_inputs(group)

    def run(fn, k, v):
        def f(q, k, v):
            out, lse = fn(q, k, v, causal=True)
            return (out * do).sum() + lse.sum() * 1e-3, (out, lse)
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)

    with jax.default_matmul_precision("highest"):
        (_, (out, lse)), (dq, dk, dv) = run(flash_attention, k, v)
        rk, rv = (jnp.repeat(t, group, axis=1) for t in (k, v))
        (_, (wout, wlse)), (wdq, wdk, wdv) = run(mha_reference, rk, rv)
    # a K/V head's gradient is the sum over the query heads that read it
    wdk, wdv = (t.reshape(2, 8 // group, group, 256, 32).sum(2)
                for t in (wdk, wdv))
    assert dk.shape == k.shape and dv.shape == v.shape
    np.testing.assert_allclose(out, wout, atol=2e-5)
    np.testing.assert_allclose(lse, wlse, atol=2e-5)
    for got, want in ((dq, wdq), (dk, wdk), (dv, wdv)):
        np.testing.assert_allclose(got, want,
                                   atol=5e-4 * float(jnp.abs(want).max()))


def test_mha_reference_takes_grouped_kv():
    q, k, v, _ = _gqa_inputs(4, s=32)
    got, glse = mha_reference(q, k, v, causal=True)
    want, wlse = mha_reference(q, jnp.repeat(k, 4, 1), jnp.repeat(v, 4, 1),
                               causal=True)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(glse, wlse)


def test_no_repeated_kv_reaches_the_forward_or_dq_kernel():
    """By the traced calls' operand shapes: K and V go in as (b h_kv, S,
    d) to the forward and to the one backward kernel, which writes dq too,
    and the geometry counter's key says so."""
    q, k, v, _ = _gqa_inputs(4)

    def f(q, k, v):
        out, _ = flash_attention(q, k, v, causal=True)
        return out.sum()

    jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
    seen = {}

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"] if "name" in eqn.params \
                    else eqn.params["name_and_src_info"].name
                seen[name] = [tuple(x.aval.shape) for x in eqn.invars
                              if len(x.aval.shape) == 3]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert set(seen) == {"ddstore_flash_fwd", "ddstore_flash_dkv"}
    for name, shapes in seen.items():
        # q (16 = 2 x 8 heads), then k and v at 4 = 2 x 2 heads
        assert shapes[:3] == [(16, 256, 32), (4, 256, 32), (4, 256, 32)], \
            (name, shapes)
    calls = profile.counters()["flash_geometry"]["ddstore_flash_fwd"]
    assert any(c.startswith("causal bh16 q256+0 k256+0 d32 ")
               and c.endswith(" kv4") for c in calls)


def test_flash_refuses_kv_heads_that_do_not_divide():
    q, _, _, _ = _gqa_inputs(1, s=32)
    with pytest.raises(ValueError, match="divide the query heads"):
        flash_attention(q, q[:, :3], q[:, :3], causal=True)


# ---------------------------------------------------------------------------
# The expert layer without a shared expert.
# ---------------------------------------------------------------------------


def _layer(share, n_shared=0, n_routed=8, top_k=4):
    return moe.SharedRoutedMoe(n_routed, top_k, 24, share=share,
                               n_shared=n_shared, route_eps=1e-6,
                               compute_dtype=jnp.float32)


def _arch(share):
    return dict(num_experts_per_tok=4, routed_scaling_factor=1.0,
                expert_share=share)


def test_no_shared_expert_builds_no_shared_parameters():
    x = jnp.zeros((8, 32), jnp.float32)
    without = _layer((0, 4)).init(jax.random.key(0), x)["params"]
    assert set(without) == {"router", "router_bias", "w_gate", "w_up",
                            "w_down"}
    with_one = _layer((0, 4), n_shared=1).init(jax.random.key(0), x)["params"]
    assert set(with_one) == set(without) | {"shared_gate", "shared_up",
                                            "shared_down"}
    assert with_one["shared_gate"]["kernel"].shape == (32, 24)
    # and the routed leaves are the same leaves
    for k in without:
        assert jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda a, b: bool((a == b).all()), without[k], with_one[k]))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Routed parts of all four chips' shares == the reference's uncut
    8-expert layer: there is no shared expert to count once."""
    x = jnp.asarray(np.random.default_rng(2).normal(size=(48, 32)),
                    jnp.float32)
    whole = _layer((0, 1)).init(jax.random.key(5), x)["params"]
    want, _ = ref.moe(whole, x, _arch((0, 1)))
    total, loads = jnp.zeros_like(x), []
    for which in range(4):
        cut = dict(whole, **{k: whole[k][2 * which:2 * which + 2]
                             for k in ("w_gate", "w_up", "w_down")})
        with jax.default_matmul_precision("highest"):
            y, load = _layer((which, 4)).apply({"params": cut}, x)
            mine, _ = ref.moe(cut, x, _arch((which, 4)))
        np.testing.assert_allclose(y, mine, atol=2e-5)
        total = total + y
        loads.append(load)
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert all((ld == loads[0]).all() for ld in loads)
    assert int(loads[0].sum()) == 48 * 4


def test_the_normaliser_epsilon_is_an_argument_of_the_rule():
    scores = jnp.asarray([[0.9, 0.8, 0.7, 0.1]])
    _, w0 = moe.route_noaux_tc(scores, jnp.zeros(4), 2, 1.0)
    _, w1 = moe.route_noaux_tc(scores, jnp.zeros(4), 2, 1.0, 0.3)
    np.testing.assert_allclose(w0, [[0.9 / 1.7, 0.8 / 1.7]], rtol=1e-6)
    np.testing.assert_allclose(w1, [[0.9 / 2.0, 0.8 / 2.0]], rtol=1e-6)


def test_the_bias_takes_no_gradient_and_moves_by_the_rule(built):
    model, state, tx = built
    tok, tgt, pos = batch(4)
    grads = jax.grad(lambda p: T.lm_loss(model, p, tok, tgt, pos)[0])(
        state.params)
    for i in (1, 2, 3, 4):
        assert not np.asarray(
            grads["params"][f"block{i}"]["moe"]["router_bias"]).any()
    loads = T.lm_loss(model, state.params, tok, tgt, pos)[1]
    moved = T.update_router_bias(model, state.params, loads, 0.01)
    before = state.params["params"]["block2"]["moe"]["router_bias"]
    after = moved["params"]["block2"]["moe"]["router_bias"]
    mean = float(loads[1].sum()) / 8
    np.testing.assert_allclose(
        after - before, 0.01 * np.sign(mean - np.asarray(loads[1])),
        atol=1e-7)


def test_a_train_step_returns_loss_and_loads_and_moves_the_tied_leaf(built):
    model, state, tx = built
    tok, tgt, pos = batch(5)
    step = T.make_train_step(model, tx, donate=False)
    new, (loss, loads) = step(state, tok, tgt, pos)
    assert np.isfinite(float(loss)) and loads.shape == (4, 8)
    before = state.params["params"]["embed"]["tok"]["embedding"]
    after = new.params["params"]["embed"]["tok"]["embedding"]
    assert float(jnp.abs(after - before).max()) > 0
    # one Adam state a leaf, the tied matrix's among them
    mu = new.opt_state[0].mu["params"]
    assert set(mu["lmhead"]) == {"lnf"}
    assert mu["embed"]["tok"]["embedding"].shape == before.shape


# ---------------------------------------------------------------------------
# The description.
# ---------------------------------------------------------------------------

MLA_DESC = dict(
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
    qk_rope_head_dim=4, v_head_dim=16, intermediate_size=96,
    moe_intermediate_size=24, n_routed_experts=2, num_experts_per_tok=4,
    vocab_size=128, hidden_size=32, num_attention_heads=4,
    num_hidden_layers=2, expert_parallel={"chips": 8, "chip": 1})
DENSE_DESC = dict(vocab=128, dim=32, heads=4, layers=2)


def configured(name):
    """The benchmark's description ``name`` at its dry-run sizes."""
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    return {**cfg, **cfg.get("dry_run", {})}


def untyped(desc):
    return {k: v for k, v in desc.items() if k != "model_type"}


GLM = configured("glm47-flash-ep8")
SMALLTHINKER = configured("smallthinker-21b-a3b-ep4")


@pytest.mark.parametrize("desc,arch,mixers", [
    (DENSE_DESC, type(None), None),
    (MLA_DESC, T.MlaMoeArch, ["mla", "mla"]),
    (DESC, T.Lfm2MoeArch, DESC["layer_types"]),
    # recognised without model_type, by layer_types beside conv_L_cache
    (untyped(DESC), T.Lfm2MoeArch, DESC["layer_types"]),
    (configured("dense-lm-d1024"), type(None), None),
    (GLM, T.MlaMoeArch, ["mla", "mla"]),
    # without model_type, by q_lora_rank
    (untyped(GLM), T.MlaMoeArch, ["mla", "mla"]),
    (configured("nemotron3-nano-ep16"), T.NemotronHArch,
     ["mamba2", None, "mamba2", "full_attention", None]),
    (configured("sdar-30b-a3b-ep8"), T.SdarMoeArch, ["full_attention"] * 2),
    # the catalog's description gives no model_type: recognised by
    # moe_num_primary_experts beside sliding_window_layout
    (SMALLTHINKER, T.SmallThinkerArch,
     ["full_attention"] + ["sliding_attention"] * 3),
    (dict(SMALLTHINKER, model_type="smallthinker"), T.SmallThinkerArch,
     ["full_attention"] + ["sliding_attention"] * 3)],
    ids=["dense", "mla", "lfm2", "lfm2-by-keys", "dense-config", "glm47",
         "mla-by-keys", "nemotron", "sdar", "smallthinker-by-keys",
         "smallthinker"])
def test_lm_from_description_builds_each_description(desc, arch, mixers):
    model = T.lm_from_description(desc, compute_dtype=jnp.float32)
    assert isinstance(model.arch, arch)
    assert model.vocab == desc.get("vocab_size", desc.get("vocab"))
    assert model.dim == desc.get("hidden_size", desc.get("dim"))
    if mixers is not None:
        assert [model.arch.mixer(i) for i in range(model.layers)] == mixers


def test_the_lfm2_description_maps_its_keys():
    a = T.lm_from_description(DESC).arch
    assert a.n_routed_experts == 8 and a.expert_share == (1, 4)
    assert a.first_k_dense_replace == 1 and a.rms_norm_eps == 1e-5
    assert a.n_shared_experts == 0 and a.route_eps == 1e-6
    assert a.tie_word_embeddings and a.rope_theta == 1e6
    assert a.num_nextn_predict_layers == 0
    # the newer checkpoints nest the rotary base
    nested = {k: v for k, v in DESC.items() if k != "rope_theta"}
    nested["rope_parameters"] = {"rope_theta": 5e5, "rope_type": "default"}
    assert T.lm_from_description(nested).arch.rope_theta == 5e5


@pytest.mark.parametrize("key,value,built_value", [
    ("conv_bias", True, "False"),
    ("norm_topk_prob", False, "True"),
    ("use_expert_bias", False, "True"),
    ("tie_embedding", False, "True"),
    ("layer_types", ["conv", "sliding_attention", "conv", "conv", "conv"],
     "'conv' and 'full_attention'"),
    ("layer_types", ["conv", "full_attention"], "num_hidden_layers=5")])
def test_lm_from_description_refuses_what_it_does_not_build(key, value,
                                                            built_value):
    """The message names the key and the value that is built."""
    with pytest.raises(ValueError) as e:
        T.lm_from_description(dict(DESC, **{key: value}))
    assert key in str(e.value) and built_value in str(e.value)


def test_a_tied_embedding_is_no_longer_refused_for_latent_attention():
    model = T.lm_from_description(dict(MLA_DESC, tie_word_embeddings=True),
                                  compute_dtype=jnp.float32)
    state, _ = T.create_train_state(jax.random.key(0), model)
    assert set(state.params["params"]["lmhead"]) == {"lnf"}
    tok, tgt, pos = batch(6)
    loss, _ = T.lm_loss(model, state.params, tok, tgt, pos)
    assert np.isfinite(float(loss))


def test_the_embedding_is_drawn_normal_0_1_tied_or_not():
    """The configurations' ``assumed`` initialisation: tied, a token's own
    logit is then about dim times a cosine (PERF.md section 6, PR 31)."""
    draw = lambda desc: np.asarray(T.create_train_state(
        jax.random.key(0), T.lm_from_description(
            desc, compute_dtype=jnp.float32))[0].params[
                "params"]["embed"]["tok"]["embedding"])
    assert draw(DESC).std() == pytest.approx(1.0, rel=0.05)
    assert draw(MLA_DESC).std() == pytest.approx(1.0, rel=0.05)


def test_latent_attention_refuses_unequal_value_width_on_the_cpu():
    model = T.lm_from_description(dict(MLA_DESC, v_head_dim=8),
                                  compute_dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="v_head_dim=8"):
        T.create_train_state(jax.random.key(0), model)


def test_a_sequence_parallel_mesh_raises_with_the_halo_named():
    from ddstore_tpu.parallel import make_mesh
    mesh = make_mesh({"dp": 1, "sp": 2}, jax.devices()[:2])
    model = T.lm_from_description(DESC, compute_dtype=jnp.float32, mesh=mesh)
    state, _ = T.create_train_state(jax.random.key(0), model)
    tok, tgt, pos = batch(7)
    with pytest.raises(NotImplementedError, match="two-row .*halo"):
        T.lm_loss(model, state.params, tok, tgt, pos)


def test_mixer_layout_counter_says_what_each_layer_mixes(built):
    layout = profile.counters()["mixer_layout"]
    assert layout["block0"]["kind"] == "conv"
    assert layout["block0"]["taps"] == 3
    assert {k: layout["block1"][k] for k in ("kind", "heads", "kv_heads")} \
        == dict(kind="full_attention", heads=8, kv_heads=2)
    assert [layout[f"block{i}"]["kind"] for i in (2, 3, 4)] == ["conv"] * 3
    assert all(layout[f"block{i}"]["tokens"] > 0 for i in range(5))
    moe_layout = profile.counters()["moe_layout"]
    assert {k: moe_layout["block1/moe"][k]
            for k in ("held", "of", "first", "top_k")} == dict(
                held=2, of=8, first=2, top_k=4)
    from test_transformer import assert_the_layout_names_the_products
    model = built[0]
    for i in (2, 3, 4):
        assert_the_layout_names_the_products(
            moe_layout[f"block{i}/moe"], model.dim,
            model.arch.moe_intermediate_size)


# -- the step by kind of work and by pass (ISSUE 35) ------------------------


def test_the_step_by_kind_of_work_and_pass(monkeypatch):
    """The conv and attention mixers' projections, the q/k norms and the
    dense layer's MLP under names of their own, forward, recomputed under
    ``nn.remat`` and transposed; the convolution's kernels by their own."""
    from test_transformer import (EMITS, assert_the_products_kernels_passes,
                                  passes_of, replayed_products, step_names)

    model = T.lm_from_description(
        DESC, compute_dtype=jnp.float32, remat=True,
        remat_policy="names:flash_out,flash_lse")
    found, entered, op_names = step_names(monkeypatch, model, B, S)
    assert entered == EMITS["lfm2_moe"]
    every = {"forward", "recompute", "backward"}
    for scope in ("mix_in", "mix_norm", "mix_out", "dense_mlp",
                  "moe_dispatch", "moe_experts"):
        assert passes_of(found, scope) == every, scope
    assert "shared_expert" not in {kind for kind, _ in found}
    assert passes_of(found, "ddstore_short_conv_fwd") == {"forward",
                                                          "recompute"}
    assert passes_of(found, "ddstore_short_conv_bwd") == {"backward"}
    assert replayed_products(op_names)
    assert_the_products_kernels_passes(found)
