"""The block-diffusion architecture of softmax-routed experts
(``TransformerLM(arch=SdarMoeArch)``) against its plain reference
(``benchmarks/reference/sdar_moe_lm.py``) on seeded weights at a small
size, and the pieces one by one: the block-diffusion mask against a loop
over its pairs, the flash kernels under it against ``mha_reference``, the
mask's limit case against a causal model, softmax routing, the eight
shares of an expert layer, the objective's draw, the description and its
refusals, the counters and the step's names."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddstore_tpu.models import moe, transformer as T
from ddstore_tpu.ops import attention as A
from ddstore_tpu.utils import profile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "ref_sdar_moe_lm", os.path.join(ROOT, "benchmarks", "reference",
                                    "sdar_moe_lm.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# Two layers; 2 of 16 routed experts held (chip 1 of 8), 4 a position; 4
# query heads on 2 K/V heads of 16; blocks of 4.
DESC = dict(
    model_type="sdar_moe", attention_bias=False, decoder_sparse_step=1,
    head_dim=16, hidden_act="silu", hidden_size=32, intermediate_size=96,
    mlp_only_layers=[], moe_intermediate_size=24, norm_topk_prob=True,
    num_attention_heads=4, num_experts=2, num_experts_per_tok=4,
    num_hidden_layers=2, num_key_value_heads=2, rms_norm_eps=1e-6,
    rope_scaling=None, rope_theta=1000000, sliding_window=None,
    tie_word_embeddings=False, use_sliding_window=False, vocab_size=128,
    expert_parallel={"chips": 8, "chip": 1}, block_length=4, noise_low=0.25,
    noise_seed=7)
B, S = 2, 16


def ref_arch(model):
    return dict(model.arch._asdict(), heads=model.heads,
                mask_token=model.vocab - 1)


def batch(seed=0):
    rng = np.random.default_rng(seed)
    # ids below the mask token, the vocabulary's last
    tok = rng.integers(0, 127, (B, S)).astype(np.int32)
    return tok, np.tile(np.arange(S, dtype=np.int32), (B, 1))


@pytest.fixture(scope="module")
def built():
    model = T.lm_from_description(DESC, compute_dtype=jnp.float32)
    state, tx = T.create_train_state(jax.random.key(3), model, lr=1e-3)
    return model, state, tx


def brute_force_mask(block, half):
    """The mask from its three rules, a pair at a time."""
    live = np.zeros((2 * half, 2 * half), bool)
    for p in range(2 * half):
        for r in range(2 * half):
            i, j = p % half, r % half
            if p < half and r < half:          # noised on noised
                live[p, r] = j // block == i // block
            elif p < half:                     # noised on clean
                live[p, r] = j // block < i // block
            elif r >= half:                    # clean on clean
                live[p, r] = j // block <= i // block
    return live


# ---------------------------------------------------------------------------
# The model against the reference.
# ---------------------------------------------------------------------------


def test_loss_and_every_gradient_leaf_match_the_reference(built):
    from test_lfm2_moe import _leaves_agree

    model, state, _ = built
    tok, pos = batch()
    key = T.diffusion_key(model.arch, 5)
    with jax.default_matmul_precision("highest"):
        (loss, loads), grads = jax.value_and_grad(
            lambda p: T.lm_loss(model, p, tok, None, pos, noise_key=key),
            has_aux=True)(state.params)
    masked, t = T.diffusion_noise(model.arch, key, B, S)
    assert masked.any() and not masked.all()
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tok, masked, t, pos, arch=ref_arch(model)))(
            state.params)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    # every one of the 2 S positions is routed, in both layers
    assert loads.shape == (2, 16) and loads.dtype == jnp.int32
    assert (np.asarray(loads).sum(1) == 2 * B * S * 4).all()
    assert _leaves_agree(grads, want_grads) == 23


@pytest.mark.parametrize("leave_out", ["own_clean_block", "block_causal",
                                       "weight", "softmax", "rotary"])
def test_a_broken_reference_gives_another_loss(built, leave_out):
    model, state, _ = built
    tok, pos = batch()
    masked, t = T.diffusion_noise(model.arch, T.diffusion_key(model.arch, 5),
                                  B, S)
    right, wrong = (float(ref.loss(
        state.params, tok, masked, t, pos, arch=ref_arch(model),
        leave_out=out)) for out in ((), (leave_out,)))
    assert abs(wrong - right) > 1e-4 * right


def test_the_tree_has_no_router_bias_and_an_untied_head(built):
    model, state, _ = built
    p = state.params["params"]
    assert set(p) == {"embed", "block0", "block1", "lmhead"}
    assert set(p["block0"]) == {"ln1", "qkv", "q_norm", "k_norm", "proj",
                                "ln2", "moe"}
    assert set(p["block0"]["moe"]) == {"router", "w_gate", "w_up", "w_down"}
    assert p["block0"]["moe"]["router"]["kernel"].shape == (32, 16)
    assert p["block0"]["moe"]["w_gate"].shape == (2, 32, 24)
    assert p["block0"]["qkv"]["kernel"].shape == (32, (4 + 2 * 2) * 16)
    assert p["block0"]["q_norm"]["scale"].shape == (16,)
    assert set(p["lmhead"]) == {"lnf", "head"}


def test_a_train_step_draws_its_noise_from_the_step_number(built):
    model, state, tx = built
    tok, pos = batch(5)
    step = T.make_train_step(model, tx, donate=False)
    new, (loss, loads) = step(state, tok, tok, pos)
    assert np.isfinite(float(loss)) and loads.shape == (2, 16)
    assert int(new.step) == 1
    # the targets go unread, the step number does not
    same, _ = step(state, tok, jnp.zeros_like(tok), pos)[1]
    assert float(same) == float(loss)
    later, _ = step(state._replace(step=state.step + 1), tok, tok, pos)[1]
    assert float(later) != float(loss)
    want = T.lm_loss(model, state.params, tok, tok, pos,
                     noise_key=T.diffusion_key(model.arch, 0))[0]
    np.testing.assert_allclose(loss, want, rtol=1e-6)


def test_an_accumulated_step_splits_the_steps_key(built):
    model, state, tx = built
    tok, pos = batch(6)
    step = T.make_train_step(model, tx, donate=False, accum_steps=2)
    _, (loss, loads) = step(state, tok, tok, pos)
    keys = jax.random.split(T.diffusion_key(model.arch, 0), 2)
    want = np.mean([float(T.lm_loss(
        model, state.params, tok[i:i + 1], None, pos[i:i + 1],
        noise_key=keys[i])[0]) for i in range(2)])
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    assert (np.asarray(loads).sum(1) == 2 * B * S * 4).all()


def test_the_loss_without_a_key_raises(built):
    model, state, _ = built
    tok, pos = batch()
    with pytest.raises(ValueError, match="noise_key"):
        T.lm_loss(model, state.params, tok, tok, pos)


def test_the_draw_is_clipped_and_a_function_of_key_and_shape(built):
    arch = built[0].arch
    masked, t = T.diffusion_noise(arch, T.diffusion_key(arch, 3), 4, 4096)
    assert masked.shape == (4, 4096) and t.shape == (4, 1024)
    assert 0.25 <= float(t.min()) and float(t.max()) <= 1.0
    # t uniform on [0.25, 1]: 62.5 % of a window masked in the mean
    assert abs(float(t.mean()) - 0.625) < 0.02
    assert abs(float(masked.mean()) - 0.625) < 0.02
    again, _ = T.diffusion_noise(arch, T.diffusion_key(arch, 3), 4, 4096)
    other, _ = T.diffusion_noise(arch, T.diffusion_key(arch, 4), 4, 4096)
    assert (again == masked).all() and not (other == masked).all()
    with pytest.raises(ValueError, match="block_length"):
        T.diffusion_noise(arch, T.diffusion_key(arch, 0), 1, 18)


def test_block_length_one_is_a_causal_model_on_clean_earlier_tokens(built):
    """The mask's limit case: with blocks of one position, noised row i
    sees itself and the clean tokens before it, so where nothing is noised
    the noised half's logits are a causal model's, and noising position i
    moves rows i of the noised half and nothing else."""
    model, state, _ = built
    tok, pos = batch(2)
    one = model.clone(arch=model.arch._replace(block_length=1))
    causal = model.clone(arch=model.arch._replace(block_length=0))
    both_pos = jnp.concatenate([pos, pos], axis=1)
    with jax.default_matmul_precision("highest"):
        want = causal.apply(state.params, tok, pos)[0]
        got = one.apply(state.params, jnp.concatenate([tok, tok], axis=1),
                        both_pos)[0]
        noised = jnp.asarray(tok).at[:, 5].set(127)
        moved = one.apply(state.params,
                          jnp.concatenate([noised, tok], axis=1),
                          both_pos)[0]
    # (the expert layer's part differs by the other half's rows only in
    # what it routes, never in a row's own value)
    np.testing.assert_allclose(got[:, :S], want, atol=2e-5)
    np.testing.assert_allclose(got[:, S:], want, atol=2e-5)
    changed = np.abs(np.asarray(moved - got)).max(axis=-1) > 1e-6
    assert changed[:, 5].all()
    assert not np.delete(changed, 5, axis=1).any()


# ---------------------------------------------------------------------------
# The mask and the kernels under it.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block,half", [(1, 24), (4, 40), (8, 56), (4, 8)])
def test_the_mask_against_a_loop_over_its_pairs(block, half):
    mask = A.BlockDiffusion(block, half)
    want = brute_force_mask(block, half)
    assert (A.block_diffusion_mask(mask) == want).all()
    assert want.sum() == half * half + half * block
    rows = jnp.arange(2 * half)
    assert (np.asarray(ref.visible(rows, half, block)) == want).all()


@pytest.mark.parametrize("stream", ["k", "q"])
@pytest.mark.parametrize("block,half,bq,bk", [
    (1, 24, 8, 24), (4, 40, 8, 40), (8, 56, 8, 56), (4, 64, 16, 32),
    (8, 64, 32, 8)])
def test_the_grid_is_the_blocks_that_hold_a_live_pair(block, half, bq, bk,
                                                      stream):
    """``_enumerate_masked`` against the dense mask: a step a block with a
    live pair and no other, ``_INTERIOR`` where every pair is live, and
    each row of the grid opened and closed once."""
    mask = A.BlockDiffusion(block, half)
    dense = brute_force_mask(block, half)
    outer, inner, code, variants = A._enumerate_masked(mask, bq, bk, stream)
    iq, ik = (outer, inner) if stream == "k" else (inner, outer)
    tiles = dense.reshape(2 * half // bq, bq, 2 * half // bk, bk)
    live, full = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    stepped = np.zeros_like(live)
    stepped[iq, ik] = True
    assert (stepped == live).all()
    what = code & (A._FIRST - 1)
    assert ((what == A._INTERIOR) == full[iq, ik]).all()
    assert (np.bincount(outer, (code & A._FIRST) != 0) == 1).all()
    assert (np.bincount(outer, (code & A._LAST) != 0) == 1).all()
    # a partly live block's strips hold every live pair of it
    geo = A.mask_geometry(mask, (bq, bk), (8, 8), stream)
    for q, k, c in zip(iq, ik, what):
        if c < A._DIAGONAL:
            continue
        covered = np.zeros((bq, bk), bool)
        for rows, cols, _ in A._strips(geo, variants[c - A._DIAGONAL]):
            covered[rows, cols] = True
        assert not (tiles[q, :, k, :] & ~covered).any()
    assert geo.pairs_needed == dense.sum()
    assert geo.pairs_needed <= geo.pairs_computed
    assert geo.grid_steps == geo.blocks_live == live.sum()


def _flash_case(block, half, h, h_kv, d, layout="bhsd", **blocks):
    mask = A.BlockDiffusion(block, half)
    ks = jax.random.split(jax.random.key(block + half), 4)
    shape = lambda n: (2, n, 2 * half, d) if layout == "bhsd" \
        else (2, 2 * half, n, d)
    q, k, v, w = (jax.random.normal(key, shape(n))
                  for key, n in zip(ks, (h, h_kv, h_kv, h)))
    head_major = (lambda t: t) if layout == "bhsd" \
        else (lambda t: t.transpose(0, 2, 1, 3))

    def plain(q, k, v):
        out, lse = A.mha_reference(*(head_major(t) for t in (q, k, v)),
                                   mask=mask)
        return head_major(out), lse

    def flash(q, k, v):
        return A.flash_attention(q, k, v, mask=mask, layout=layout,
                                 interpret=True, **blocks)

    def scalar(attend):
        def f(q, k, v):
            out, lse = attend(q, k, v)
            return (out * w).sum() + jnp.sin(lse).sum()
        return f

    return (q, k, v), plain, flash, scalar


@pytest.mark.parametrize("block,half,h,h_kv,d,layout,blocks", [
    (4, 64, 2, 1, 32, "bhsd", {}),
    (8, 64, 2, 2, 32, "bhsd", dict(block_q=16, block_k=32)),
    (1, 32, 2, 1, 32, "bhsd", dict(block_q=8, block_k=32)),
    (4, 256, 2, 1, 128, "bshd", dict(block_q=128, block_k=256)),
    (4, 512, 1, 1, 128, "bhsd", dict(block_q=256, block_k=512,
                                     bwd_blocks=(256, 512))),
    # more variants than static bodies: whole blocks under a traced band
    (4, 128, 1, 1, 32, "bhsd", dict(block_q=8, block_k=128)),
    # the forward's sub-tiles cut small: a noised row's first sub-tile of a
    # strip is masked, its reference is a later one
    (4, 128, 2, 1, 32, "bhsd", dict(block_q=64, block_k=128, tile=(32, 32))),
    (8, 128, 2, 2, 32, "bhsd", dict(block_q=64, block_k=128, tile=(48, 40)))],
    ids=["defaults", "blocks-of-8", "blocks-of-1", "seq-major-gqa",
         "strips", "traced-band", "first-sub-tile-masked",
         "sub-tiles-that-do-not-divide-the-strips"])
def test_flash_under_the_mask_matches_the_reference(block, half, h, h_kv, d,
                                                    layout, blocks,
                                                    small_tiles):
    """Forward, dq, dk and dv through the interpreted kernels, the lse's
    cotangent included."""
    blocks = dict(blocks)
    if "tile" in blocks:
        small_tiles(blocks.pop("tile"))
    operands, plain, flash, scalar = _flash_case(block, half, h, h_kv, d,
                                                 layout, **blocks)
    for got, want in zip(flash(*operands), plain(*operands)):
        np.testing.assert_allclose(got, want, atol=2e-5)
    got = jax.grad(scalar(flash), (0, 1, 2))(*operands)
    want = jax.grad(scalar(plain), (0, 1, 2))(*operands)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(g, w_, atol=1e-4)


def test_the_traced_band_case_has_more_variants_than_bodies():
    mask = A.BlockDiffusion(4, 128)
    geo = A.mask_geometry(mask, (8, 128), (8, 128), "k")
    assert not A._static_diagonal(geo, A._steps(geo)[3])
    geo = A.mask_geometry(A.BlockDiffusion(4, 64), (64, 64), (64, 64), "k")
    assert A._static_diagonal(geo, A._steps(geo)[3])


def test_flash_under_the_mask_counts_its_blocks():
    operands, _, flash, _ = _flash_case(4, 64, 2, 1, 32, block_q=16,
                                        block_k=32)
    flash(*operands)
    calls = profile.counters()["flash_geometry"]["ddstore_flash_fwd"]
    mine = calls["blockdiff4 bh4 q128+0 k128+0 d32 blocks 16x32 sub 16x32 "
                 "bhsd kv2"]
    assert mine["pairs_needed"] == 64 * 64 + 4 * 64
    # a noised block of 16 rows: its own noised key block, and as a clean
    # block the clean key blocks from its start back (4 + 2 of 2 x 4)
    assert mine["grid_steps"] == mine["blocks_live"] == 4 + 6 + 6
    assert mine["steps_fetching_dead"] == 0


@pytest.mark.parametrize("mask,message", [
    (A.BlockDiffusion(3, 24), "power of two"),
    (A.BlockDiffusion(256, 256), "power of two"),
    (A.BlockDiffusion(4, 30), "power of two"),
    (A.BlockDiffusion(4, 16), "both halves long"),
    (A.BlockDiffusion(4, 20), "multiples of 8")])
def test_flash_refuses_a_mask_it_cannot_tile(mask, message):
    q = jnp.zeros((1, 1, 48 if mask.half != 20 else 40, 32))
    if mask.block == 256:
        q = jnp.zeros((1, 1, 512, 32))
    if mask.half == 30:
        q = jnp.zeros((1, 1, 60, 32))
    with pytest.raises(ValueError, match=message):
        A.flash_attention(q, q, q, mask=mask, interpret=True)


def test_a_mask_is_not_causal_and_takes_no_offsets():
    q = jnp.zeros((1, 1, 32, 32))
    for kw in (dict(causal=True), dict(q_offset=8)):
        with pytest.raises(ValueError, match="not causal"):
            A.flash_attention(q, q, q, mask=A.BlockDiffusion(4, 16),
                              interpret=True, **kw)


# ---------------------------------------------------------------------------
# The expert layer.
# ---------------------------------------------------------------------------


def test_softmax_routing_against_a_hand_written_form():
    logits = jnp.asarray([[2.0, 0.0, 1.0, -1.0], [0.0, 0.0, 3.0, 0.5]])
    chosen, weights = moe.route_softmax(logits, 2)
    assert chosen.tolist() == [[0, 2], [2, 3]]
    e = np.exp(np.asarray(logits))
    p = e / e.sum(-1, keepdims=True)
    want = np.stack([p[0, [0, 2]] / p[0, [0, 2]].sum(),
                     p[1, [2, 3]] / p[1, [2, 3]].sum()])
    np.testing.assert_allclose(weights, want, rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)


def _layer(share, n_routed=16, top_k=4):
    return moe.SharedRoutedMoe(n_routed, top_k, 24, share=share, n_shared=0,
                               compute_dtype=jnp.float32, scoring="softmax")


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The share ties to the model: the eight chips' parts of one expert
    layer add up to the reference's output for the whole 16-expert
    layer."""
    x = jnp.asarray(np.random.default_rng(2).normal(size=(48, 32)),
                    jnp.float32)
    whole = _layer((0, 1)).init(jax.random.key(5), x)["params"]
    assert set(whole) == {"router", "w_gate", "w_up", "w_down"}
    arch = lambda share: dict(num_experts_per_tok=4, expert_share=share)
    want, _ = ref.moe(whole, x, arch((0, 1)))
    total, loads = jnp.zeros_like(x), []
    for which in range(8):
        cut = dict(whole, **{k: whole[k][2 * which:2 * which + 2]
                             for k in ("w_gate", "w_up", "w_down")})
        with jax.default_matmul_precision("highest"):
            y, load = _layer((which, 8)).apply({"params": cut}, x)
            mine, _ = ref.moe(cut, x, arch((which, 8)))
        np.testing.assert_allclose(y, mine, atol=2e-5)
        total = total + y
        loads.append(load)
    np.testing.assert_allclose(total, want, atol=2e-5)
    # every chip routes over all 16 alike
    assert all((ld == loads[0]).all() for ld in loads)
    assert int(loads[0].sum()) == 48 * 4


def test_the_layer_refuses_a_scoring_it_does_not_build():
    layer = moe.SharedRoutedMoe(8, 2, 24, scoring="tanh")
    with pytest.raises(ValueError, match="scoring 'tanh'"):
        layer.init(jax.random.key(0), jnp.zeros((8, 32)))


# ---------------------------------------------------------------------------
# The description.
# ---------------------------------------------------------------------------


def test_the_sdar_description_maps_its_keys():
    model = T.lm_from_description(DESC)
    a = model.arch
    assert isinstance(a, T.SdarMoeArch)
    assert (model.vocab, model.dim, model.heads, model.layers) \
        == (128, 32, 4, 2)
    assert a.n_routed_experts == 16 and a.expert_share == (1, 8)
    assert a.num_key_value_heads == 2 and a.head_dim == 16
    assert a.router_scoring == "softmax" and a.n_shared_experts == 0
    assert a.block_length == 4 and a.noise_low == 0.25 and a.noise_seed == 7
    assert a.rms_norm_eps == 1e-6 and a.rope_theta == 1e6
    assert not a.tie_word_embeddings and a.bias_update_speed == 0
    assert [a.mixer(i) for i in range(2)] == ["full_attention"] * 2
    assert [a.mlp(i) for i in range(2)] == ["experts"] * 2


@pytest.mark.parametrize("key,value,built_value", [
    ("use_sliding_window", True, "False"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "None"),
    ("attention_bias", True, "False"),
    ("norm_topk_prob", False, "True"),
    ("mlp_only_layers", [0], "[]"),
    ("decoder_sparse_step", 2, "1"),
    ("hidden_act", "gelu", "'silu'"),
    # what every model of the type has alike is no option of a description
    ("router_scoring", "sigmoid", "'softmax'"),
    ("n_shared_experts", 1, "0"),
    ("tie_word_embeddings", True, "False"),
    ("num_nextn_predict_layers", 1, "0"),
    ("expert_activation", "relu2", "'swiglu'"),
    ("qk_norm", False, "True"),
    ("rotary", False, "True")])
def test_lm_from_description_refuses_what_it_does_not_build(key, value,
                                                            built_value):
    """The message names the key, its value and the value that is built."""
    with pytest.raises(ValueError) as e:
        T.lm_from_description(dict(DESC, **{key: value}))
    assert f"{key}={value!r}" in str(e.value)
    assert f"only {key}={built_value}" in str(e.value)


def test_a_description_sets_the_published_and_the_objectives_keys_alone():
    """A constant of the class may be repeated at its value; a field the
    description has no key for (``expert_share``) is not read from it."""
    a = T.lm_from_description(dict(
        DESC, router_scoring="softmax", tie_word_embeddings=False,
        expert_share=(3, 4))).arch
    assert a == T.lm_from_description(DESC).arch
    # the published keys and the objective's, then the two derived fields
    assert set(a._fields) == {
        "num_key_value_heads", "head_dim", "moe_intermediate_size",
        "num_experts_per_tok", "rope_theta", "rms_norm_eps", "block_length",
        "noise_low", "mask_token", "noise_seed"} | {"n_routed_experts",
                                                   "expert_share"}
    assert not set(T.SdarMoeArch.fixed()) & set(a._fields)


def test_the_benchmarks_file_is_the_published_description():
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sdar-30b-a3b-ep8.json")) as f:
        cfg = json.load(f)
    model = T.lm_from_description(cfg)
    a = model.arch
    assert (model.dim, model.heads, a.num_key_value_heads, a.head_dim) \
        == (2048, 32, 4, 128)
    assert (a.moe_intermediate_size, a.num_experts_per_tok,
            a.n_routed_experts) == (768, 8, 128)
    assert a.expert_share == (0, 8) and model.layers == 6
    assert model.vocab == 18992 and a.mask_token == 18991
    assert model.remat and model.remat_policy == "names:flash_out,flash_lse"
    assert set(cfg["reduced"]) == set(cfg["published"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert {"block_length", "noise_schedule", "mask_token", "balance"} \
        <= set(cfg["assumed"])


# ---------------------------------------------------------------------------
# Tracing.
# ---------------------------------------------------------------------------


def test_the_counters_say_what_the_objective_and_the_layers_are(built):
    model, state, _ = built
    tok, pos = batch()
    T.lm_loss(model, state.params, tok, tok, pos,
              noise_key=T.diffusion_key(model.arch, 0))
    counters = profile.counters()
    assert counters["diffusion"][""] .items() >= dict(
        block_length=4, window=S, positions=2 * S, noise_low=0.25,
        mask_token=127, noise_seed=7).items()
    layer = counters["moe_layout"]["block1/moe"]
    assert layer["scoring"] == "softmax" and layer["held"] == 2
    mixer = counters["mixer_layout"]["block0"]
    assert mixer["mask"] == "block_diffusion 4" and mixer["kv_heads"] == 2
    assert mixer["tokens"] == 2 * B * S


def test_the_step_by_kind_of_work_and_pass(monkeypatch):
    """``diffusion_noise`` is a kind of work of its own, forward alone; the
    mixer's projections, the q/k norms and the expert layer forward,
    recomputed under ``nn.remat`` and transposed."""
    from test_transformer import (EMITS, assert_the_products_kernels_passes,
                                  passes_of, replayed_products, step_names)

    model = T.lm_from_description(
        DESC, compute_dtype=jnp.float32, remat=True,
        remat_policy="names:flash_out,flash_lse")
    found, entered, op_names = step_names(monkeypatch, model, B, S)
    assert entered == EMITS["sdar_moe"]
    assert passes_of(found, "diffusion_noise") == {"forward"}
    every = {"forward", "recompute", "backward"}
    for scope in ("mix_in", "mix_norm", "mix_out", "moe_dispatch",
                  "moe_experts"):
        assert passes_of(found, scope) == every, scope
    assert not {"shared_expert", "dense_mlp"} & {kind for kind, _ in found}
    assert replayed_products(op_names)
    assert_the_products_kernels_passes(found)


def test_the_example_trains_the_benchmarks_file_from_a_store(tmp_path):
    """``examples/lm_longcontext.py --config`` takes the new file as it
    takes the others: the model from ``lm_from_description``, the state and
    the step from ``create_train_state`` / ``make_train_step``, windows
    from a store through the loader; the loss falls over two epochs."""
    import re
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", "lm_longcontext.py"),
         "--config", os.path.join(ROOT, "benchmarks", "configs",
                                  "sdar-30b-a3b-ep8.json"),
         "--dry-sizes", "--seq", "64", "--windows", "16", "--epochs", "2",
         "--steps", "4"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    losses = [float(x) for x in re.findall(r"epoch \d+: loss=([\d.]+)",
                                           proc.stdout)]
    assert len(losses) == 2 and losses[1] < losses[0]
    assert "mask=block_diffusion 4" in proc.stdout
    assert "block diffusion lm: block_length=4 window=64 positions=128" \
        in proc.stdout


def test_placement_relabels_the_experts_and_evens_the_chips_share(built):
    """``place_experts``: a permutation of each router's columns, nothing
    else; over the batches it measured, this chip's share of a layer's
    pairs is then within the heaviest expert's load of an eighth."""
    model, state, _ = built
    rng = np.random.default_rng(9)
    # half of every window one token, as a window's masked positions are
    tok = rng.integers(0, 127, (3, B, S)).astype(np.int32)
    tok[:, :, ::2] = 5
    pos = np.tile(np.arange(S, dtype=np.int32), (B, 1))
    placed = T.place_experts(model, state, jnp.asarray(tok), pos)

    def loads(params):
        return sum(np.asarray(T.lm_loss(
            model, params, tok[i], None, pos,
            noise_key=T.diffusion_key(model.arch, i))[1]) for i in range(3))

    before, after = loads(state.params), loads(placed.params)
    flat = dict(jax.tree_util.tree_flatten_with_path(state.params)[0])
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            placed.params)[0]:
        if "router" in jax.tree_util.keystr(path):
            # the same columns in another order
            assert sorted(map(tuple, np.asarray(leaf).T.tolist())) \
                == sorted(map(tuple, np.asarray(flat[path]).T.tolist()))
        else:
            assert (leaf == flat[path]).all()
    # the first layer's routing is what it was: the same loads, relabelled
    # (a later layer's follows what the held experts before it add)
    assert (np.sort(before[0]) == np.sort(after[0])).all()
    assert (before.sum(-1) == after.sum(-1)).all()
    which, of = model.arch.expert_share
    held = after[:, 2 * which:2 * which + 2].sum(-1)
    even = after.sum(-1) / of
    assert (np.abs(held - even) <= after.max(-1)).all()
    assert placed.opt_state is state.opt_state


@pytest.mark.parametrize("stepped", ["step", "adam_count"])
def test_placement_refuses_a_state_that_has_stepped(built, stepped):
    """Relabelling the router over matrices that stay is the same model on
    fresh seeded experts alone."""
    model, state, _ = built
    tok, pos = batch()
    if stepped == "step":
        state = state._replace(step=state.step + 3)
    else:
        adam = state.opt_state[0]
        state = state._replace(opt_state=(
            adam._replace(count=adam.count + 1),) + state.opt_state[1:])
    with pytest.raises(ValueError, match="fresh seeded experts alone"):
        T.place_experts(model, state, jnp.asarray(tok)[None], pos)
