"""The Mamba-2 / attention / expert hybrid of one-branch layers
(``TransformerLM(arch=NemotronHArch)``) against its plain reference
(``benchmarks/reference/nemotron_h_lm.py``) on seeded weights at a small
size, and the pieces one by one: the chunked state-space scan against the
recurrence, the biased convolution, the ungated expert layer and its
sixteen shares, one-branch layers, the description and its refusals, the
counters; and the two older described models, unchanged."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddstore_tpu.models import moe, transformer as T
from ddstore_tpu.ops.short_conv import short_conv
from ddstore_tpu.ops.ssd import ssd
from ddstore_tpu.utils import profile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("ref_nemotron_h_lm", "benchmarks", "reference",
            "nemotron_h_lm.py")
# the older described models' descriptions, batches and leaf comparison
_older = _load("older_described", "tests", "test_lfm2_moe.py")

# The benchmark's nine-layer pattern at toy widths: 8 Mamba heads of 8 on 2
# groups of state 16, 4 query heads on 2 K/V heads of 16 (4 x 16 = 64 is
# not the hidden size 32), 2 of 8 routed experts held (chip 1 of 4), 3 a
# token, a shared expert of its own width.
DESC = dict(
    model_type="nemotron_h", hidden_size=32, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, hybrid_override_pattern="MEMEM*EME",
    num_hidden_layers=9, mamba_num_heads=8, mamba_head_dim=8,
    ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=8,
    moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
    n_routed_experts=2, num_experts_per_tok=3, n_shared_experts=1,
    routed_scaling_factor=2.5, layer_norm_epsilon=1e-5, norm_topk_prob=True,
    n_group=1, topk_group=1, mlp_hidden_act="relu2", mamba_hidden_act="silu",
    use_conv_bias=True, mamba_proj_bias=False, use_bias=False,
    mlp_bias=False, attention_bias=False, time_step_min=0.001,
    time_step_max=0.1, time_step_floor=1e-4, tie_word_embeddings=False,
    vocab_size=128, rope_theta=10000, partial_rotary_factor=1,
    expert_parallel={"chips": 4, "chip": 1})
B, S = 2, 32


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
    # the leaves that are drawn at a constant, moved off it
    rng = np.random.default_rng(9)
    moved = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf + jnp.asarray(
            rng.normal(size=leaf.shape) * 0.3, leaf.dtype)
        if jax.tree_util.keystr(path).endswith(("['conv_bias']", "['D']"))
        else leaf, state.params)
    return model, state._replace(params=moved), tx


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
    assert (np.asarray(loads).sum(1) == B * S * 3).all()
    assert _older._leaves_agree(grads, want_grads) == 70
    # every leaf but the correction biases takes a gradient
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert bool(np.asarray(g).any()) != (
            "router_bias" in jax.tree_util.keystr(path)), path


@pytest.mark.parametrize("part", ["decay", "older_taps", "experts", "relu2",
                                  "norm_groups"])
def test_each_part_the_controls_leave_out_moves_the_reference(built, part):
    """What the chip's controls drop is in the mathematics: the loss
    without it differs from the whole by far more than any rounding."""
    model, state, _ = built
    tok, tgt, pos = batch()
    arch = ref_arch(model)
    whole = ref.loss(state.params, tok, tgt, pos, arch=arch)
    less = ref.loss(state.params, tok, tgt, pos, arch=arch,
                    leave_out=(part,))
    assert abs(float(less) - float(whole)) > 1e-3 * abs(float(whole))


def test_every_layer_is_one_branch(built):
    _, state, _ = built
    p = state.params["params"]
    assert set(p) == {"embed", "lmhead"} | {f"block{i}" for i in range(9)}
    assert set(p["lmhead"]) == {"lnf", "head"}          # an untied head
    mamba = {"ln1", "in_proj", "conv_taps", "conv_bias", "dt_bias", "A_log",
             "D", "norm", "out_proj"}
    for i, kind in enumerate(DESC["hybrid_override_pattern"]):
        leaves = set(p[f"block{i}"])
        if kind == "M":          # no ln2, no MLP
            assert leaves == mamba
        elif kind == "*":        # no q/k norm, no ln2, no MLP
            assert leaves == {"ln1", "qkv", "proj"}
        else:                    # no ln1, no mixer
            assert leaves == {"ln2", "moe"}
    m = p["block0"]
    assert m["in_proj"]["kernel"].shape == (32, 64 + (64 + 2 * 32) + 8)
    assert m["conv_taps"].shape == (4, 128) and m["conv_bias"].shape == (128,)
    assert m["norm"]["scale"].shape == (64,)
    assert m["out_proj"]["kernel"].shape == (64, 32)
    # heads x head width, not the hidden size
    assert p["block5"]["qkv"]["kernel"].shape == (32, (4 + 2 + 2) * 16)
    assert p["block5"]["proj"]["kernel"].shape == (64, 32)
    e = p["block1"]["moe"]
    assert set(e) == {"router", "router_bias", "w_up", "w_down",
                      "shared_up", "shared_down"}         # no gate anywhere
    assert e["router"]["kernel"].shape == (32, 8)
    assert e["w_up"].shape == (2, 32, 24) and e["w_down"].shape == (2, 24, 32)
    assert e["shared_up"]["kernel"].shape == (32, 40)


def test_the_cut_holds_666_963_456_parameters_at_the_published_widths():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron3-nano-ep16.json")) as f:
        cfg = json.load(f)
    model = T.lm_from_description(cfg, compute_dtype=jnp.bfloat16)
    state = jax.eval_shape(lambda k: T.create_train_state(k, model)[0],
                           jax.random.key(0))
    p = state.params["params"]
    size = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))
    assert size(p["block0"]) == 38_744_896               # M
    assert size(p["block1"]) == 20_302_592 + 8 * 9_977_856   # E, 8 held
    assert size(p["block5"]) == 23_399_040               # *
    assert size(p["embed"]) + size(p["lmhead"]) == 88_080_384 + 2_688
    assert size(p) == 666_963_456
    a = model.arch
    assert (a.n_routed_experts, a.expert_share) == (128, (0, 16))
    assert (model.heads, a.num_key_value_heads, a.head_dim) == (32, 2, 128)
    assert [a.mixer(i) for i in range(9)] == [
        "mamba2", None, "mamba2", None, "mamba2", "full_attention", None,
        "mamba2", None]
    assert [a.mlp(i) for i in range(9)] == [
        None, "experts", None, "experts", None, None, "experts", None,
        "experts"]


# ---------------------------------------------------------------------------
# The state-space scan.
# ---------------------------------------------------------------------------


def _ssd_inputs(seed=0, b=2, s=48, h=4, p=8, g=2, n=16):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return (f(b, s, h, p),
            jnp.asarray(rng.uniform(0.001, 0.5, (b, s, h)), jnp.float32),
            -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32),
            f(b, s, g, n), f(b, s, g, n), f(h))


@pytest.mark.parametrize("chunk", [8, 16])
def test_ssd_forward_and_gradient_match_the_recurrence(chunk):
    """Six and three chunks a sequence, two heads a group: the output and
    the gradient in all six operands against the recurrence walked a
    position at a time."""
    args = _ssd_inputs()
    dy = jnp.asarray(np.random.default_rng(1).normal(size=args[0].shape),
                     jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(
            lambda *a: (ref.scan_recurrence(*a, block=16) * dy).sum(),
            argnums=range(6))(*args)
        got, grads = jax.value_and_grad(
            lambda *a: (ssd(*a, chunk) * dy).sum(), argnums=range(6))(*args)
        np.testing.assert_allclose(
            ssd(*args, chunk), ref.scan_recurrence(*args), atol=2e-4)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, atol=1e-4 * float(jnp.abs(w).max()))


def test_ssd_takes_a_sequence_shorter_than_a_chunk_as_one_chunk():
    args = _ssd_inputs(2, s=8)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ssd(*args, 128),
                                   ref.scan_recurrence(*args), atol=1e-4)


def test_ssd_refuses_a_length_its_chunk_does_not_divide():
    with pytest.raises(ValueError, match="multiple of the scan's chunk 16"):
        ssd(*_ssd_inputs(0, s=40), 16)
    x, dt, A, B, C, D = _ssd_inputs(0, h=4, g=2)
    with pytest.raises(ValueError, match="3 groups do not divide 4 heads"):
        ssd(x, dt, A, jnp.zeros((2, 48, 3, 16)), jnp.zeros((2, 48, 3, 16)),
            D, 16)


@pytest.mark.parametrize("t", [0, 7, 8, 30])
def test_ssd_is_causal_across_chunks(t):
    """Perturbing x at position t moves the outputs from t on, the later
    chunks' among them, and none before."""
    x, *rest = _ssd_inputs(3)
    moved = np.asarray(jnp.abs(ssd(x.at[:, t].add(1.0), *rest, 8)
                               - ssd(x, *rest, 8)).max((0, 2, 3)))
    assert not moved[:t].any() and moved[t] > 0
    assert (moved[t:] > 0).sum() > 8       # reaches past its own chunk


def test_ssd_in_bfloat16_keeps_decays_and_states_in_float32():
    args = _ssd_inputs(4)
    low = tuple(a.astype(jnp.bfloat16) if i in (0, 3, 4) else a
                for i, a in enumerate(args))
    got = ssd(*low, 16)
    assert got.dtype == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        want = ref.scan_recurrence(*(a.astype(jnp.float32) for a in low))
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=2 ** -6 * scale)


def _scan_and_gradients(fn, args, dy):
    return jax.value_and_grad(
        lambda *a: (fn(*a).astype(jnp.float32) * dy).sum(),
        argnums=range(6))(*args)


@pytest.mark.parametrize("shape,chunk,low", [
    (dict(b=1, s=48, h=4, p=8, g=2, n=16), 8, False),
    (dict(b=1, s=32, h=8, p=4, g=2, n=8), 8, False),
    (dict(b=1, s=24, h=2, p=8, g=2, n=16), 8, False),
    (dict(b=1, s=16, h=4, p=8, g=2, n=16), 16, False),
    (dict(b=1, s=8, h=4, p=8, g=2, n=16), 128, False),
    (dict(b=2, s=32, h=4, p=8, g=1, n=16), 16, False),
    (dict(b=1, s=16, h=2, p=128, g=1, n=8), 8, False),
    (dict(b=2, s=32, h=4, p=8, g=2, n=16), 8, True)],
    ids=["six-chunks-two-heads-a-group", "four-heads-a-group",
         "one-head-a-group", "one-chunk", "shorter-than-a-chunk",
         "batch-of-2-one-group", "a-head-a-lane-tile", "bfloat16"])
def test_ssd_kernels_match_the_recurrence_in_all_six_gradients(shape, chunk,
                                                               low):
    """``ddstore_ssd_fwd`` / ``_bwd`` in interpreter mode: the output and
    the gradient in ``x, dt, A, B, C, D`` against the recurrence walked a
    position at a time, by the norm of the difference; in bfloat16 the
    operands are rounded for both and the decays stay float32."""
    args = _ssd_inputs(5, **shape)
    if low:
        args = tuple(a.astype(jnp.bfloat16) if i in (0, 3, 4) else a
                     for i, a in enumerate(args))
    dy = jnp.asarray(np.random.default_rng(6).normal(size=args[0].shape),
                     jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = _scan_and_gradients(
            lambda *a: ref.scan_recurrence(
                *(t.astype(jnp.float32) for t in a)), args, dy)
        got = _scan_and_gradients(lambda *a: ssd(*a, chunk), args, dy)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert g.shape == w.shape
        assert np.linalg.norm(g - w) <= (2e-2 if low else 1e-4) \
            * np.linalg.norm(w)


@pytest.mark.parametrize("what", ["row", "group"])
def test_ssd_carried_state_is_reset_at_each_row_and_group(what):
    """The state a kernel carries from chunk to chunk in VMEM belongs to
    one batch row and one group: moving row 0's ``x`` (group 0's ``B``)
    moves row 0 (group 0's heads), later chunks included, and leaves row 1
    (group 1's heads) as it was, output and gradient."""
    x, dt, A, B, C, D = _ssd_inputs(7, b=2, s=32, h=4, p=8, g=2, n=16)
    dt = dt * 0.05                   # decays slow enough to outlast chunks
    if what == "row":
        moved_args = (x.at[0, 3].add(1.0), dt, A, B, C, D)
        mine, other = (lambda t: t[0]), (lambda t: t[1])
    else:
        moved_args = (x, dt, A, B.at[:, 3, 0].add(1.0), C, D)
        mine, other = (lambda t: t[:, :, :2]), (lambda t: t[:, :, 2:])
    dx = jax.grad(lambda x, *r: ssd(x, *r, 8).sum())
    y0, y1 = ssd(x, dt, A, B, C, D, 8), ssd(*moved_args, 8)
    np.testing.assert_array_equal(other(y0), other(y1))
    assert (np.abs(mine(y1) - mine(y0)).max((0, 2) if what == "row" else
                                           (0, 2, 3))[8:] > 0).all()
    np.testing.assert_array_equal(other(dx(x, dt, A, B, C, D)),
                                  other(dx(*moved_args)))


def test_a_traced_mamba_layer_reports_its_scan_as_the_kernels(built):
    # counted while the model's init was traced
    layout = profile.counters()["mixer_layout"]
    assert [layout[f"block{i}"]["scan"] for i in (0, 2, 4, 7)] \
        == ["pallas"] * 4
    assert "scan" not in layout["block5"]


# ---------------------------------------------------------------------------
# The biased convolution.
# ---------------------------------------------------------------------------


def short_conv_xla(x, taps, bias):
    """The same as plain shifted products, for XLA to fuse: the kernels'
    oracle here and what they are measured against on the chip."""
    s, n = x.shape[1], taps.shape[0]
    z = jnp.pad(x.astype(jnp.float32), ((0, 0), (n - 1, 0), (0, 0)))
    acc = bias.astype(jnp.float32) + sum(
        taps[j].astype(jnp.float32) * jax.lax.slice_in_dim(z, j, j + s, axis=1)
        for j in range(n))
    return jax.nn.silu(acc).astype(x.dtype)


def _conv_loop(x, taps, bias):
    """silu(bias + sum_j taps[j] x[t - (L-1) + j]) written out."""
    x, taps, bias = (np.asarray(a, np.float64) for a in (x, taps, bias))
    b, s, c = x.shape
    acc = np.zeros((b, s, c)) + bias
    for t in range(s):
        for j in range(len(taps)):
            src = t - (len(taps) - 1) + j
            if src >= 0:
                acc[:, t] += taps[j] * x[:, src]
    return acc / (1 + np.exp(-acc))


def _conv_inputs(seed=0, b=2, s=24, c=16, n=4):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return f(b, s, c), f(n, c), f(c)


@pytest.mark.parametrize("conv", [short_conv, short_conv_xla],
                         ids=["kernels", "xla"])
@pytest.mark.parametrize("b,s,c,n", [(2, 24, 16, 4), (2, 16, 8, 1)])
def test_short_conv_against_a_written_out_loop(conv, b, s, c, n):
    args = _conv_inputs(0, b, s, c, n)
    np.testing.assert_allclose(conv(*args), _conv_loop(*args), atol=2e-5)


@pytest.mark.parametrize("b,s,c,n,rows", [
    (2, 24, 16, 4, 8),        # three blocks a sequence: the carried rows
    (1, 64, 1024, 4, 16),     # two strips of 512 lanes
    (2, 32, 16, 7, 8),        # seven taps: all the block of taps holds
    (1, 16, 8, 1, 8)])        # one tap: bias and silu alone
def test_short_conv_kernels_across_blocks_match_the_xla_form(
        monkeypatch, b, s, c, n, rows):
    """Output and all three gradients, with the sequence cut into several
    blocks of rows: what a block carries from the one before and computes
    again of the one after is right."""
    from ddstore_tpu.ops import short_conv as module

    monkeypatch.setattr(module, "_ROWS", rows)
    args = _conv_inputs(7, b, s, c, n)
    dy = jnp.asarray(np.random.default_rng(8).normal(size=(b, s, c)),
                     jnp.float32)

    def run(fn):
        return jax.value_and_grad(
            lambda *a: (fn(*a) * dy).sum(), argnums=(0, 1, 2))(*args)

    got, grads = run(short_conv)
    want, want_grads = run(short_conv_xla)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, atol=2e-4)


def test_short_conv_refuses_what_its_blocks_cannot_hold():
    x, taps, bias = _conv_inputs(0, s=20)
    with pytest.raises(ValueError, match="multiple of 8"):
        short_conv(x, taps, bias)
    x, taps, bias = _conv_inputs(0, s=16, n=8)
    with pytest.raises(ValueError, match="8 taps"):
        short_conv(x, taps, bias)


def test_short_conv_gradients_against_finite_differences_of_the_loop():
    args = _conv_inputs(1, b=1, s=8, c=4)
    dy = np.random.default_rng(5).normal(size=(1, 8, 4))
    grads = jax.grad(lambda *a: (short_conv(*a) * dy).sum(),
                     argnums=(0, 1, 2))(*args)
    loop = lambda *a: (_conv_loop(*a) * dy).sum()
    eps = 1e-5
    for which, got in enumerate(grads):
        base = [np.asarray(a, np.float64) for a in args]
        want = np.zeros(base[which].shape)
        for idx in np.ndindex(want.shape):
            hi, lo = [a.copy() for a in base], [a.copy() for a in base]
            hi[which][idx] += eps
            lo[which][idx] -= eps
            want[idx] = (loop(*hi) - loop(*lo)) / (2 * eps)
        np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("t", [0, 5, 23])
def test_short_conv_is_causal_and_four_wide(t):
    x, taps, bias = _conv_inputs(2)
    moved = np.asarray(jnp.abs(short_conv(x.at[:, t].add(1.0), taps, bias)
                               - short_conv(x, taps, bias)).max((0, 2)))
    want = [i for i in range(t, t + 4) if i < x.shape[1]]
    assert np.flatnonzero(moved > 0).tolist() == want


def test_short_conv_in_bfloat16_accumulates_in_float32():
    x, taps, bias = _conv_inputs(3)
    got = short_conv(x.astype(jnp.bfloat16), taps, bias)
    assert got.dtype == jnp.bfloat16
    want = _conv_loop(x.astype(jnp.bfloat16).astype(jnp.float32), taps, bias)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2 ** -7, atol=1e-6)


# ---------------------------------------------------------------------------
# The ungated expert layer and its shares.
# ---------------------------------------------------------------------------


def _layer(share, n_routed=32, top_k=6, **kw):
    return moe.SharedRoutedMoe(
        n_routed, top_k, 24, share=share, scaling=2.5, route_eps=1e-20,
        compute_dtype=jnp.float32, activation="relu2", shared_hidden=40,
        **kw)


def _arch(share):
    return dict(num_experts_per_tok=6, routed_scaling_factor=2.5,
                route_eps=1e-20, expert_share=share)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The routed parts of all sixteen chips' shares, with the shared
    expert (which every chip computes alike) counted once, equal the
    reference's uncut 32-expert layer."""
    x = jnp.asarray(np.random.default_rng(2).normal(size=(48, 32)),
                    jnp.float32)
    whole = _layer((0, 1)).init(jax.random.key(5), x)["params"]
    shared_alone = {k: jnp.zeros_like(v) if k in ("w_up", "w_down") else v
                    for k, v in whole.items()}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(whole, x, _arch((0, 1)))
        shared, _ = ref.moe(shared_alone, x, _arch((0, 1)))
    assert float(jnp.abs(shared).max()) > 0
    total, loads = jnp.zeros_like(x), []
    for which in range(16):
        cut = dict(whole, **{k: whole[k][2 * which:2 * which + 2]
                             for k in ("w_up", "w_down")})
        with jax.default_matmul_precision("highest"):
            y, load = _layer((which, 16)).apply({"params": cut}, x)
            mine, _ = ref.moe(cut, x, _arch((which, 16)))
        np.testing.assert_allclose(y, mine, atol=2e-5)
        total = total + (y - shared)
        loads.append(load)
    np.testing.assert_allclose(total + shared, want, atol=5e-5)
    assert all((ld == loads[0]).all() for ld in loads)
    assert int(loads[0].sum()) == 48 * 6


def test_ungated_experts_have_no_gate_and_a_shared_width_of_their_own():
    x = jnp.zeros((8, 32), jnp.float32)
    ungated = _layer((0, 16)).init(jax.random.key(0), x)["params"]
    assert set(ungated) == {"router", "router_bias", "w_up", "w_down",
                            "shared_up", "shared_down"}
    assert ungated["shared_up"]["kernel"].shape == (32, 40)
    assert ungated["shared_down"]["kernel"].shape == (40, 32)
    gated = moe.SharedRoutedMoe(32, 6, 24, share=(0, 16)).init(
        jax.random.key(0), x)["params"]
    assert set(gated) == set(ungated) | {"w_gate", "shared_gate"}
    assert gated["shared_up"]["kernel"].shape == (32, 24)
    for k in ("router", "router_bias", "w_up", "w_down"):
        assert jax.tree_util.tree_map(jnp.shape, ungated[k]) \
            == jax.tree_util.tree_map(jnp.shape, gated[k])


def test_an_expert_is_down_of_relu_squared_of_up():
    """One token, every expert held and chosen with the router's weights
    known: y = sum_e w_e down_e(relu(up_e x)^2) + the shared expert."""
    layer = _layer((0, 1), n_routed=2, top_k=2)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 32)),
                    jnp.float32)
    p = layer.init(jax.random.key(1), x)["params"]
    with jax.default_matmul_precision("highest"):
        y, _ = layer.apply({"params": p}, x)
        s = jax.nn.sigmoid(x @ p["router"]["kernel"])
        w = s / s.sum(-1, keepdims=True) * 2.5
        want = jnp.square(jax.nn.relu(x @ p["shared_up"]["kernel"])) \
            @ p["shared_down"]["kernel"]
        for e in range(2):
            want = want + w[:, e:e + 1] * (jnp.square(jax.nn.relu(
                x @ p["w_up"][e])) @ p["w_down"][e])
    np.testing.assert_allclose(y, want, atol=2e-5)


def test_the_layer_refuses_an_activation_it_does_not_build():
    layer = moe.SharedRoutedMoe(8, 2, 24, activation="gelu")
    with pytest.raises(ValueError, match="'swiglu', 'reglu' and 'relu2'"):
        layer.init(jax.random.key(0), jnp.zeros((8, 32)))


def test_the_bias_takes_no_gradient_and_moves_by_the_rule(built):
    model, state, _ = built
    tok, tgt, pos = batch(4)
    loads = T.lm_loss(model, state.params, tok, tgt, pos)[1]
    moved = T.update_router_bias(model, state.params, loads, 0.01)
    # the expert layers are the arch's: blocks 1, 3, 6, 8 in load order
    for row, i in enumerate((1, 3, 6, 8)):
        before = state.params["params"][f"block{i}"]["moe"]["router_bias"]
        after = moved["params"][f"block{i}"]["moe"]["router_bias"]
        mean = float(loads[row].sum()) / 8
        np.testing.assert_allclose(
            after - before, 0.01 * np.sign(mean - np.asarray(loads[row])),
            atol=1e-7)


def test_balancing_in_set_up_moves_the_biases_alone(built):
    model, state, _ = built
    tok, tgt, pos = batch(6)
    balanced = T.balance_router_bias(model, state, tok[None], tgt[None],
                                     pos, iters=4)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(state.params)[0],
            jax.tree_util.tree_leaves(balanced.params)):
        same = bool((np.asarray(a) == np.asarray(b)).all())
        assert same != ("router_bias" in jax.tree_util.keystr(path)), path


def test_a_train_step_returns_loss_and_loads_and_moves_every_kind(built):
    model, state, tx = built
    tok, tgt, pos = batch(5)
    step = T.make_train_step(model, tx, donate=False)
    new, (loss, loads) = step(state, tok, tgt, pos)
    assert np.isfinite(float(loss)) and loads.shape == (4, 8)
    for blk, leaf in (("block0", "A_log"), ("block0", "dt_bias"),
                      ("block0", "conv_taps"), ("block5", "qkv"),
                      ("block1", "moe")):
        before = jax.tree_util.tree_leaves(state.params["params"][blk][leaf])
        after = jax.tree_util.tree_leaves(new.params["params"][blk][leaf])
        assert any(float(jnp.abs(a - b).max()) > 0
                   for a, b in zip(after, before)), (blk, leaf)


# ---------------------------------------------------------------------------
# The description.
# ---------------------------------------------------------------------------


def test_lm_from_description_recognises_the_model_type():
    model = T.lm_from_description(DESC, compute_dtype=jnp.float32)
    a = model.arch
    assert isinstance(a, T.NemotronHArch)
    assert (model.vocab, model.dim, model.heads, model.layers) \
        == (128, 32, 4, 9)
    assert a.pattern == "MEMEM*EME"
    assert a.n_routed_experts == 8 and a.expert_share == (1, 4)
    assert a.moe_shared_expert_intermediate_size == 40
    assert a.expert_activation == "relu2" and a.route_eps == 1e-20
    assert not a.rotary and not a.qk_norm and not a.tie_word_embeddings
    assert a.rms_norm_eps == 1e-5 and a.num_nextn_predict_layers == 0
    assert [a.mlp(i) for i in (0, 1, 5)] == [None, "experts", None]
    assert T._expert_layers(model) == [
        (f"block{i}", "moe") for i in (1, 3, 6, 8)]


@pytest.mark.parametrize("key,value,built_value", [
    ("mamba_proj_bias", True, "False"), ("use_bias", True, "False"),
    ("mlp_bias", True, "False"), ("attention_bias", True, "False"),
    ("use_conv_bias", False, "True"), ("n_group", 2, "1"),
    ("topk_group", 2, "1"), ("norm_topk_prob", False, "True"),
    ("mlp_hidden_act", "silu", "'relu2'"),
    ("mamba_hidden_act", "gelu", "'silu'"),
    ("hybrid_override_pattern", "MEMEM-EME", "'-' is a dense MLP layer"),
    ("hybrid_override_pattern", "MEM", "num_hidden_layers=9")])
def test_lm_from_description_refuses_what_it_does_not_build(key, value,
                                                            built_value):
    """The message names the key and the value that is built."""
    with pytest.raises(ValueError) as e:
        T.lm_from_description(dict(DESC, **{key: value}))
    assert key in str(e.value) and built_value in str(e.value)


def test_a_sequence_parallel_mesh_raises_with_the_carried_state_named():
    from ddstore_tpu.parallel import make_mesh
    mesh = make_mesh({"dp": 1, "sp": 2}, jax.devices()[:2])
    model = T.lm_from_description(DESC, compute_dtype=jnp.float32, mesh=mesh)
    state, _ = T.create_train_state(jax.random.key(0), model)
    tok, tgt, pos = batch(7)
    with pytest.raises(NotImplementedError) as e:
        T.lm_loss(model, state.params, tok, tgt, pos)
    for reason in ("rotary key", "grouped K/V", "halo", "carried state"):
        assert reason in str(e.value), reason


def test_the_seeded_leaves_are_drawn_as_the_configuration_assumes():
    model = T.lm_from_description(
        dict(DESC, mamba_num_heads=64, n_groups=8), compute_dtype=jnp.float32)
    p = T.create_train_state(jax.random.key(1), model)[0].params["params"]
    m = p["block0"]
    step = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert 0.001 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
    assert step.max() / step.min() > 5            # log-uniform, not a point
    a = np.exp(np.asarray(m["A_log"]))
    assert 1 <= a.min() and a.max() <= 16 and a.max() - a.min() > 5
    assert (np.asarray(m["D"]) == 1).all()
    assert not np.asarray(m["conv_bias"]).any()
    assert np.asarray(m["conv_taps"]).std() == pytest.approx(0.5, rel=0.2)
    assert (np.asarray(m["norm"]["scale"]) == 1).all()
    assert np.asarray(p["embed"]["tok"]["embedding"]).std() \
        == pytest.approx(1.0, rel=0.05)


def test_mixer_layout_counter_says_what_each_layer_mixes(built):
    model, state, _ = built
    T.lm_loss(model, state.params, *batch())     # counted while traced
    layout = profile.counters()["mixer_layout"]
    assert layout["block0"] == dict(
        kind="mamba2", heads=8, head_dim=8, state=16, groups=2, chunk=8,
        taps=4, tokens=layout["block0"]["tokens"], scan="pallas")
    assert {k: layout["block5"][k]
            for k in ("kind", "heads", "kv_heads", "head_dim")} == dict(
                kind="full_attention", heads=4, kv_heads=2, head_dim=16)
    assert all(f"block{i}" not in layout or layout[f"block{i}"]["kind"]
               != "mamba2" for i in (1, 3, 6, 8))
    moe_layout = profile.counters()["moe_layout"]
    assert {k: moe_layout["block1/moe"][k]
            for k in ("held", "of", "first", "top_k")} == dict(
                held=2, of=8, first=2, top_k=3)
    from test_transformer import assert_the_layout_names_the_products
    for i in (1, 3, 6, 8):
        assert_the_layout_names_the_products(
            moe_layout[f"block{i}/moe"], model.dim,
            model.arch.moe_intermediate_size)


# ---------------------------------------------------------------------------
# The two older described models, unchanged by the one path's new reading.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("desc,loss,leaves,names", [
    (_older.DESC, 15.32583236694336, 51, "044aa1d7c9d901fc"),
    (_older.MLA_DESC, 5.124960899353027, 32, "7cc2d276ef0ce82c")],
    ids=["lfm2", "mla"])
def test_the_older_described_models_keep_their_leaves_and_losses(
        desc, loss, leaves, names):
    """Leaf names and shapes (hashed) and the seeded first loss, as the
    commit before the one-branch layers read them (PR 32, d93df0e)."""
    model = T.lm_from_description(desc, compute_dtype=jnp.float32)
    state, _ = T.create_train_state(jax.random.key(3), model, lr=1e-3)
    tok, tgt, pos = _older.batch()
    got, _ = T.lm_loss(model, state.params, tok, tgt, pos)
    assert float(got) == pytest.approx(loss, rel=1e-6)
    flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
    assert len(flat) == leaves
    listed = sorted(jax.tree_util.keystr(p) + str(tuple(v.shape))
                    for p, v in flat)
    assert hashlib.sha256("\n".join(listed).encode()).hexdigest()[:16] \
        == names


# -- the step by kind of work and by pass (ISSUE 35) ------------------------


def test_the_step_by_kind_of_work_and_pass(monkeypatch):
    """The Mamba and attention mixers' projections, the gated norm and the
    shared expert under names of their own, forward, recomputed under
    ``nn.remat`` and transposed (a one-branch layer ends in its output
    projection, which nothing needs a second time); the scan's and the
    convolution's kernels by their own."""
    from test_transformer import (EMITS, assert_the_products_kernels_passes,
                                  passes_of, replayed_products, step_names)

    model = T.lm_from_description(
        DESC, compute_dtype=jnp.float32, remat=True,
        remat_policy="names:flash_out,flash_lse")
    found, entered, op_names = step_names(monkeypatch, model, B, S)
    assert entered == EMITS["nemotron_h"]
    every = {"forward", "recompute", "backward"}
    for scope in ("mix_in", "mix_norm", "shared_expert", "moe_dispatch",
                  "moe_experts"):
        assert passes_of(found, scope) == every, scope
    assert passes_of(found, "mix_out") == {"forward", "backward"}
    assert "dense_mlp" not in {kind for kind, _ in found}
    for kernel in ("ddstore_ssd_fwd", "ddstore_conv_silu_fwd"):
        assert passes_of(found, kernel) == {"forward", "recompute"}, kernel
    for kernel in ("ddstore_ssd_bwd", "ddstore_conv_silu_bwd"):
        assert passes_of(found, kernel) == {"backward"}, kernel
    assert replayed_products(op_names)
    assert_the_products_kernels_passes(found)
    assert profile.counters()["remat"]["block8"]["saved"] == [
        "flash_out", "flash_lse"]
