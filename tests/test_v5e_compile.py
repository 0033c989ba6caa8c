"""Compiles, for a described TPU v5e and at the sizes the benchmark runs,
what no interpret-mode test can refuse: the two flash kernels (the
forward, and the backward that holds a head's dq in VMEM) at head width
256 (VMEM), at the ring's call shapes on four chips, with grouped K/V
heads and at the largest call shapes the cells make, within the VMEM
limit the backward sets, the latent-attention mixer with the copies XLA puts
around its kernels, the gated short convolution's two kernels, the expert
layer's grouped products (``ops/moe_gmm.py``'s kernels at the three
configurations' widths) and its way back (``ops/moe_combine.py``'s kernel
at the four configurations' shapes), the Mamba-2
convolution's and scan's two kernels each, and the whole step of the
``lfm2-8b-a1b-ep4.s8192.b4``, ``nemotron3-nano-ep16.s8192``,
``sdar-30b-a3b-ep8.s8192.b1`` and ``smallthinker-21b-a3b-ep4.s16384.b1``
cells against the chip's memory. Nothing
runs and no time is read; a compile that passes is not a chip run. Every
such test lives in this one file, and the topology is described inside a
fixture: one process at a time may load the TPU's library
(on-chip-measurement guide, section 2)."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it.
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("bh,s,d", [
    pytest.param(160, 2048, 256, id="160-2048"),
    pytest.param(40, 8192, 256, id="40-8192"),
    pytest.param(32, 8192, 128, id="width-128")])
def test_flash_kernels_at_width_256_fit_the_chip(one_chip, no_compile_cache,
                                                 bh, s, d):
    """The MLA block's calls: 20 heads of 256 over 16,384 tokens, at the
    block defaults the call's shape selects (1024 x 1024); and a call at
    width 128 (Nemotron's and SmallThinker's heads, 512 x 2048), whose
    forward's one-pass body takes other sub-tiles than width 64's."""
    from ddstore_tpu.ops.attention import flash_attention

    q = jax.ShapeDtypeStruct((1, bh, s, d), jnp.bfloat16,
                             sharding=one_chip)

    def f(q, k, v):
        out, _ = flash_attention(q, k, v, causal=True, interpret=False)
        return (out.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(q, q, q) \
        .compile().as_text()
    for kernel in ("ddstore_flash_fwd", "ddstore_flash_dkv"):
        assert kernel in text
    assert "ddstore_flash_dq" not in text


@pytest.mark.parametrize("causal,bh,s", [(True, 16, 16384),
                                         (False, 32, 8192)],
                         ids=["step0-causal", "stripe-pairs"])
def test_the_ring_calls_of_the_four_chip_cell_fit_the_chip(
        one_chip, no_compile_cache, causal, bh, s):
    """A chip's two flash calls a layer in ``dense-lm-d1024.s32k.dp2sp2``
    (PR 28): the local 16,384 rows causally, then two stacked 8,192 x
    8,192 stripe pairs unmasked, at head width 64 and the blocks the
    calls' shapes select."""
    from ddstore_tpu.ops.attention import flash_attention

    q = jax.ShapeDtypeStruct((1, bh, s, 64), jnp.bfloat16, sharding=one_chip)

    def f(q, k, v):
        out, _ = flash_attention(q, k, v, causal=causal, interpret=False)
        return (out.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(q, q, q) \
        .compile().as_text()
    for kernel in ("ddstore_flash_fwd", "ddstore_flash_dkv"):
        assert kernel in text
    assert "ddstore_flash_dq" not in text


@pytest.mark.parametrize("shape,h_kv,layout,how", [
    ((1, 16, 16384, 64), 16, "bhsd", dict(causal=True)),
    ((1, 16384, 28, 128), 4, "bshd", dict(causal=True)),
    ((1, 16384, 28, 128), 4, "bshd", dict(causal=True, window=4096)),
    ((2, 8192, 20, 256), 20, "bshd", dict(causal=True)),
    ((1, 16384, 32, 128), 4, "bshd", dict(mask=(4, 8192)))],
    ids=["four-chip-causal-16384-d64", "smallthinker-full-16384-d128",
         "smallthinker-window-16384-d128", "glm-8192-d256",
         "sdar-masked-16384-d128"])
def test_the_fused_backward_lowers_within_its_vmem_limit(
        one_chip, no_compile_cache, shape, h_kv, layout, how):
    """The largest flash calls the cells make, differentiated: the
    backward is one kernel, ``ddstore_flash_dkv``, which holds the head's
    float32 dq (S_q d 4 bytes: 4 MB at 16,384 x 64, 8 MB at 16,384 x 128
    and at 8,192 x 256) and lowers within the VMEM limit it sets and the
    counter records; no ``ddstore_flash_dq`` kernel is left."""
    import re

    from ddstore_tpu.ops.attention import BlockDiffusion, flash_attention
    from ddstore_tpu.utils import profile

    how = dict(how)
    if "mask" in how:
        how["mask"] = BlockDiffusion(*how["mask"])
    heads = 2 if layout == "bshd" else 1
    kv_shape = shape[:heads] + (h_kv,) + shape[heads + 1:]
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct(kv_shape, jnp.bfloat16, sharding=one_chip)

    def f(q, k, v):
        out, _ = flash_attention(q, k, v, layout=layout, interpret=False,
                                 **how)
        return (out.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(q, kv, kv) \
        .compile().as_text()
    assert "ddstore_flash_dq" not in text
    (call,) = _mosaic_calls(text, "ddstore_flash_dkv")
    limit = int(re.search(r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
                          call).group(1))
    (b, s, h), d = ((shape[0], shape[1], shape[2]) if layout == "bshd"
                    else (shape[0], shape[2], shape[1])), shape[-1]
    key = ("window4096" if "window" in how else "blockdiff4" if "mask" in how
           else "causal") + f" bh{b * h} q{s}+0 k{s}+0 d{d} "
    (geo,) = [g for c, g in profile.counters()["flash_geometry"][
        "ddstore_flash_dkv"].items() if c.startswith(key)
        and c.endswith(f"{layout} kv{b * h_kv}")]
    assert geo["dq"] == "resident" and geo["dq_vmem_bytes"] == s * d * 4
    assert limit == geo["vmem_limit"] \
        == 32 * 2 ** 20 + geo["dq_vmem_bytes"] + 2 * s * d * 2


def test_expert_layer_lowers_to_grouped_products(one_chip, no_compile_cache,
                                                 monkeypatch):
    """Published widths, a quarter of a step's tokens: the grouped products
    are ``ops/moe_gmm.py``'s kernels, whose steps follow the rows each
    expert got. The first trip over the sorted rows and the loop's body
    for the others each hold the forward's three, and in the backward rule
    the forward's three again beside the transposes' six (three of them
    the same kernel, three the matrices' cotangent); XLA may share the
    in-line trip's three between the two rules (here, where nothing is
    rematerialised, it does). No ragged-dot is left."""
    from ddstore_tpu.models.moe import SharedRoutedMoe, routed_chunk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    layer = SharedRoutedMoe(64, 4, 1536, share=(0, 8), scaling=1.8)
    x = jax.ShapeDtypeStruct((4096, 2048), jnp.bfloat16, sharding=one_chip)
    params = jax.eval_shape(layer.init, jax.random.key(0), x)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)

    def f(p, x):
        y, load = layer.apply(p, x)
        return (y.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(f, argnums=(0, 1))).lower(params, x) \
        .compile().as_text()
    assert routed_chunk(4096, 4, 8, 64) == 3072     # of 16,384 pairs
    assert "ragged-dot" not in text
    assert len(_mosaic_calls(text, "ddstore_moe_gmm")) in (2 * (3 + 6) - 3,
                                                           2 * (3 + 6))
    assert len(_mosaic_calls(text, "ddstore_moe_tgmm")) == 2 * 3


@pytest.mark.parametrize("rows,d,hidden", [
    (9216, 2688, 1856), (49152, 2048, 1792), (12288, 2048, 1536)],
    ids=["nemotron3-nano-ep16", "lfm2-8b-a1b-ep4", "glm47-flash-ep8"])
def test_grouped_product_kernels_fit_the_chip_at_the_cells_shapes(
        one_chip, no_compile_cache, rows, d, hidden):
    """A trip's six products of each configuration (into the experts'
    width and out of it: the product, the rows' cotangent, the matrices'),
    at the tiles the rule gives their shapes, the experts' width padded to
    whole lane tiles: they lower and fit VMEM."""
    from ddstore_tpu.ops import moe_gmm

    wide = moe_gmm.padded(hidden)
    shape = lambda dims, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dt, sharding=one_chip)

    def f(x, w_in, w_out, sizes):
        gmm = lambda a, b: moe_gmm.moe_gmm(a, b, sizes, interpret=False)
        return (gmm(gmm(x, w_in), w_out).astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(
        shape((rows, d)), shape((8, d, wide)), shape((8, wide, d)),
        shape((8,), jnp.int32)).compile().as_text()
    assert len(_mosaic_calls(text, "ddstore_moe_gmm")) == 4
    assert len(_mosaic_calls(text, "ddstore_moe_tgmm")) == 2


@pytest.mark.parametrize("tokens,k,held,of,d", [
    (16384, 8, 16, 128, 2048), (32768, 4, 8, 32, 2048),
    (16384, 6, 8, 128, 2688), (16384, 4, 8, 64, 2048)],
    ids=["sdar-30b-a3b-ep8", "lfm2-8b-a1b-ep4", "nemotron3-nano-ep16",
         "glm47-flash-ep8"])
def test_the_way_back_kernel_fits_the_chip_at_the_cells_shapes(
        one_chip, no_compile_cache, tokens, k, held, of, d):
    """``ddstore_moe_combine`` over a trip of each configuration, with
    float32 weights (the way back: three products a block) and with unit
    weights into the rows' type (the way there's transpose), at the tiles
    the rule gives: its two stages, sized for every pair of a tile live,
    fit VMEM, and its DMAs move whole tiles of the rows."""
    from ddstore_tpu.models.moe import routed_chunk
    from ddstore_tpu.ops import moe_combine

    shape = lambda dims, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dt, sharding=one_chip)

    def f(rows, rank, sizes, w):
        back = moe_combine.moe_combine(rows, rank, sizes, w, interpret=False)
        there = moe_combine.moe_combine(rows, rank, sizes,
                                        dtype=rows.dtype, interpret=False)
        return back, there

    text = jax.jit(f).lower(
        shape((routed_chunk(tokens, k, held, of), d)),
        shape((tokens, k), jnp.int32), shape((held,), jnp.int32),
        shape((tokens, k), jnp.float32)).compile().as_text()
    assert len(_mosaic_calls(text, "ddstore_moe_combine")) == 2


def test_gqa_kernels_lower_with_kv_at_their_own_heads(one_chip,
                                                      no_compile_cache):
    """The LFM2 cell's one attention call a step: 32 query heads on 8 K/V
    heads of 64 at S=8192, b=4. K and V enter both kernels as (b x 8, S,
    64): no operand repeated to 32 heads reaches them."""
    from ddstore_tpu.ops.attention import flash_attention

    q = jax.ShapeDtypeStruct((4, 32, 8192, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((4, 8, 8192, 64), jnp.bfloat16,
                              sharding=one_chip)

    def f(q, k, v):
        out, _ = flash_attention(q, k, v, causal=True, interpret=False)
        return (out.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(q, kv, kv) \
        .compile().as_text()
    calls = [ln for ln in text.splitlines() if "custom-call(" in ln
             and any(k in ln.split(" = ")[0] for k in (
                 "ddstore_flash_fwd", "ddstore_flash_dq",
                 "ddstore_flash_dkv"))]
    assert len(calls) == 2
    for ln in calls:
        operands = ln.split("custom-call(")[1]
        assert operands.count("bf16[32,8192,64]") >= 2, ln   # k and v
        assert "bf16[128,8192,64]" in operands, ln           # q


def test_short_conv_kernels_lower_for_the_chip(one_chip, no_compile_cache):
    """A conv layer's call in the LFM2 cell: (4, 8192, 3 x 2048) bfloat16,
    forward and backward kernels."""
    from ddstore_tpu.ops.short_conv import gated_short_conv

    x = jax.ShapeDtypeStruct((4, 8192, 6144), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((3, 2048), jnp.float32, sharding=one_chip)

    def f(x, w):
        y = gated_short_conv(x, w, interpret=False)
        return (y.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(f, argnums=(0, 1))).lower(x, w).compile() \
        .as_text()
    assert "ddstore_short_conv_fwd" in text
    assert "ddstore_short_conv_bwd" in text


def test_the_lfm2_cell_step_fits_the_chip(one_chip, no_compile_cache,
                                          monkeypatch):
    """``lfm2-8b-a1b-ep4.s8192.b4``'s whole train step at its published
    widths, 32,768 tokens, compiled for the described chip: 507,820,288
    parameters, and arguments + temporaries inside the v5e's 16.9 GB (the
    described compile reads temporaries high: PERF.md section 7). The
    model asks the backend which attention to run; the test says TPU."""
    import json
    import os

    import optax

    from ddstore_tpu.models import transformer as T

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "lfm2-8b-a1b-ep4.json")) as f:
        cfg = json.load(f)
    model = T.lm_from_description(cfg, compute_dtype=jnp.bfloat16)
    lr = optax.linear_schedule(0.0, cfg["lr"], cfg["lr_warmup_steps"])
    state = jax.eval_shape(
        lambda k: T.create_train_state(k, model, lr=lr)[0],
        jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(state.params)) \
        == 507_820_288
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    tok = jax.ShapeDtypeStruct((4, 8192), jnp.int32, sharding=one_chip)
    step = T.make_train_step(model, optax.adam(lr))
    compiled = step.lower(on_chip(state), tok, tok, tok).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 4.3e9 < total < 15e9, total
    text = compiled.as_text()
    for kernel in ("ddstore_flash_fwd", "ddstore_flash_dkv",
                   "ddstore_short_conv_fwd",
                   "ddstore_short_conv_bwd"):
        assert kernel in text, kernel
    assert "ragged-dot" not in text
    # by pass: the flash forward's output is saved by name, so only the
    # convolution's forward kernel runs again under nn.remat
    assert _kernel_passes(text) == {
        "ddstore_flash_fwd": {"forward"}, "ddstore_flash_dkv": {"backward"},
        "ddstore_short_conv_fwd": {"forward", "recompute"},
        "ddstore_short_conv_bwd": {"backward"}, **_PRODUCTS_PASSES}


def test_conv_silu_kernels_lower_for_the_chip(one_chip, no_compile_cache):
    """A Mamba-2 layer's convolution in the ``nemotron3-nano-ep16`` cell:
    (2, 8192, 6144) bfloat16 under four biased taps, forward and backward
    kernels."""
    from ddstore_tpu.ops.short_conv import short_conv

    x = jax.ShapeDtypeStruct((2, 8192, 6144), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((4, 6144), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((6144,), jnp.float32, sharding=one_chip)

    def f(x, w, b):
        y = short_conv(x, w, b, interpret=False)
        return (y.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(x, w, b).compile() \
        .as_text()
    assert "ddstore_conv_silu_fwd" in text
    assert "ddstore_conv_silu_bwd" in text


def _kernel_passes(text):
    """``{kernel: {pass}}`` of a compiled step's Mosaic calls: each by the
    name the program gave it, which its ``op_name`` carries inside the
    layer's scopes, in the pass ``profile.describe`` reads around them."""
    import re

    from ddstore_tpu.utils import profile

    found = {}
    for ln in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in ln:
            continue
        m = re.search(r'op_name="([^"]*)"', ln)
        scopes, which = profile.describe(m.group(1) if m else "")
        if scopes and scopes[-1].startswith("ddstore_"):
            assert scopes[-1] in profile.STEP_SCOPES, scopes
            found.setdefault(scopes[-1], set()).add(which)
    return found


# The grouped products' kernels in a step: the forward, ``_routed_bwd``'s
# replay (``nn.remat``'s second forward needs none: the rule keeps the
# layer's inputs and nothing of its products) and the transposed side; the
# way back from the experts' rows in the forward (its replay is dead) and,
# with unit weights, as the way there's transpose.
_PRODUCTS_PASSES = {"ddstore_moe_gmm": {"forward", "recompute", "backward"},
                    "ddstore_moe_tgmm": {"backward"},
                    "ddstore_moe_combine": {"forward", "backward"}}


def _mosaic_calls(text, kernel):
    return [ln for ln in text.splitlines() if "custom-call(" in ln
            and kernel in ln.split(" = ")[0]]


def test_ssd_kernels_lower_for_the_chip_and_keep_the_decay_in_vmem(
        one_chip, no_compile_cache):
    """A Mamba-2 layer's scan in the ``nemotron3-nano-ep16`` cell, handed
    over as the mixer does (the three parts of one ``xBC``): (2, 8192), 64
    heads of 64 on 8 groups of state 128, chunk 128, bfloat16. Forward and
    backward kernels lower and fit VMEM, and around them the module holds
    no float32 buffer of tokens x H x Q elements: the decay matrix, its
    product with ``C B^T`` and the chunk states stay inside the kernels."""
    import math
    import re

    from ddstore_tpu.ops.ssd import ssd

    b, s, h, p, g, n, q = 2, 8192, 64, 64, 8, 128, 128
    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)
    xbc = shape((b, s, h * p + 2 * g * n), jnp.bfloat16)
    dt, A = shape((b, s, h), jnp.float32), shape((h,), jnp.float32)

    def f(xbc, dt, A, D):
        x, B, C = jnp.split(xbc, (h * p, h * p + g * n), axis=-1)
        y = ssd(x.reshape(b, s, h, p), dt, A, B.reshape(b, s, g, n),
                C.reshape(b, s, g, n), D, q, interpret=False)
        return (y.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(f, argnums=(0, 1, 2, 3))).lower(
        xbc, dt, A, A).compile().as_text()
    assert len(_mosaic_calls(text, "ddstore_ssd_fwd")) == 1
    assert len(_mosaic_calls(text, "ddstore_ssd_bwd")) == 1
    largest = max(math.prod(int(d) for d in dims.split(","))
                  for dims in re.findall(r"f32\[([0-9,]+)\]", text))
    assert largest < b * s * h * q, largest
    assert f"bf16[{b},{s // q},{n},{h * p}]" in text     # the chunks' states


def test_the_nemotron_cell_step_fits_the_chip(one_chip, no_compile_cache,
                                              monkeypatch):
    """``nemotron3-nano-ep16.s8192``'s whole train step at its published
    widths, 16,384 tokens, compiled for the described chip: 666,963,456
    parameters, arguments + temporaries inside the v5e's 16.91 GB; its
    attention through the sequence-major (``bshd``) kernels at 32 heads of
    128 with K and V at their own 2 heads; its expert layers through
    grouped products; its convolutions through their kernels. The model
    asks the backend which attention to run; the test says TPU."""
    import json
    import os

    import optax

    from ddstore_tpu.models import transformer as T

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "nemotron3-nano-ep16.json")) as f:
        cfg = json.load(f)
    model = T.lm_from_description(cfg, compute_dtype=jnp.bfloat16)
    lr = optax.linear_schedule(0.0, cfg["lr"], cfg["lr_warmup_steps"])
    state = jax.eval_shape(
        lambda k: T.create_train_state(k, model, lr=lr)[0],
        jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(state.params)) \
        == 666_963_456
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    tok = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)
    step = T.make_train_step(model, optax.adam(lr))
    compiled = step.lower(on_chip(state), tok, tok, tok).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 8.0e9 < total < 16.91e9, total
    text = compiled.as_text()
    for kernel in ("ddstore_conv_silu_fwd", "ddstore_conv_silu_bwd"):
        assert kernel in text, kernel
    assert "ragged-dot" not in text
    # an expert layer's two products: the forward's, the replay's, their
    # transposes (two of each form), in the first trip and in the loop's
    # body for the others; the widths padded 1856 -> 1920
    assert len(_mosaic_calls(text, "ddstore_moe_gmm")) == 2 * 4 * (2 + 2 + 2)
    assert len(_mosaic_calls(text, "ddstore_moe_tgmm")) == 2 * 4 * 2
    assert "bf16[8,2688,1920]" in text and "bf16[8,1920,2688]" in text
    # a Mamba layer's scan: the forward, remat's second forward (which
    # writes the chunks' states) and the backward, one kernel each
    assert len(_mosaic_calls(text, "ddstore_ssd_fwd")) == 2 * 4
    assert len(_mosaic_calls(text, "ddstore_ssd_bwd")) == 4
    assert _kernel_passes(text) == {
        "ddstore_flash_fwd": {"forward"}, "ddstore_flash_dkv": {"backward"},
        "ddstore_ssd_fwd": {"forward", "recompute"},
        "ddstore_ssd_bwd": {"backward"},
        "ddstore_conv_silu_fwd": {"forward", "recompute"},
        "ddstore_conv_silu_bwd": {"backward"}, **_PRODUCTS_PASSES}
    for kernel in ("ddstore_flash_fwd", "ddstore_flash_dkv"):
        calls = _mosaic_calls(text, kernel)
        assert calls, kernel
        for ln in calls:
            operands = ln.split("custom-call(")[1]
            assert "bf16[2,8192,4096]" in operands, ln            # q
            assert operands.count("bf16[2,8192,256]") >= 2, ln    # k and v


def test_the_sdar_cell_step_fits_the_chip(one_chip, no_compile_cache,
                                          monkeypatch):
    """``sdar-30b-a3b-ep8.s8192.b1``'s whole train step at its published
    widths, one window of 8,192 tokens = 16,384 positions, compiled for the
    described chip: 645,623,296 parameters (the configuration file's own
    count), arguments + temporaries inside the v5e's 16.91 GB; its
    attention through the sequence-major kernels under the block-diffusion
    mask, one call a layer over both halves, 32 heads of 128 with K and V
    at their own 4 heads; its experts through grouped products at 768, six
    lane tiles and no padding. The model asks the backend which attention
    to run; the test says TPU."""
    import json
    import os

    import optax

    from ddstore_tpu.models import transformer as T
    from ddstore_tpu.utils import profile

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "sdar-30b-a3b-ep8.json")) as f:
        cfg = json.load(f)
    model = T.lm_from_description(cfg, compute_dtype=jnp.bfloat16)
    lr = optax.linear_schedule(0.0, cfg["lr"], cfg["lr_warmup_steps"])
    state = jax.eval_shape(
        lambda k: T.create_train_state(k, model, lr=lr)[0],
        jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(state.params)) \
        == cfg["parameters"] == 645_623_296
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    tok = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    step = T.make_train_step(model, optax.adam(lr))
    compiled = step.lower(on_chip(state), tok, tok, tok).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 7.7e9 < total < 16.91e9, total
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert "bf16[16,2048,768]" in text and "bf16[16,768,2048]" in text
    assert _kernel_passes(text) == {
        "ddstore_flash_fwd": {"forward"}, "ddstore_flash_dkv": {"backward"}, **_PRODUCTS_PASSES}
    for kernel in ("ddstore_flash_fwd", "ddstore_flash_dkv"):
        calls = _mosaic_calls(text, kernel)
        assert len(calls) == 6, kernel
        for ln in calls:
            operands = ln.split("custom-call(")[1]
            assert "bf16[1,16384,4096]" in operands, ln            # q
            assert operands.count("bf16[1,16384,512]") >= 2, ln    # k and v
        # no dead block is a step of any grid
        geo, = [c for call, c in profile.counters()["flash_geometry"][
            kernel].items() if call.startswith("blockdiff4 bh32 q16384")]
        assert geo["grid_steps"] == geo["blocks_live"]
        assert geo["pairs_needed"] == 8192 * 8192 + 4 * 8192
        assert geo["pairs_computed"] < 1.13 * geo["pairs_needed"]


def test_the_smallthinker_cell_step_fits_the_chip(one_chip, no_compile_cache,
                                                  monkeypatch):
    """``smallthinker-21b-a3b-ep4.s16384.b1``'s whole train step at its
    published widths, one window of 16,384 tokens, compiled for the
    described chip: 656,529,920 parameters (the configuration file's own
    count), arguments + temporaries inside the v5e's 16.91 GB; its
    attention through the sequence-major kernels, one causal call (the full
    layer) and three under the 4,096-key window, 28 query heads on K and V
    at their own 4 (a group of 7), the windowed calls' grids the blocks
    holding a live pair and no others; its ReGLU experts through grouped
    products at 768. The model asks the backend which attention to run;
    the test says TPU."""
    import json
    import os
    import re

    import optax

    from ddstore_tpu.models import transformer as T
    from ddstore_tpu.utils import profile

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "smallthinker-21b-a3b-ep4.json")) as f:
        cfg = json.load(f)
    model = T.lm_from_description(cfg, compute_dtype=jnp.bfloat16)
    lr = optax.linear_schedule(0.0, cfg["lr"], cfg["lr_warmup_steps"])
    state = jax.eval_shape(
        lambda k: T.create_train_state(k, model, lr=lr)[0],
        jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(state.params)) \
        == cfg["parameters"] == 656_529_920
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    tok = jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one_chip)
    step = T.make_train_step(model, optax.adam(lr))
    compiled = step.lower(on_chip(state), tok, tok, tok).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 7.8e9 < total < 16.91e9, total
    print(f"arguments {m.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.2f} GB")
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert "bf16[16,2560,768]" in text and "bf16[16,768,2560]" in text
    assert _kernel_passes(text) == {
        "ddstore_flash_fwd": {"forward"}, "ddstore_flash_dkv": {"backward"}, **_PRODUCTS_PASSES}
    for kernel in ("ddstore_flash_fwd", "ddstore_flash_dkv"):
        calls = _mosaic_calls(text, kernel)
        assert len(calls) == 4, kernel
        windowed = 0
        for ln in calls:
            operands = ln.split("custom-call(")[1]
            assert "bf16[1,16384,3584]" in operands, ln            # q
            assert operands.count("bf16[1,16384,512]") >= 2, ln    # k and v
            op_name = re.search(r'op_name="([^"]*)"', ln).group(1)
            windowed += "window" in profile.describe(op_name)[0]
        assert windowed == 3, kernel
        geo, = [c for call, c in profile.counters()["flash_geometry"][
            kernel].items() if call.startswith("window4096 bh28 q16384")]
        assert geo["grid_steps"] == geo["blocks_live"]
        assert geo["pairs_needed"] == 4096 * 4097 // 2 + 12288 * 4096
        assert geo["pairs_computed"] < 1.2 * geo["pairs_needed"]
        print(kernel, geo)


@pytest.mark.parametrize("b,s,most", [(8, 2048, 7), (2, 8192, 8)])
def test_mla_mixer_hands_the_kernels_what_its_products_write(
        one_chip, no_compile_cache, monkeypatch, b, s, most):
    """One latent-attention mixer of ``glm47-flash-ep8`` at its published
    widths (20 heads of 192 | 64 and 256), forward and backward under the
    cell's ``remat_policy``, at the two cells' shapes. Both kernels
    take q, k, v (and ``do``) as ``bf16[b,S,5120]``, sequence-major, and fit
    VMEM; and the module holds at most ``most`` transposing ``copy``
    instructions of a 20 x 256-wide bfloat16 tensor: q and k after their
    192 | 64 concatenations (forward and remat's), ``out`` into ``proj``,
    ``dq`` and ``dk`` into their slices, and at (2, 8192) ``do`` (PERF.md
    section 6, PR 32: 13 and 11 with head-major kernels and V sliced out of
    a 448-wide product). The model asks the backend which attention to run;
    the test says TPU."""
    import json
    import math
    import os
    import re

    import flax.linen as nn

    from ddstore_tpu.models import mixers, transformer as T

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "glm47-flash-ep8.json")) as f:
        cfg = json.load(f)
    lm = T.lm_from_description(cfg, compute_dtype=jnp.bfloat16)

    class Mixer(nn.Module):
        dim: int = lm.dim
        heads: int = lm.heads
        arch: object = lm.arch
        compute_dtype: object = jnp.bfloat16

        @nn.compact
        def __call__(self, x, positions):
            return x + mixers._mla_mixer(self, x, positions)

    mixer = Mixer()
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    x = jax.ShapeDtypeStruct((b, s, lm.dim), jnp.float32)
    pos = jax.ShapeDtypeStruct((b, s), jnp.int32)
    params = jax.eval_shape(mixer.init, jax.random.key(0), x, pos)
    policy = T._remat_policy(cfg["remat_policy"])

    def f(p, x, pos):
        y = jax.checkpoint(mixer.apply, policy=policy)(p, x, pos)
        return (y ** 2).sum()

    text = jax.jit(jax.grad(f, argnums=(0, 1))).lower(
        *on_chip((params, x, pos))).compile().as_text()
    wide = f"bf16[{b},{s},5120]"
    for kernel, operands in (("ddstore_flash_fwd", 3),
                             ("ddstore_flash_dkv", 4)):
        (call,) = [ln for ln in text.splitlines() if "custom-call(" in ln
                   and kernel in ln.split(" = ")[0]]
        assert call.split("custom-call(")[1].count(wide) == operands, call
    copies = [m.group(1) for m in re.finditer(
        r"= bf16\[([0-9,]+)\]\S* copy\(", text)
        if math.prod(map(int, m.group(1).split(","))) == b * s * 5120]
    assert 1 <= len(copies) <= most, copies
