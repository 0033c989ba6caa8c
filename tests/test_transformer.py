"""Long-context transformer: sequence-parallel train step equivalence vs
the unsharded step (exactness oracle — ring attention is exact), plus
store-fed training where token windows are fetched from the distributed
store."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddstore_tpu import DDStore, SingleGroup
from ddstore_tpu.data import DeviceLoader, DistributedSampler, ShardedDataset
from ddstore_tpu.models import transformer
from ddstore_tpu.parallel import make_mesh
from ddstore_tpu.utils import profile


def _data(key, b, s, vocab):
    tokens = jax.random.randint(jax.random.key(key), (b, s), 0, vocab,
                                jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    positions = jnp.tile(jnp.arange(s, dtype=jnp.int32), (b, 1))
    return tokens, targets, positions


def test_forward_shapes():
    model = transformer.TransformerLM(vocab=64, dim=32, heads=4, layers=2)
    tok, _, pos = _data(0, 2, 64, 64)
    params = model.init(jax.random.key(0), tok, pos)
    logits = model.apply(params, tok, pos)
    assert logits.shape == (2, 64, 64)


def test_sp_step_matches_single_device():
    # f32 compute so the only difference is the ring decomposition.
    mesh = make_mesh({"dp": 2, "sp": 4})
    kw = dict(vocab=64, dim=32, heads=4, layers=2,
              compute_dtype=jnp.float32)
    model_sp = transformer.TransformerLM(mesh=mesh, **kw)
    model_s = transformer.TransformerLM(**kw)
    state_sp, tx = transformer.create_train_state(jax.random.key(0),
                                                  model_sp, mesh=mesh)
    state_s, tx_s = transformer.create_train_state(jax.random.key(0),
                                                   model_s)
    step_sp = transformer.make_train_step(model_sp, tx, mesh=mesh,
                                          donate=False)
    step_s = transformer.make_train_step(model_s, tx_s, donate=False)

    tok, tgt, pos = _data(1, 4, 128, 64)
    new_sp, loss_sp = step_sp(state_sp, tok, tgt, pos)
    new_s, loss_s = step_s(state_s, tok, tgt, pos)
    np.testing.assert_allclose(float(loss_sp), float(loss_s), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(new_sp.params),
                    jax.tree.leaves(new_s.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_store_fed_lm_training_loss_decreases():
    """Token windows live in the store; the model learns a repeated-pattern
    corpus (loss must fall well below uniform log(vocab))."""
    mesh = make_mesh({"dp": 2, "sp": 4})
    vocab, seq = 32, 128
    rng = np.random.default_rng(0)
    base = rng.integers(0, vocab, size=16)
    corpus = np.tile(base, 64 * seq // 16 + 2)
    starts = rng.integers(0, len(corpus) - seq - 1, size=256)
    windows = np.stack([corpus[s:s + seq] for s in starts]).astype(np.int32)
    nexts = np.stack([corpus[s + 1:s + seq + 1] for s in starts]
                     ).astype(np.int32)

    with DDStore(SingleGroup(), backend="local") as store:
        ds = ShardedDataset(store, windows, nexts)
        model = transformer.TransformerLM(
            vocab=vocab, dim=64, heads=4, layers=2, mesh=mesh)
        state, tx = transformer.create_train_state(jax.random.key(0), model,
                                                   lr=1e-3, mesh=mesh)
        step = transformer.make_train_step(model, tx, mesh=mesh)
        sampler = DistributedSampler(len(ds), 1, 0, seed=0)
        pos = jnp.tile(jnp.arange(seq, dtype=jnp.int32), (8, 1))
        losses = []
        for epoch in range(2):
            sampler.set_epoch(epoch)
            loader = DeviceLoader(ds, sampler, batch_size=8, mesh=mesh,
                                  spec=jax.P("dp", "sp"))
            for tok, tgt in loader:
                state, loss = step(state, tok, tgt, pos)
                losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.6, (losses[0], losses[-1])
        assert losses[-1] < np.log(vocab)


def test_the_step_carries_its_scope_names():
    """The names a device trace is read by: the step's function name and
    one scope a module boundary, forward and transposed, in the lowered
    text's locations (they become each operation's ``op_name``)."""
    mesh = make_mesh({"dp": 2, "sp": 2}, jax.devices()[:4])
    model = transformer.TransformerLM(vocab=64, dim=32, heads=4, layers=2,
                                      compute_dtype=jnp.float32, mesh=mesh)
    state, tx = transformer.create_train_state(jax.random.key(0), model,
                                               mesh=mesh)
    step = transformer.make_train_step(model, tx, mesh=mesh, state=state)
    tok = jnp.zeros((2, 16), jnp.int32)
    pos = jnp.tile(jnp.arange(16, dtype=jnp.int32), (2, 1))
    text = step.lower(state, tok, tok, pos).as_text(debug_info=True)
    assert "jit_ddstore_lm_train_step" in text
    for scope in ("embed", "attn", "mlp", "head", "optimizer"):
        assert f"/{scope}/" in text or f"({scope})" in text, scope
    # a shard_map's body begins a name stack of its own; the compiled
    # module joins it to the caller's (``.../attn/shard_map/ring_step/...``)
    assert '"ring_step/' in text
    assert "transpose(jvp(TransformerLM))/block1/attn/" in text
    assert "jvp(TransformerLM)/block0/mlp/" in text


# -- the step by kind of work and by pass (ISSUE 35) ------------------------

# The ``jax.named_scope`` names each architecture's step enters (the
# kernels' ``pallas_call(name=)`` are ``tests/test_v5e_compile.py``'s: the
# CPU runs attention's reference). Each model test file holds its
# architecture to its row; together they are ``STEP_SCOPES``.
COMMON = {"embed", "attn", "mlp", "head", "optimizer", "mix_in", "mix_out"}
EMITS = {
    "dense": COMMON | {"dense_mlp", "ring_step", "recompute"},
    "mla_moe": COMMON | {"mix_norm", "dense_mlp", "shared_expert",
                         "moe_dispatch", "moe_experts", "mtp", "recompute"},
    "lfm2_moe": COMMON | {"mix_norm", "dense_mlp", "conv_mixer",
                          "short_conv", "moe_dispatch", "moe_experts",
                          "recompute"},
    "nemotron_h": COMMON | {"mix_norm", "mamba_mixer", "mamba_conv",
                            "short_conv", "ssd", "shared_expert",
                            "moe_dispatch", "moe_experts", "recompute"},
    "sdar_moe": COMMON | {"mix_norm", "moe_dispatch", "moe_experts",
                          "recompute", "diffusion_noise"},
    "smallthinker": COMMON | {"moe_dispatch", "moe_experts", "recompute",
                              "window"},
}


def step_names(monkeypatch, model, batch, seq, **step_kw):
    """``({(innermost scope, pass)}, {names}, {op_names})`` of ``model``'s
    train step compiled at a toy size: every operation's ``op_name``
    through ``profile.describe``, the ``jax.named_scope`` names the
    program's own files entered while it was traced, and the ``op_name``s
    themselves."""
    entered, real = set(), jax.named_scope
    ours = os.sep + "ddstore_tpu" + os.sep

    def recording(name, *args, **kwargs):
        if ours in sys._getframe(1).f_code.co_filename:
            entered.add(name)
        return real(name, *args, **kwargs)

    monkeypatch.setattr(jax, "named_scope", recording)
    state, tx = transformer.create_train_state(
        jax.random.key(0), model, mesh=step_kw.get("mesh"))
    if "mesh" in step_kw:
        step_kw["state"] = state
    step = transformer.make_train_step(model, tx, **step_kw)
    tok = jnp.zeros((batch, seq), jnp.int32)
    pos = jnp.tile(jnp.arange(seq, dtype=jnp.int32), (batch, 1))
    text = step.lower(state, tok, tok, pos).compile().as_text()
    found, op_names = set(), set(re.findall(r'op_name="([^"]*)"', text))
    for op_name in op_names:
        scopes, which = profile.describe(op_name)
        if scopes:
            found.add((scopes[-1], which))
    return found, entered, op_names


def replayed_products(op_names):
    """The expert products ``moe._routed_bwd`` computes again, by their
    ``op_name``: under the program's marker, which alone makes them the
    pass ``recompute`` (without it JAX names them as the backward's). The
    kernels' transposed rules, which the replay's ``jax.vjp`` pulls back,
    carry the marker too, behind a second ``transpose(``: the backward's."""
    marked = [n for n in op_names
              if "/recompute/" in n and "moe_experts" in n
              and n.endswith("dot_general")]
    replayed = [n for n in marked if profile.describe(n)[1] == "recompute"]
    for n in replayed:
        assert profile.describe(n.replace("/recompute/", "/"))[1] \
            == "backward", n
    for n in set(marked) - set(replayed):
        assert profile.describe(n)[1] == "backward", n
        before = n.split("/recompute/")[0].split("/")
        assert sum(part.startswith("transpose(") for part in before) == 2, n
        assert "/ddstore_moe_" in n, n
    return replayed


def passes_of(found, scope):
    return {which for kind, which in found if kind == scope}


def assert_the_products_kernels_passes(found, replayed=True):
    """An expert layer's grouped products by their kernels' own names:
    ``ddstore_moe_gmm`` forward, again (``nn.remat``'s second forward and
    ``moe._routed_bwd``'s replay; where nothing is rematerialised XLA may
    share the replay's with the forward's) and, transposed, in the
    backward; ``ddstore_moe_tgmm`` in the backward alone."""
    every = {"forward", "recompute", "backward"}
    got = passes_of(found, "ddstore_moe_gmm")
    assert every - (set() if replayed else {"recompute"}) <= got <= every
    assert passes_of(found, "ddstore_moe_tgmm") == {"backward"}
    # the way back in the forward (``_routed_bwd``'s replay of it is dead:
    # the compiled step drops it), the way there's transpose backward
    assert passes_of(found, "ddstore_moe_combine") == {"forward",
                                                         "backward"}


def assert_the_layout_names_the_products(entry, d, hidden):
    """``counters()["moe_layout"][layer]``: what the grouped products ran
    as, the tiles of each, and the width the experts' was padded to."""
    from ddstore_tpu.ops import moe_gmm

    assert entry["products"] == "pallas" and entry["combine"] == "pallas"
    wide = moe_gmm.padded(hidden)
    assert entry.get("padded_to") == (wide if wide != hidden else None)
    assert set(entry["tiles"]) == {"in", "out"}
    for (k, n), forms in zip(((d, wide), (wide, d)), entry["tiles"].values()):
        assert set(forms) == {"gmm", "gmm_t", "tgmm"}
        for tm, tk, tn in forms.values():
            assert entry["rows"] % tm == 0 and k % tk == 0 and n % tn == 0


def test_the_architectures_emit_the_vocabulary_between_them():
    kernels = {n for n in profile.STEP_SCOPES if n.startswith("ddstore_")}
    assert set().union(*EMITS.values()) | kernels == set(profile.STEP_SCOPES)
    # the flash backward is one kernel, ``ddstore_flash_dkv``: no dq kernel
    assert len(kernels) == 11 and "ddstore_flash_dq" not in kernels


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_dense_step_by_kind_of_work_and_pass(monkeypatch, remat):
    """``Block``'s projections and MLP under names of their own, forward
    and transposed; under ``nn.remat`` a second time; without it the only
    recomputation is the fused head's, which its rule marks."""
    mesh = make_mesh({"dp": 2, "sp": 2}, jax.devices()[:4])
    model = transformer.TransformerLM(
        vocab=64, dim=32, heads=4, layers=2, compute_dtype=jnp.float32,
        mesh=mesh, remat=remat)
    found, entered, _ = step_names(monkeypatch, model, 2, 16, mesh=mesh,
                                   fused_xent=True)
    assert entered == EMITS["dense"]
    again = {"recompute"} if remat else set()
    for scope in ("mix_in", "mix_out", "dense_mlp", "ring_step"):
        assert passes_of(found, scope) == {"forward", "backward"} | again, \
            scope
    assert passes_of(found, "head") == {"forward", "backward", "recompute"}
    assert passes_of(found, "optimizer") == {"update"}
    assert passes_of(found, "embed") == {"forward", "backward"}
    want = {"remat": remat, "policy": None, "saved": []}
    assert profile.counters()["remat"]["block1"] == want
