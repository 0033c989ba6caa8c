"""Family ``sdar_moe_lm``: a decoder of grouped-query attention and
softmax-routed experts trained by block diffusion
(``ddstore_tpu.models.transformer`` with an ``SdarMoeArch``) as one
expert-parallel chip's share, fed token windows from the store, built
through the calls ``examples/lm_longcontext.py`` makes: the configuration's
keys are the description ``lm_from_description`` takes.

**A token is a window token.** A step takes ``batch`` windows of ``seq``
tokens from the store (``units_per_row`` is ``seq``); the model runs ``2
seq`` positions a window, the noised window beside the clean one, which is
the model's business. The windows' shifted targets, which the data set
carries for the other families, go unread: a position's target is the
window's own token.

The first step is held to the reference's loss **and** gradient, every
leaf, by ``loss_rtol`` and ``grad_rtol``, folded into the harness's one
comparison as ``families/lfm2_moe_lm.py`` folds them (its module docstring;
``step``, the fold and the data set are that family's, inherited). The
reference draws nothing: it is given the step's mask bits and the blocks' t
as it is given the tokens (``transformer.diffusion_noise`` for the key of
the state's step number), and builds the noised window, the mask and the
weights itself.

**A third number: the window's start alone.** An error at the mask's edge
moves ``block_length`` keys of a row, 4 of a mean of 4,096 at ``seq`` 8192:
the reference with a noised query let see its own clean block reads a
whole-window gradient 7.5e-3 and 8.4e-3 off the program's, inside what
bfloat16 reads (5.1e-3 to 7.4e-3; my chip runs, PR 37). Where a row has few keys the same
error is most of the row, so the family also takes the gradient of the
loss over the window's first ``reference_prefix`` tokens alone (its own
draw for that shape, through the same kernels at ``2 reference_prefix``
positions) and holds it, every leaf, to the reference's by
``prefix_grad_rtol`` (the program reads 1.07e-2 to 1.26e-2 there, that
broken reference 0.124), folded in as the other two are.

**The shared flash readers.** ``ddbench/scopes.py:flash_kernel_work`` counts
``seq (seq + 1) / 2`` causal pairs and ``seq`` rows a call. ``_FlashView``
hands it two calls' worth a layer: ``seq^2 + seq`` pairs and ``2 seq`` rows
for the mask's own ``seq^2 + block_length seq`` pairs over ``2 seq`` rows
(0.04 % under at ``seq`` 8192, ``block_length`` 4). ``job.flash_flops`` /
``job.flash_bytes`` are the mask's own count (``ddbench/sdar_flops.py``)."""

from __future__ import annotations

import collections
import functools
import time

import numpy as np

from ddbench import rows, sdar_flops, spec

_lfm2 = spec.load_module("families", "lfm2_moe_lm")
UNIT, KEEP_LOADS = _lfm2.UNIT, _lfm2.KEEP_LOADS
shard, reference_rows, open_dataset = (
    _lfm2.shard, _lfm2.reference_rows, _lfm2.open_dataset)


class _FlashView:
    """What ``ddbench/scopes.py:flash_kernel_work`` reads of ``job.model``
    (``dim // job.heads`` as the head width, ``layers`` as the causal flash
    calls of ``job.seq`` rows a step, ``compute_dtype``): heads are
    ``head_dim`` wide, and a layer's one call over ``2 seq`` positions
    under the mask is two such calls' worth (the module's docstring). The
    real model is ``job.lm``."""

    def __init__(self, heads, head_dim, layers, compute_dtype):
        self.dim = heads * head_dim
        self.layers = 2 * layers
        self.compute_dtype = compute_dtype


class Job(_lfm2.Job):
    """State, step and reference of one cell; ``step`` is one iteration of
    the example's loop body."""

    def __init__(self, config, traffic, mesh, seed, dry_run):
        import jax
        import jax.numpy as jnp
        import optax

        from ddstore_tpu.models import transformer

        # The noise is an input like the windows: drawn from the run's seed
        # and the step number. ``n_routed_experts`` is what
        # ``moe_scopes.held_loads`` and ``moe_flops.expert_flops_bytes``
        # read the held experts by.
        config = dict(config, noise_seed=int(seed) & 0x7FFFFFFF)
        self.config = dict(config, n_routed_experts=int(config["num_experts"]))
        self.batch = int(traffic["batch"])
        self.seq = int(traffic["seq"])
        self.units_per_row = self.seq
        self.heads = int(config["num_attention_heads"])
        self.loader_kwargs = {"spec": jax.P("dp", None)}
        # float32 on the CPU, as the other families' dry runs.
        dtype = jnp.float32 if dry_run else jnp.dtype(config["compute_dtype"])
        self.lm = transformer.lm_from_description(
            config, compute_dtype=dtype, mesh=mesh)
        # The window is a job's first steps: the rate is still warming up.
        lr = optax.linear_schedule(0.0, float(config["lr"]),
                                   int(config["lr_warmup_steps"]))
        self.state, self._tx = transformer.create_train_state(
            jax.random.key(seed & 0x7FFFFFFF), self.lm, lr=lr, mesh=mesh)
        self._step = transformer.make_train_step(
            self.lm, self._tx, mesh=mesh, state=self.state)
        self.pos = jnp.tile(jnp.arange(self.seq, dtype=jnp.int32),
                            (self.batch, 1))
        # Nothing balances the router: the model has no such rule, the
        # layer drops no pair whatever the routing, and the cell reports
        # the imbalance it runs at. Which experts this chip holds is the
        # deployment's choice: placed by their load on the data set's first
        # windows, so that the chip's share of the pairs, and with it the
        # step's time, does not swing with the seed.
        n = transformer.PLACEMENT_BATCHES
        tok = rows.token_shard(seed, 0, n * self.batch, self.seq,
                               self.lm.vocab)[0]
        t0 = time.perf_counter()
        self.state = transformer.place_experts(
            self.lm, self.state, tok.reshape(n, self.batch, self.seq),
            self.pos)
        print(f"experts placed over {n} batches in "
              f"{time.perf_counter() - t0:.1f} s of set-up", flush=True)
        self._compiled = None
        self._reference = None
        self.loads = collections.deque(maxlen=KEEP_LOADS)
        layers, head_dim = (int(config["num_hidden_layers"]),
                            int(config["head_dim"]))
        self.model = _FlashView(self.heads, head_dim, layers, dtype)
        self.flash_flops, self.flash_bytes = \
            sdar_flops.flash_flops_bytes_per_step(
                layers, self.batch, self.heads,
                int(config["num_key_value_heads"]), self.seq,
                int(config["block_length"]), head_dim,
                jnp.dtype(dtype).itemsize)

    @property
    def flops_per_step(self) -> float:
        """Required FLOPs a step, the experts' from what the run's steps
        routed to the held ones (their mean a layer; the expectation before
        any)."""
        pairs = None
        if self.loads:
            held = int(self.config["num_experts"])
            first = int(self.config["expert_parallel"]["chip"]) * held
            pairs = np.mean([np.asarray(x)[:, first:first + held].sum(-1)
                             for x in self.loads], axis=0)
            even = 2 * self.batch * self.seq * int(
                self.config["num_experts_per_tok"]) / int(
                self.config["expert_parallel"]["chips"])
            print(f"held pairs a layer, mean over the run's steps: "
                  f"{[round(float(p)) for p in pairs]} (an eighth of a "
                  f"layer's pairs: {even:.0f})", flush=True)
        return sdar_flops.step_flops(self.config, self.batch, self.seq, pairs)

    def reference_loss(self, host_batch) -> float:
        """The plain float32 loss on the current parameters, this batch and
        this step's noise draw, and its gradient, kept on the host for the
        first ``step``: a window at a time. Call before the first ``step``:
        the step donates the state. Adam's two moments, zeros until the
        first step, are let go for the while and made again after
        (``tx.init``, where they lay), as ``families/nemotron_h_lm.py``
        does: the gradient's program sits beside the parameters alone."""
        import jax

        from ddstore_tpu.models import transformer
        from ddstore_tpu.models.transformer import TrainState
        from ddstore_tpu.parallel.tp import shardings_of

        ref = spec.load_module("reference", "sdar_moe_lm")
        tok = np.asarray(host_batch[0])
        a = self.lm.arch
        arch = dict(a._asdict(), heads=self.heads,
                    mask_token=self.lm.vocab - 1 if a.mask_token is None
                    else a.mask_token)
        state, self.state = self.state, None
        if int(state.opt_state[0].count) != 0:
            raise RuntimeError("the reference is taken before the first step")
        masked, t = (np.asarray(x) for x in transformer.diffusion_noise(
            a, transformer.diffusion_key(a, state.step), *tok.shape))
        fn = jax.jit(jax.value_and_grad(
            functools.partial(ref.loss, arch=arch, token_block=1024)))
        pos = np.asarray(self.pos[:1])
        params, step, moments = (state.params, state.step,
                                 shardings_of(state.opt_state))
        del state
        loss, grads = 0.0, None
        for i in range(len(tok)):
            one, g = fn(params, tok[i:i + 1], masked[i:i + 1], t[i:i + 1],
                        pos)
            loss += float(one) / len(tok)
            g = [np.array(x) for x in jax.tree_util.tree_leaves(g)]
            if grads is None:
                grads = g
            else:
                for mine, one in zip(grads, g):
                    mine += one
        self._prefix_err = self._prefix_against_reference(
            ref, arch, params, step, tok)
        self.state = TrainState(params, jax.jit(
            self._tx.init, out_shardings=moments)(params), step)
        self._reference = (loss, [g / len(tok) for g in grads])
        return loss

    def _prefix_against_reference(self, ref, arch, params, step, tok):
        """The gradient of the loss over the windows' first
        ``reference_prefix`` tokens alone, the program's against the
        reference's for the same draw: the norm of the difference over the
        reference's norm, every leaf (``None`` where the window is no
        longer than the prefix)."""
        import jax

        from ddstore_tpu.models import transformer

        n = int(self.config.get("reference_prefix", 0))
        if not 0 < n < self.seq:
            return None
        a = self.lm.arch
        key = transformer.diffusion_key(a, step)
        tok, pos = tok[:, :n], np.asarray(self.pos[:, :n])
        masked, t = (np.asarray(x) for x in transformer.diffusion_noise(
            a, key, *tok.shape))
        mine = jax.jit(jax.grad(lambda p: transformer.lm_loss(
            self.lm, p, tok, None, pos, noise_key=key)[0]))(params)
        mine = [np.asarray(x, np.float64)
                for x in jax.tree_util.tree_leaves(mine)]
        want = jax.jit(jax.grad(functools.partial(
            ref.loss, arch=arch, token_block=1024)))(params, tok, masked, t,
                                                     pos)
        diff = norm = 0.0
        for g, w in zip(mine, jax.tree_util.tree_leaves(want)):
            w = np.asarray(w, np.float64)
            diff += float(np.vdot(g - w, g - w))
            norm += float(np.vdot(w, w))
        return (diff / norm) ** 0.5

    def _held_to_reference(self, loss) -> float:
        """``lfm2_moe_lm``'s fold of the loss's and the gradient's
        differences, and the prefix gradient's as a third, each as a share
        of its limit."""
        want = self._reference[0]
        folded = super()._held_to_reference(loss)
        if self._prefix_err is None:
            return folded
        loss_rtol, prefix_rtol = (float(self.config[k]) for k in (
            "loss_rtol", "prefix_grad_rtol"))
        print(f"first step against the reference, the window's first "
              f"{self.config['reference_prefix']} tokens alone: gradient, "
              f"every leaf: norm of the difference over the reference's "
              f"norm {self._prefix_err:.3e} (allowed {prefix_rtol})",
              flush=True)
        return max(folded, want * (
            1.0 + self._prefix_err * loss_rtol / prefix_rtol))


def build(config, traffic, mesh, seed, dry_run=False):
    return Job(config, traffic, mesh, seed, dry_run)
