"""Family ``nemotron_h_lm``: a decoder of one-branch layers, each a Mamba-2
mixer, rotary-free grouped-query attention or a shared + routed layer of
ungated relu² experts, with an untied head
(``ddstore_tpu.models.transformer`` with a ``NemotronHArch``) as one
expert-parallel chip's share, fed token windows from the store, built
through the calls ``examples/lm_longcontext.py`` makes: the configuration's
keys are the description ``lm_from_description`` takes.

The first step is held to the reference's loss **and** gradient, every
leaf, by ``loss_rtol`` and ``grad_rtol``, folded into the harness's one
comparison as ``families/lfm2_moe_lm.py`` folds them (its module docstring;
``step`` and the fold are that family's, inherited): a seeded first loss
hardly sees the order of positions, which here only the scan and the
convolution give, and nothing of the backward pass."""

from __future__ import annotations

import collections
import functools

import numpy as np

from ddbench import flops, nemotron_flops, rows, spec

_lfm2 = spec.load_module("families", "lfm2_moe_lm")
UNIT, KEEP_LOADS = _lfm2.UNIT, _lfm2.KEEP_LOADS
shard, reference_rows, open_dataset = (
    _lfm2.shard, _lfm2.reference_rows, _lfm2.open_dataset)


class _FlashView:
    """What ``ddbench/scopes.py:flash_kernel_work`` reads of ``job.model``
    (``dim // job.heads`` as the head width, ``layers`` as the flash calls
    a step, ``compute_dtype``): this model's heads are ``head_dim`` wide,
    not ``hidden_size / heads``, so ``dim`` is heads x ``head_dim``, and it
    calls the kernels once a ``*`` layer. The real model is ``job.lm``."""

    def __init__(self, heads, head_dim, calls, compute_dtype):
        self.dim = heads * head_dim
        self.layers = calls
        self.compute_dtype = compute_dtype


class Job(_lfm2.Job):
    """State, step and reference of one cell; ``step`` is one iteration of
    the example's loop body."""

    def __init__(self, config, traffic, mesh, seed, dry_run):
        import jax
        import jax.numpy as jnp
        import optax

        from ddstore_tpu.models import transformer

        # ``n_routed_experts`` and ``expert_parallel`` are what
        # ``moe_scopes.held_loads`` reads, under the names it reads them by.
        self.config = dict(config)
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
        # The correction biases to where the noaux_tc rule would have them
        # for these seeded weights, on the data set's first windows.
        bal = dict(config["bias_balance"])
        n = int(bal.pop("batches"))
        tok, tgt = (a.reshape(n, self.batch, self.seq)
                    for a in rows.token_shard(
                        seed, 0, n * self.batch, self.seq, self.lm.vocab))
        self.state = transformer.balance_router_bias(
            self.lm, self.state, tok, tgt, self.pos, **bal)
        self._compiled = None
        self._reference = None
        self.loads = collections.deque(maxlen=KEEP_LOADS)
        calls = str(config["hybrid_override_pattern"]).count("*")
        head_dim = int(config["head_dim"])
        self.model = _FlashView(self.heads, head_dim, calls, dtype)
        self.flash_flops, self.flash_bytes = flops.flash_flops_bytes_per_step(
            calls, self.batch, self.heads, self.seq, head_dim,
            jnp.dtype(dtype).itemsize)

    @property
    def flops_per_step(self) -> float:
        """Required FLOPs a step, the experts' from what the run's steps
        routed to the held ones (their mean; the expectation before any)."""
        pairs = None
        if self.loads:
            held = int(self.config["n_routed_experts"])
            first = int(self.config["expert_parallel"]["chip"]) * held
            pairs = float(np.mean([np.asarray(x)[:, first:first + held].sum()
                                   for x in self.loads]))
        return nemotron_flops.step_flops(self.config, self.batch, self.seq,
                                         pairs)

    def reference_loss(self, host_batch) -> float:
        """The plain float32 loss on the current parameters and this batch,
        whole batch, and its gradient, kept on the host for the first
        ``step``: a window at a time, so that the gradient's program sits
        beside the parameters. Call before the first ``step``: the step
        donates the state.

        Adam's two moments, zeros until the first step, are let go for the
        while and made again after (``tx.init``, where they lay): beside
        all 8.0 GB of the state the gradient's program (6.04 GB reserved)
        found 5.81 GB of the chip free (my chip run, PR 33)."""
        import jax

        from ddstore_tpu.models.transformer import TrainState
        from ddstore_tpu.parallel.tp import shardings_of

        ref = spec.load_module("reference", "nemotron_h_lm")
        tok, tgt = (np.asarray(a) for a in host_batch)
        arch = dict(self.lm.arch._asdict(), heads=self.heads)
        fn = jax.jit(jax.value_and_grad(
            functools.partial(ref.loss, arch=arch, token_block=1024)))
        pos = np.asarray(self.pos[:1])
        state, self.state = self.state, None
        if int(state.opt_state[0].count) != 0:
            raise RuntimeError("the reference is taken before the first step")
        params, step, moments = (state.params, state.step,
                                 shardings_of(state.opt_state))
        del state
        loss, grads = 0.0, None
        for i in range(len(tok)):
            one, g = fn(params, tok[i:i + 1], tgt[i:i + 1], pos)
            loss += float(one) / len(tok)
            g = [np.array(x) for x in jax.tree_util.tree_leaves(g)]
            if grads is None:
                grads = g
            else:
                for mine, one in zip(grads, g):
                    mine += one
        self.state = TrainState(params, jax.jit(
            self._tx.init, out_shardings=moments)(params), step)
        self._reference = (loss, [g / len(tok) for g in grads])
        return loss


def build(config, traffic, mesh, seed, dry_run=False):
    return Job(config, traffic, mesh, seed, dry_run)
