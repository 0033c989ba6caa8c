"""Family ``lfm2_moe_lm``: a decoder of gated short-convolution and
grouped-query attention layers with bias-routed experts, no shared expert
and a tied head (``ddstore_tpu.models.transformer`` with an
``Lfm2MoeArch``) as one expert-parallel chip's share, fed token windows
from the store, built through the calls ``examples/lm_longcontext.py``
makes: the configuration's keys are the description ``lm_from_description``
takes.

**What the first step is held to.** The harness compares one number of
the first step with one of the reference, by ``loss_rtol``. A first loss
on seeded weights hardly sees positions (the reference without its rotary
step reads 1.4e-4 to 4.4e-4 from it where the bfloat16 program reads up to
9.1e-5) and nothing of the backward kernels. The step's gradient sees
both, so this family holds the first step to two limits: its loss within
``loss_rtol`` of the reference's, and its gradient, every leaf, within
``grad_rtol`` of the reference's gradient by the norm of the difference
over the norm of the reference's. ``reference_loss`` returns the
reference's loss; the first call of ``step`` returns that loss moved by
the larger of the two differences, each as a share of its limit, so that
the harness's one comparison fails where either limit does, and prints
both. Every later call returns the step's loss."""

from __future__ import annotations

import collections
import functools
import time

import numpy as np

from ddbench import flops, lfm2_flops, rows, spec

UNIT = "tokens"
# Steps whose load vectors are kept for the readers, which pick the traced
# ones: every step of a run (a few hundred bytes each).
KEEP_LOADS = 4096
# ``optax.adam``'s decay of the first moment: from zeroed moments the first
# step leaves ``(1 - ADAM_B1) * gradient`` there.
ADAM_B1 = 0.9


# Token windows over ``vocab_size`` ids and their data set: the expert
# family's, as they are.
_windows = spec.load_module("families", "mla_moe_lm")
shard, reference_rows, open_dataset = (
    _windows.shard, _windows.reference_rows, _windows.open_dataset)


class _FlashView:
    """What ``ddbench/scopes.py:flash_kernel_work`` reads of ``job.model``
    (``dim // job.heads`` as the head width, ``layers`` as the flash calls
    a step, ``compute_dtype``): this model calls the kernels once a
    ``full_attention`` layer, not once a layer. K and V have fewer heads
    than it counts bytes for; FLOPs bound all three kernels at these
    lengths. The real model is ``job.lm``."""

    def __init__(self, dim, calls, compute_dtype):
        self.dim = dim
        self.layers = calls
        self.compute_dtype = compute_dtype


class Job:
    """State, step and reference of one cell; ``step`` is one iteration of
    the example's loop body."""

    def __init__(self, config, traffic, mesh, seed, dry_run):
        import jax
        import jax.numpy as jnp
        import optax

        from ddstore_tpu.models import transformer

        # What ``moe_scopes.held_loads`` and ``moe_flops.expert_flops_bytes``
        # read, under the names they read it by: the experts held here.
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
        self.state, tx = transformer.create_train_state(
            jax.random.key(seed & 0x7FFFFFFF), self.lm, lr=lr, mesh=mesh)
        self._step = transformer.make_train_step(self.lm, tx, mesh=mesh,
                                                 state=self.state)
        self.pos = jnp.tile(jnp.arange(self.seq, dtype=jnp.int32),
                            (self.batch, 1))
        # The expert biases to where the noaux_tc rule would have them for
        # these seeded weights, on the data set's first windows (a model in
        # training is balanced; a seeded router is not).
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
        calls = list(config["layer_types"]).count("full_attention")
        self.model = _FlashView(int(config["hidden_size"]), calls, dtype)
        self.flash_flops, self.flash_bytes = flops.flash_flops_bytes_per_step(
            calls, self.batch, self.heads, self.seq,
            int(config["hidden_size"]) // self.heads,
            jnp.dtype(dtype).itemsize)

    @property
    def flops_per_step(self) -> float:
        """Required FLOPs a step, the experts' from what the run's steps
        routed to the held ones (their mean; the expectation before any)."""
        pairs = None
        if self.loads:
            ep = self.config["expert_parallel"]
            held = int(self.config["num_experts"])
            first = int(ep["chip"]) * held
            pairs = float(np.mean([np.asarray(x)[:, first:first + held].sum()
                                   for x in self.loads]))
        return lfm2_flops.step_flops(self.config, self.batch, self.seq, pairs)

    def step(self, batch):
        tok, tgt = batch
        if self._compiled is None:
            t0 = time.perf_counter()
            self._compiled = self._step.lower(self.state, tok, tgt,
                                              self.pos).compile()
            self.compile_s = time.perf_counter() - t0
        self.state, (loss, loads) = self._compiled(self.state, tok, tgt,
                                                   self.pos)
        # Device arrays, read only by a traced run's readers after the
        # window: work is counted from what was routed.
        self.loads.append(loads)
        if self._reference is not None:
            loss = self._held_to_reference(float(loss))
        return loss

    def reference_loss(self, host_batch) -> float:
        """The plain float32 loss on the current parameters and this batch,
        whole batch, and its gradient, kept on the host for the first
        ``step``: a window at a time, so that the gradient's program sits
        beside the state. Call before the first ``step``: the step donates
        the state."""
        import jax

        ref = spec.load_module("reference", "lfm2_moe_lm")
        tok, tgt = (np.asarray(a) for a in host_batch)
        arch = dict(self.lm.arch._asdict(), heads=self.heads)
        fn = jax.jit(jax.value_and_grad(
            functools.partial(ref.loss, arch=arch, token_block=1024)))
        pos = np.asarray(self.pos[:1])
        loss, grads = 0.0, None
        for i in range(len(tok)):
            one, g = fn(self.state.params, tok[i:i + 1], tgt[i:i + 1], pos)
            loss += float(one) / len(tok)
            g = [np.array(x) for x in jax.tree_util.tree_leaves(g)]
            if grads is None:
                grads = g
            else:
                for mine, one in zip(grads, g):
                    mine += one
        self._reference = (loss, [g / len(tok) for g in grads])
        return loss

    def _held_to_reference(self, loss) -> float:
        """The reference's loss moved by the larger of the first step's two
        differences from the reference, each as a share of its limit
        (the module's docstring). The step's gradient is read off the
        state it left: Adam's first moment after one step from zero."""
        import jax

        want, grads = self._reference
        self._reference = None
        adam = self.state.opt_state[0]
        if int(adam.count) != 1:
            raise RuntimeError("the reference was taken on a state that "
                               "had already stepped")
        diff = norm = 0.0
        for mu, g in zip(jax.tree_util.tree_leaves(adam.mu), grads):
            g = g.astype(np.float64)
            d = np.asarray(mu, np.float64) / (1.0 - ADAM_B1) - g
            diff += float(np.vdot(d, d))
            norm += float(np.vdot(g, g))
        loss_err = abs(loss - want) / abs(want)
        grad_err = (diff / norm) ** 0.5
        loss_rtol = float(self.config["loss_rtol"])
        grad_rtol = float(self.config["grad_rtol"])
        print(f"first step against the reference: loss {loss:.6f} for "
              f"{want:.6f}, relative difference {loss_err:.2e} (allowed "
              f"{loss_rtol}); gradient, every leaf: norm of the difference "
              f"over the reference's norm {grad_err:.3e} (allowed "
              f"{grad_rtol})", flush=True)
        return want * (1.0 + max(loss_err,
                                 grad_err * loss_rtol / grad_rtol))


def build(config, traffic, mesh, seed, dry_run=False):
    return Job(config, traffic, mesh, seed, dry_run)
