"""Family ``smallthinker_moe_lm``: a decoder of grouped-query attention,
window and full mixed, over softmax-routed ReGLU experts whose router reads
the layer's input before attention (``ddstore_tpu.models.transformer`` with
a ``SmallThinkerArch``) as one expert-parallel chip's share, fed token
windows from the store, built through the calls
``examples/lm_longcontext.py`` makes: the configuration's keys are the
description ``lm_from_description`` takes.

The first step is held to the reference's loss **and** gradient, every
leaf, by ``loss_rtol`` and ``grad_rtol``, folded into the harness's one
comparison as ``families/lfm2_moe_lm.py`` folds them (its module docstring;
``step``, the fold and the data set are that family's, inherited).

**A third number: the window's statistics.** An error at the window's edge
moves one key of 4,096 a row, which bfloat16 hides in the loss and the
gradient. So the family also takes, for the first windowed layer, the q, k
and v the program hands the flash kernels (the model's own forward on the
same parameters and window, sown under ``intermediates``), runs the
kernels' call on them as the step does (the same shapes, window and
blocks), and holds its ``lse`` on the rows at and past the window, where
the lower edge cuts, to the reference's on the same bfloat16 q and k
(``reference.window_lse``, float32, a block of query rows at a time): the
mean absolute difference over those rows and every head, by
``window_lse_atol``, folded in as the other two are.

**The shared flash readers.** ``ddbench/scopes.py:flash_kernel_work``
counts ``seq (seq + 1) / 2`` causal pairs and ``seq`` rows a call.
``_FlashView`` hands it the mix's own pairs: the full layer's and the
windowed layers' ``sum of min(i + 1, W)`` over a causal call's, 2.3125
calls' worth at ``seq`` 16,384 and W 4,096 for four layers, so that the
per-kernel shares are of the work the window leaves (their bytes are of
that many calls' rows, under the four the kernels move; FLOPs bound all
three at this length). ``job.flash_flops`` / ``job.flash_bytes`` are the
mix's own count and ``job.window_flops`` / ``job.window_bytes`` the
windowed layers' alone (``ddbench/smallthinker_flops.py``)."""

from __future__ import annotations

import collections
import functools
import time

import numpy as np

from ddbench import rows, smallthinker_flops, spec

_lfm2 = spec.load_module("families", "lfm2_moe_lm")
UNIT, KEEP_LOADS = _lfm2.UNIT, _lfm2.KEEP_LOADS
shard, reference_rows, open_dataset = (
    _lfm2.shard, _lfm2.reference_rows, _lfm2.open_dataset)


class _FlashView:
    """What ``ddbench/scopes.py:flash_kernel_work`` reads of ``job.model``
    (``dim // job.heads`` as the head width, ``layers`` as the causal flash
    calls of ``job.seq`` rows a step, ``compute_dtype``): heads are
    ``head_dim`` wide, and ``calls`` is the layers' pairs in causal calls'
    worth (the module's docstring). The real model is ``job.lm``."""

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

        # ``n_routed_experts`` and ``moe_intermediate_size`` are what
        # ``moe_scopes.held_loads`` and ``moe_flops.expert_flops_bytes``
        # read the held experts by.
        self.config = dict(
            config, n_routed_experts=int(config["moe_num_primary_experts"]),
            moe_intermediate_size=int(config["moe_ffn_hidden_size"]))
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
        # Nothing balances the router; which experts this chip holds is the
        # deployment's choice: placed by their load on the data set's first
        # windows (as ``families/sdar_moe_lm.py``), so that the chip's share
        # of the pairs, and with it the step's time, does not swing with
        # the seed.
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
        self._lse_err = None
        self.loads = collections.deque(maxlen=KEEP_LOADS)
        head_dim, kv_heads = (int(config["head_dim"]),
                              int(config["num_key_value_heads"]))
        windows = smallthinker_flops.layer_windows(config)
        causal = smallthinker_flops.window_pairs(self.seq)
        self.model = _FlashView(
            self.heads, head_dim,
            sum(smallthinker_flops.window_pairs(self.seq, w)
                for w in windows) / causal, dtype)
        itemsize = jnp.dtype(dtype).itemsize
        count = functools.partial(
            smallthinker_flops.flash_flops_bytes_per_step, b=self.batch,
            heads=self.heads, kv_heads=kv_heads, s=self.seq,
            head_dim=head_dim, itemsize=itemsize)
        self.flash_flops, self.flash_bytes = count(windows)
        self.window_flops, self.window_bytes = count(
            [w for w in windows if w is not None])

    @property
    def flops_per_step(self) -> float:
        """Required FLOPs a step, the experts' from what the run's steps
        routed to the held ones (their mean, summed over the layers; the
        expectation before any)."""
        pairs = None
        if self.loads:
            held = int(self.config["moe_num_primary_experts"])
            first = int(self.config["expert_parallel"]["chip"]) * held
            pairs = float(np.mean([np.asarray(x)[:, first:first + held].sum()
                                   for x in self.loads]))
        return smallthinker_flops.step_flops(self.config, self.batch,
                                             self.seq, pairs)

    def reference_of(self, params, tok, tgt, leave_out=(),
                     matrix_dtype=None):
        """``(loss, gradient leaves on the host)`` of the plain float32
        reference on ``params`` and the windows ``tok`` / ``tgt``, a window
        at a time; ``leave_out`` / ``matrix_dtype`` as the reference's."""
        import jax

        ref = spec.load_module("reference", "smallthinker_moe_lm")
        arch = dict(self.lm.arch._asdict(), heads=self.heads)
        fn = jax.jit(jax.value_and_grad(functools.partial(
            ref.loss, arch=arch, token_block=1024, leave_out=leave_out,
            matrix_dtype=matrix_dtype)))
        pos = np.asarray(self.pos[:1])
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
        return loss, [g / len(tok) for g in grads]

    def window_lse_error(self, params, tok, leave_out=()):
        """The mean absolute difference, over every head and the rows at
        and past the window, between the flash call's ``lse`` on the first
        windowed layer's q, k and v as the program computes them and the
        reference's on the same q and k (the module's docstring); ``None``
        where no layer has a window shorter than the sequence. The mean, not
        the largest: float32 sums in either order are 1e-4 apart on a few
        rows in 10^5 (my chip runs, PR 39), where one key more or less
        moves every row."""
        import jax

        from ddstore_tpu.ops.attention import flash_attention

        ref = spec.load_module("reference", "smallthinker_moe_lm")
        a = self.lm.arch
        layer = next((i for i, w in enumerate(a.sliding_window_layout)
                      if w), None)
        if layer is None or a.sliding_window >= self.seq:
            return None
        w, pos = a.sliding_window, np.asarray(self.pos[:1])
        lm = self.lm.clone(remat=False)

        @jax.jit
        def program(params, tok):
            sown = lm.apply(params, tok, pos, True,
                            mutable=["intermediates"])[1]["intermediates"]
            q, k, v = sown[f"block{layer}"]["window_qk"][0]
            # as ``transformer._attend`` hands them over: a head of whole
            # lanes as it lies, a narrower one (a dry run's) head-major
            lanes = q.shape[-1] % 128 == 0
            heads = (q, k, v) if lanes else (
                t.transpose(0, 2, 1, 3) for t in (q, k, v))
            return q, k, flash_attention(
                *heads, causal=True, window=w,
                layout="bshd" if lanes else "bhsd")[1]

        total = 0.0
        for i in range(len(tok)):
            q, k, lse = program(params, tok[i:i + 1])
            want = jax.jit(functools.partial(
                ref.window_lse, window=w, leave_out=leave_out))(
                    q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3))
            total += float(np.abs(
                np.asarray(lse, np.float64)[..., w:]
                - np.asarray(want, np.float64)[..., w:]).mean())
        return total / len(tok)

    def reference_loss(self, host_batch) -> float:
        """The plain float32 loss on the current parameters and this batch,
        and its gradient, kept on the host for the first ``step``, and the
        window's statistics against the reference's. Call before the first
        ``step``: the step donates the state. Adam's two moments, zeros
        until the first step, are let go for the while and made again after
        (``tx.init``, where they lay), as ``families/sdar_moe_lm.py``
        does."""
        import jax

        from ddstore_tpu.models.transformer import TrainState
        from ddstore_tpu.parallel.tp import shardings_of

        tok, tgt = (np.asarray(a) for a in host_batch)
        state, self.state = self.state, None
        if int(state.opt_state[0].count) != 0:
            raise RuntimeError("the reference is taken before the first step")
        params, step, moments = (state.params, state.step,
                                 shardings_of(state.opt_state))
        del state
        loss, grads = self.reference_of(params, tok, tgt)
        self._lse_err = self.window_lse_error(params, tok)
        self.state = TrainState(params, jax.jit(
            self._tx.init, out_shardings=moments)(params), step)
        self._reference = (loss, grads)
        return loss

    def _held_to_reference(self, loss) -> float:
        """``lfm2_moe_lm``'s fold of the loss's and the gradient's
        differences, and the window's statistics' as a third, each as a
        share of its limit."""
        want = self._reference[0]
        folded = super()._held_to_reference(loss)
        if self._lse_err is None:
            return folded
        loss_rtol, lse_atol = (float(self.config[k]) for k in (
            "loss_rtol", "window_lse_atol"))
        print(f"first step against the reference, the window's statistics: "
              f"mean absolute difference of the flash call's lse from the "
              f"reference's on the rows at and past the window "
              f"{self._lse_err:.3e} (allowed {lse_atol})", flush=True)
        return max(folded, want * (
            1.0 + self._lse_err * loss_rtol / lse_atol))


def build(config, traffic, mesh, seed, dry_run=False):
    return Job(config, traffic, mesh, seed, dry_run)
