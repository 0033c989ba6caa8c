"""Family ``mla_moe_lm``: a latent-attention, shared + routed expert, MTP
decoder (``ddstore_tpu.models.transformer`` with ``arch``) as one
expert-parallel chip's share, fed token windows from the store, built
through the calls ``examples/lm_longcontext.py`` makes: the configuration's
keys are the description ``lm_from_description`` takes."""

from __future__ import annotations

import collections
import functools
import time

import numpy as np

from ddbench import flops, moe_flops, rows, spec

UNIT = "tokens"
# Steps whose load vectors are kept for the readers, which pick the traced
# ones: every step of a run (a few KB each).
KEEP_LOADS = 4096


def shard(seed, rank, traffic, config):
    """This rank's windows and their targets (two ``pre_sharded`` variables)."""
    n = int(traffic["rows_per_rank"])
    return rows.token_shard(seed, rank * n, n, int(traffic["seq"]),
                            int(config["vocab_size"]))


def reference_rows(seed, ids, traffic, config):
    ref = spec.load_module("reference", "rows")
    return ref.token_rows(seed, ids, int(traffic["seq"]),
                          int(config["vocab_size"]))


def open_dataset(store, arrays):
    from ddstore_tpu.data import ShardedDataset

    return ShardedDataset(store, arrays[0], arrays[1], name="windows",
                          pre_sharded=True)


class _FlashView:
    """What ``ddbench/scopes.py:flash_kernel_work`` reads of ``job.model``
    (``dim // job.heads`` as the head width, ``layers`` as the flash calls
    a step, ``compute_dtype``), for a model whose heads are not ``dim /
    heads`` wide and which calls the kernels once more than it has layers
    (the MTP block). The real model is ``job.lm``."""

    def __init__(self, heads, head_dim, calls, compute_dtype):
        self.dim = heads * head_dim
        self.layers = calls
        self.compute_dtype = compute_dtype


class Job:
    """State, step and reference of one cell; ``step`` is one iteration of
    the example's loop body."""

    def __init__(self, config, traffic, mesh, seed, dry_run):
        import jax
        import jax.numpy as jnp

        from ddstore_tpu.models import transformer

        self.config = config
        self.batch = int(traffic["batch"])
        self.seq = int(traffic["seq"])
        self.units_per_row = self.seq
        self.heads = int(config["num_attention_heads"])
        self.loader_kwargs = {"spec": jax.P("dp", None)}
        # float32 on the CPU, as the dense family's dry run.
        dtype = jnp.float32 if dry_run else jnp.dtype(config["compute_dtype"])
        self.lm = transformer.lm_from_description(
            config, compute_dtype=dtype, mesh=mesh)
        import optax

        # The window is a job's first steps: the rate is still warming up.
        lr = optax.linear_schedule(0.0, float(config["lr"]),
                                   int(config["lr_warmup_steps"]))
        self.state, tx = transformer.create_train_state(
            jax.random.key(seed & 0x7FFFFFFF), self.lm, lr=lr, mesh=mesh)
        self._step = transformer.make_train_step(self.lm, tx, mesh=mesh,
                                                 state=self.state)
        self.pos = jnp.tile(jnp.arange(self.seq, dtype=jnp.int32),
                            (self.batch, 1))
        # The correction biases to where the noaux_tc rule would have them
        # for these seeded weights, on the data set's first windows (a
        # model in training is balanced; a seeded router is not).
        bal = dict(config["bias_balance"])
        n = int(bal.pop("batches"))
        tok, tgt = (a.reshape(n, self.batch, self.seq)
                    for a in rows.token_shard(
                        seed, 0, n * self.batch, self.seq, self.lm.vocab))
        self.state = transformer.balance_router_bias(
            self.lm, self.state, tok, tgt, self.pos, **bal)
        self._compiled = None
        self.loads = collections.deque(maxlen=KEEP_LOADS)
        self.flops_per_step = moe_flops.step_flops(config, self.batch,
                                                   self.seq)
        head_dim = int(config["qk_nope_head_dim"]) \
            + int(config["qk_rope_head_dim"])
        calls = int(config["num_hidden_layers"]) \
            + int(config["num_nextn_predict_layers"])
        self.model = _FlashView(self.heads, head_dim, calls, dtype)
        self.flash_flops, self.flash_bytes = flops.flash_flops_bytes_per_step(
            calls, self.batch, self.heads, self.seq, head_dim,
            jnp.dtype(dtype).itemsize)

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
        return loss

    def reference_loss(self, host_batch) -> float:
        """The plain float32 loss (main + MTP) on the current parameters and
        this batch, whole batch, in blocks of tokens small enough to sit
        beside the state. Call before the first ``step``: the step donates
        the state."""
        import jax

        ref = spec.load_module("reference", "mla_moe_lm")
        tok, tgt = (np.asarray(a) for a in host_batch)
        arch = dict(self.lm.arch._asdict(), heads=self.heads)
        fn = jax.jit(functools.partial(ref.loss, arch=arch, token_block=1024))
        return float(fn(self.state.params, tok, tgt, np.asarray(self.pos)))


def build(config, traffic, mesh, seed, dry_run=False):
    return Job(config, traffic, mesh, seed, dry_run)
