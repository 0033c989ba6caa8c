"""Family ``dense_lm``: the repo's dense decoder block
(``ddstore_tpu.models.transformer``) fed token windows from the store, built
through the calls ``examples/lm_longcontext.py`` makes."""

from __future__ import annotations

import functools
import time

import numpy as np

from ddbench import flops, rows, spec

UNIT = "tokens"


def shard(seed, rank, traffic, config):
    """This rank's windows and their targets (two ``pre_sharded`` variables)."""
    n = int(traffic["rows_per_rank"])
    return rows.token_shard(seed, rank * n, n, int(traffic["seq"]),
                            int(config["vocab"]))


def reference_rows(seed, ids, traffic, config):
    ref = spec.load_module("reference", "rows")
    return ref.token_rows(seed, ids, int(traffic["seq"]),
                          int(config["vocab"]))


def open_dataset(store, arrays):
    from ddstore_tpu.data import ShardedDataset

    return ShardedDataset(store, arrays[0], arrays[1], name="windows",
                          pre_sharded=True)


class Job:
    """State, step and reference of one cell; ``step`` is one iteration of
    the example's loop body."""

    def __init__(self, config, traffic, mesh, seed, dry_run):
        import jax
        import jax.numpy as jnp

        from ddstore_tpu.models import transformer

        self.batch = int(traffic["batch"])
        self.seq = int(traffic["seq"])
        self.units_per_row = self.seq
        self.heads = int(config["heads"])
        sp = mesh.shape.get("sp", 1)
        self.loader_kwargs = {"spec": jax.P("dp", "sp" if sp > 1 else None)}
        # float32 on the CPU: XLA's CPU backend cannot promote the bf16
        # all-reduce an sp mesh produces (examples/lm_longcontext.py).
        dtype = jnp.float32 if dry_run else jnp.dtype(config["compute_dtype"])
        self.model = transformer.TransformerLM(
            vocab=int(config["vocab"]), dim=int(config["dim"]),
            heads=self.heads, layers=int(config["layers"]),
            mlp_ratio=int(config["mlp_ratio"]), compute_dtype=dtype,
            mesh=mesh)
        self.state, tx = transformer.create_train_state(
            jax.random.key(seed & 0x7FFFFFFF), self.model,
            lr=float(config["lr"]), mesh=mesh)
        self._step = transformer.make_train_step(self.model, tx, mesh=mesh,
                                                 state=self.state)
        self.pos = jnp.tile(jnp.arange(self.seq, dtype=jnp.int32),
                            (self.batch, 1))
        self._compiled = None
        self.flops_per_step = flops.lm_flops_per_step(
            self.model.vocab, self.model.dim, self.model.layers, self.batch,
            self.seq)
        self.flash_flops, self.flash_bytes = flops.flash_flops_bytes_per_step(
            self.model.layers, self.batch, self.heads, self.seq,
            self.model.dim // self.heads, jnp.dtype(dtype).itemsize)

    def step(self, batch):
        tok, tgt = batch
        if self._compiled is None:
            # Lowered and compiled ahead of the first call (as chip_smoke.py
            # does), so compile seconds are read apart from run time.
            t0 = time.perf_counter()
            self._compiled = self._step.lower(self.state, tok, tgt,
                                              self.pos).compile()
            self.compile_s = time.perf_counter() - t0
        self.state, loss = self._compiled(self.state, tok, tgt, self.pos)
        return loss

    def reference_loss(self, host_batch) -> float:
        """The plain float32 forward pass on the current parameters and this
        batch (whole batch, every position). Call before the first ``step``:
        the step donates the state."""
        import jax

        ref = spec.load_module("reference", "dense_lm")
        tok, tgt = (np.asarray(a) for a in host_batch)
        fn = jax.jit(functools.partial(ref.loss, heads=self.heads))
        return float(fn(self.state.params, tok, tgt, np.asarray(self.pos)))


def build(config, traffic, mesh, seed, dry_run=False):
    return Job(config, traffic, mesh, seed, dry_run)
