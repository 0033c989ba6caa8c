"""End-to-end DP training of a message-passing GNN on store-held graphs.

This is the workload class DDStore was built for — GNN training on
atomistic datasets too large for one node's RAM (reference README.md:
200-212) — which its repo never actually demonstrates (its only example is
an MNIST VAE). Here: each process holds a shard of variable-size molecular
graphs in the store as ragged variables, any process fetches any graph
one-sidedly, batches are packed into fixed node/edge budgets (static
shapes → one XLA compilation), and the train step runs data-parallel over
the device mesh.

Run single-process (8 virtual devices):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/gnn_molecules.py --epochs 2

Run 4 host processes on localhost (store goes over TCP):
    for r in 0 1 2 3; do DDSTORE_RANK=$r DDSTORE_WORLD=4 \
        DDSTORE_RDV_DIR=/tmp/gnn_rdv JAX_PLATFORMS=cpu \
        python examples/gnn_molecules.py --epochs 1 & done; wait

Trains on real QM9 xyz files when ``--data-dir`` points at a directory of
``.xyz``/``.xyz.gz`` molecule files (each rank loads the directory and
takes its contiguous shard); otherwise uses QM9-shaped synthetic molecules
(no network access here).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--graphs", type=int, default=2048,
                   help="graphs per process shard")
    p.add_argument("--graphs-per-slot", type=int, default=8)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--width", type=int, default=None,
                   help="replica-group width (ranks per store group)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--data-dir", type=str, default=None,
                   help="directory of QM9 .xyz/.xyz.gz files; omit for "
                        "synthetic molecules")
    p.add_argument("--target-index", type=int, default=1,
                   help="comment-line property used as regression target "
                        "(real QM9 comment lines are 'gdb <id> <props...>'"
                        " — index 0 is the molecule serial number, so the "
                        "default 1 is the first physical property, A)")
    args = p.parse_args()

    import jax
    import numpy as np

    from ddstore_tpu import DDStore, auto_group
    from ddstore_tpu.data import (DeviceLoader, DistributedSampler,
                                  GraphShardedDataset, synthetic_graphs)
    from ddstore_tpu.models import gnn
    from ddstore_tpu.parallel import make_mesh
    from ddstore_tpu.utils import enable_compile_cache

    enable_compile_cache()
    group = auto_group()
    store = DDStore(group, width=args.width)
    if args.data_dir is not None:
        from ddstore_tpu.data import load_qm9_dir, nsplit
        all_graphs = load_qm9_dir(args.data_dir,
                                  target_index=args.target_index,
                                  limit=args.graphs * store.world
                                  if args.graphs else None)
        counts = nsplit(len(all_graphs), store.world)
        begin = int(sum(counts[: store.rank]))
        graphs = all_graphs[begin: begin + counts[store.rank]]
    else:
        graphs = synthetic_graphs(
            np.random.default_rng(args.seed + store.rank), args.graphs)
    ds = GraphShardedDataset(store, graphs,
                             graphs_per_slot=args.graphs_per_slot)

    n_local = len(jax.local_devices())
    mesh = make_mesh({"dp": n_local}, jax.local_devices()) \
        if jax.process_count() == 1 else make_mesh({"dp": len(jax.devices())})
    # one packed slot per addressable device
    per_proc_batch = n_local * args.graphs_per_slot

    sampler = DistributedSampler(len(ds), store.world_group.size,
                                 store.world_group.rank, seed=args.seed)
    model = state = tx = step = None
    for epoch in range(args.epochs):
        sampler.set_epoch(epoch)
        loader = DeviceLoader(ds, sampler, batch_size=per_proc_batch,
                              mesh=mesh)
        t0 = time.perf_counter()
        total, nb = 0.0, 0
        for step_i, gb in enumerate(loader):
            if args.steps is not None and step_i >= args.steps:
                break
            if model is None:
                host_gb = jax.tree.map(np.asarray, gb)
                model, state, tx = gnn.create_train_state(
                    jax.random.key(args.seed), host_gb, lr=args.lr,
                    mesh=mesh)
                step = gnn.make_train_step(model, tx, mesh=mesh)
            state, loss = step(state, gb)
            total += float(loss)
            nb += 1
        dt = time.perf_counter() - t0
        m = loader.metrics.summary()
        if store.rank == 0:
            gps = nb * per_proc_batch * max(1, jax.process_count()) / dt
            print(f"epoch {epoch}: loss={total / max(1, nb):.4f} "
                  f"graphs/s={gps:.0f} "
                  f"loader_wait_share={m['loader_wait_share']:.4f} "
                  f"fetch_p50={m['host_fetch']['p50_s'] * 1e3:.2f}ms",
                  flush=True)
    store.close()


if __name__ == "__main__":
    main()
