"""End-to-end DP training: store-fed VAE under jit on a device mesh.

Parity with the reference's examples/vae/vae-ddp.py (torch DDP + MNIST +
DistributedSampler + per-batch fences) rebuilt TPU-first: the dataset lives
in the distributed store (one shard per process), a DistributedSampler
partitions the global index space, the DeviceLoader prefetches coalesced
one-sided reads and stages sharded device batches, and the train step runs
under jit with the batch sharded over ``dp`` — XLA's allreduce replaces
NCCL.

Run single-process (8 virtual devices):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/vae_mnist.py --epochs 2

Run 4 host processes on localhost (store goes over TCP):
    for r in 0 1 2 3; do DDSTORE_RANK=$r DDSTORE_WORLD=4 \
        DDSTORE_RDV_DIR=/tmp/vae_rdv JAX_PLATFORMS=cpu \
        python examples/vae_mnist.py --epochs 1 & done; wait

Trains on real MNIST idx files when ``--data-dir`` points at the canonical
``train-images-idx3-ubyte``/``train-labels-idx1-ubyte`` pair (plain or
.gz — parity with the reference's torchvision MNIST pipeline,
vae-ddp.py:202-216); otherwise falls back to a synthetic MNIST-shaped
dataset (this environment has no network access).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--batch-size", type=int, default=128,
                   help="global batch size")
    p.add_argument("--samples", type=int, default=None,
                   help="dataset size cap (default: 4096 synthetic "
                        "samples; the full file with --data-dir)")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--width", type=int, default=None,
                   help="replica-group width (ranks per store group)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--steps", type=int, default=None,
                   help="cap steps per epoch (smoke runs)")
    p.add_argument("--data-dir", type=str, default=None,
                   help="directory with MNIST idx files (plain or .gz); "
                        "omit for synthetic data")
    p.add_argument("--readahead-windows", type=int, default=0,
                   help="epoch-window readahead ring depth (0 = off): "
                        "whole-epoch read planning, bulk window fetches "
                        "through the native async engine, window N+1 in "
                        "flight while N is consumed")
    p.add_argument("--readahead-window-batches", type=int, default=8,
                   help="window size W in batches for --readahead-windows")
    p.add_argument("--device-collective", action="store_true",
                   help="stage batches with the ICI device-collective "
                        "fetch (one local read per host + on-device "
                        "all_to_all) instead of the host DCN path; "
                        "falls back automatically when no mesh supports "
                        "it")
    args = p.parse_args()

    import jax

    from ddstore_tpu import DDStore, auto_group
    from ddstore_tpu.data import (DeviceLoader, DistributedSampler,
                                  ShardedDataset, synthetic_mnist)
    from ddstore_tpu.models import vae
    from ddstore_tpu.parallel import make_mesh
    from ddstore_tpu.utils import enable_compile_cache

    enable_compile_cache()
    group = auto_group()
    store = DDStore(group, width=args.width)
    if args.data_dir is not None:
        from ddstore_tpu.data import load_mnist
        # Raw uint8 in the store: 4x less read volume AND 4x less
        # host->device staging; the train step dequantizes on device
        # with ToTensor-identical numerics.
        data, _labels = load_mnist(args.data_dir, split="train",
                                   normalize=False)
        if args.samples is not None and args.samples < len(data):
            print(f"capping dataset: {args.samples} of {len(data)} samples",
                  flush=True)
            data, _labels = data[: args.samples], _labels[: args.samples]
    else:
        data, _labels = synthetic_mnist(args.samples or 4096, args.seed)
    # The VAE objective never reads labels; registering only the data
    # variable halves the hot-path read volume.
    ds = ShardedDataset(store, data)

    n_local = len(jax.local_devices())
    mesh = make_mesh({"dp": n_local}, jax.local_devices()) \
        if jax.process_count() == 1 else make_mesh({"dp": len(jax.devices())})
    per_proc_batch = args.batch_size // max(1, jax.process_count())

    model, state, tx = vae.create_train_state(
        jax.random.key(args.seed), lr=args.lr, mesh=mesh)
    train_step = vae.make_train_step(model, tx, mesh=mesh)

    # Partition indices over the GLOBAL world, not the replica group: with
    # --width, each replica group stores a full copy, but different groups
    # must still draw disjoint samples.
    sampler = DistributedSampler(len(ds), store.world_group.size,
                                 store.world_group.rank, seed=args.seed)
    key = jax.random.key(args.seed + 1)
    for epoch in range(args.epochs):
        sampler.set_epoch(epoch)
        loader = DeviceLoader(
            ds, sampler, batch_size=per_proc_batch, mesh=mesh,
            device_collective=args.device_collective,
            readahead_windows=args.readahead_windows,
            readahead_window_batches=args.readahead_window_batches)
        if args.device_collective \
                and loader.collective_fallback_reason is not None \
                and store.rank == 0 and epoch == 0:
            print(f"device-collective fallback: "
                  f"{loader.collective_fallback_reason}", flush=True)
        if args.readahead_windows \
                and loader.readahead_fallback_reason is not None \
                and store.rank == 0 and epoch == 0:
            print(f"readahead fallback: "
                  f"{loader.readahead_fallback_reason}", flush=True)
        t0 = time.perf_counter()
        total, nb = 0.0, 0
        for step_i, xb in enumerate(loader):
            if args.steps is not None and step_i >= args.steps:
                break
            key, sub = jax.random.split(key)
            state, loss = train_step(state, xb, sub)
            total += float(loss)
            nb += 1
        dt = time.perf_counter() - t0
        m = loader.metrics.summary()
        if store.rank == 0:
            sps = nb * per_proc_batch * max(1, jax.process_count()) / dt
            print(f"epoch {epoch}: loss/sample="
                  f"{total / max(1, nb) / per_proc_batch:.3f} "
                  f"samples/s={sps:.0f} "
                  f"loader_wait_share={m['loader_wait_share']:.4f} "
                  f"fetch_p50={m['host_fetch']['p50_s'] * 1e3:.2f}ms"
                  + (" bytes_moved=" + str(m["bytes_moved"])
                     if "bytes_moved" in m else ""),
                  flush=True)
    store.close()


if __name__ == "__main__":
    main()
