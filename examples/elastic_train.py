"""Elastic training demo: a run that SURVIVES a killed rank.

The reference's failure story is fatal — a transport error prints to
stderr and the whole MPI job dies (/root/reference/src/common.cxx:100-111).
This example shows the ddstore_tpu alternative end to end:

* 4 worker processes build a TCP store, checkpoint their shards
  (``save_shard``) and train a store-fed VAE. Every worker pins JAX to
  the CPU, by design and on any machine: four ranks train side by side
  and one of them is SIGKILLed, while a chip belongs to one process at a
  time — the point here is the store fabric, not the chip.
* The supervisor (this script) SIGKILLs one worker mid-training.
* Survivors hit a bounded-timeout ``DDStoreError``, call
  ``elastic_recover`` and block at the recovery rendezvous.
* The supervisor relaunches the dead rank with ``--rejoin``; it calls
  ``elastic_rejoin``, restores its shard from the checkpoint, and the
  whole world resumes training — same data, no global restart.

Run (single machine, all local processes)::

    python examples/elastic_train.py --steps 40 --kill-at 15

Worker internals: see ``ddstore_tpu/elastic.py``; the end-to-end
correctness test for this flow is ``tests/test_elastic.py``.
"""

import argparse
import os
import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

WORLD = 4
ROWS = 2048


def worker(args):
    import numpy as np

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")

    from ddstore_tpu import (DDStore, DDStoreError, FileGroup,
                             elastic_recover, elastic_rejoin)
    from ddstore_tpu.data import DistributedSampler
    from ddstore_tpu.models import vae
    from ddstore_tpu.utils import save_shard

    rank = args.rank
    if args.rejoin:
        store = elastic_rejoin(args.elastic_dir, rank, WORLD,
                               args.ckpt_dir, timeout=120)
        print(f"[r{rank}] rejoined from checkpoint", flush=True)
    else:
        g = FileGroup(args.rdv_dir, rank, WORLD)
        store = DDStore(g, backend="tcp")
        gen = np.random.default_rng(rank)
        shard = gen.random((ROWS, vae.IMAGE_DIM), np.float32)
        store.add("x", shard)
        save_shard(store, "x", args.ckpt_dir)
        store.barrier()

    model, state, tx = vae.create_train_state(jax.random.key(rank))
    step = vae.make_train_step(model, tx)
    sampler = DistributedSampler(store.total_rows("x"), WORLD, rank,
                                 seed=0)
    key = jax.random.key(100 + rank)
    it = iter(sampler)
    t = 0
    print(f"[r{rank}] TRAINING", flush=True)
    while t < args.steps:
        idx = np.fromiter(it, np.int64, count=64)
        try:
            batch = store.get_batch("x", idx)
        except DDStoreError as e:
            print(f"[r{rank}] peer death detected at step {t}: {e}; "
                  f"recovering...", flush=True)
            elastic_recover(store, args.elastic_dir, timeout=120)
            print(f"[r{rank}] recovered; resuming", flush=True)
            batch = store.get_batch("x", idx)
        key, sub = jax.random.split(key)
        state, loss = step(state, jax.numpy.asarray(batch), sub)
        t += 1
        if t % 10 == 0:
            print(f"[r{rank}] step {t}: loss/sample={float(loss):.2f}",
                  flush=True)
    store.barrier()
    store.close()
    print(f"[r{rank}] done", flush=True)


def supervise(args):
    base = args.workdir or f"/tmp/elastic_demo_{os.getpid()}"
    os.makedirs(base, exist_ok=True)
    dirs = {"--rdv-dir": f"{base}/rdv", "--elastic-dir": f"{base}/elastic",
            "--ckpt-dir": f"{base}/ckpt"}
    common = [sys.executable, os.path.abspath(__file__),
              "--steps", str(args.steps)]
    for k, v in dirs.items():
        common += [k, v]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               DDSTORE_READ_TIMEOUT_S="5", DDSTORE_CONNECT_TIMEOUT_S="3",
               DDSTORE_BARRIER_TIMEOUT_S="60")

    logs = {r: f"{base}/r{r}.log" for r in range(WORLD)}

    def launch(rank, rejoin=False):
        cmd = common + ["--rank", str(rank)] + (["--rejoin"] if rejoin
                                                else [])
        return subprocess.Popen(cmd, env=env,
                                stdout=open(logs[rank], "ab"),
                                stderr=subprocess.STDOUT)

    procs = {r: launch(r) for r in range(WORLD)}
    victim = args.victim
    # Kill only once the victim is demonstrably TRAINING (setup, compile,
    # and the collective adds must be behind it — a death mid-setup is a
    # launch failure, not the elastic scenario).
    deadline = time.time() + 300
    while True:
        try:
            if b"TRAINING" in open(logs[victim], "rb").read():
                break
        except OSError:
            pass
        if time.time() > deadline:
            for p in procs.values():
                p.kill()
            print("[supervisor] victim never reached training; logs in "
                  f"{base}", flush=True)
            return 1
        time.sleep(0.2)
    time.sleep(args.kill_after)
    print(f"[supervisor] SIGKILL rank {victim}", flush=True)
    procs[victim].send_signal(signal.SIGKILL)
    procs[victim].wait()
    time.sleep(1.0)
    print(f"[supervisor] relaunching rank {victim} (--rejoin)",
          flush=True)
    procs[victim] = launch(victim, rejoin=True)
    rc = 0
    for r, p in procs.items():
        rc |= p.wait()
    for r in range(WORLD):
        with open(logs[r]) as f:
            for line in f.read().splitlines()[-4:]:
                print(f"  {line}")
    print(f"[supervisor] all workers exited; "
          f"status={'OK' if rc == 0 else 'FAIL'} (logs in {base})",
          flush=True)
    return rc


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--kill-after", type=float, default=8.0,
                    help="seconds before the supervisor kills the victim")
    ap.add_argument("--kill-at", type=float, dest="kill_after",
                    help=argparse.SUPPRESS)
    ap.add_argument("--victim", type=int, default=2)
    ap.add_argument("--workdir", default=None)
    # worker-mode flags (internal)
    ap.add_argument("--rank", type=int, default=None)
    ap.add_argument("--rejoin", action="store_true")
    ap.add_argument("--rdv-dir", dest="rdv_dir")
    ap.add_argument("--elastic-dir", dest="elastic_dir")
    ap.add_argument("--ckpt-dir", dest="ckpt_dir")
    args = ap.parse_args()
    if args.rank is None:
        sys.exit(supervise(args))
    worker(args)


if __name__ == "__main__":
    main()
