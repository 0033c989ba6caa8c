"""Interop: the store's TCP data plane and XLA's collective stack coexist
in one process under load — the TPU-native analogue of the reference's
MPI-RMA + NCCL interleaving test (test.py:142-154, which alternates
one-sided gets with torch dist.all_reduce every batch)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import os, sys
sys.path.insert(0, {repo!r})
import numpy as np
import jax
import jax.numpy as jnp
from ddstore_tpu import DDStore, FileGroup
from ddstore_tpu.parallel import make_mesh

rank = int(os.environ["DDSTORE_RANK"])
world = 2
g = FileGroup(os.environ["DDSTORE_RDV_DIR"], rank, world)
store = DDStore(g, backend="tcp")
rows, dim = 64, 8
store.add("v", np.full((rows, dim), rank + 1, np.float64))

mesh = make_mesh({{"dp": 8}})
psum = jax.jit(jax.shard_map(
    lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
    in_specs=jax.P("dp"), out_specs=jax.P()))

rng = np.random.default_rng(rank)
for it in range(25):
    # one-sided remote reads (TCP data plane)...
    idx = rng.integers(0, world * rows, size=16)
    got = store.get_batch("v", idx)
    owners = idx // rows + 1
    assert (got == owners[:, None]).all(), it
    # ...interleaved with an XLA collective on the same process
    x = jnp.full((8, 4), float(rank + it), jnp.float32)
    r = psum(x)
    assert float(r[0, 0]) == 8.0 * (rank + it), it
    if it % 5 == 0:
        store.barrier()
store.barrier()
store.close()
print(f"rank {{rank}} INTEROP PASS", flush=True)
"""


def test_store_and_xla_collectives_interleave(tmp_path):
    env = dict(os.environ, DDSTORE_RDV_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8")
               .strip())
    script = _SCRIPT.format(repo=REPO)
    procs = [subprocess.Popen([sys.executable, "-c", script],
                              env=dict(env, DDSTORE_RANK=str(r)),
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
             for r in (0, 1)]
    outs = [p.communicate(timeout=300)[0] for p in procs]
    codes = [p.returncode for p in procs]
    assert codes == [0, 0], outs
    for r, out in enumerate(outs):
        assert f"rank {r} INTEROP PASS" in out, out
