"""ddstore_tpu — a TPU-pod-native distributed in-memory sample store.

Built from scratch with the capabilities of ORNL/DDStore (reference at
/root/reference; structural analysis in SURVEY.md): every process (TPU-VM
host) holds one shard of the dataset in host RAM, a global row-index space
spans all shards, and any process reads any sample with a one-sided remote
fetch — no MPI, no GPU in the path.

Layers (bottom-up):

* ``native/`` — C++17 store core + transports (in-process, TCP/DCN
  one-sided read service); the counterpart of the reference's
  ddstore.hpp/common.cxx, redesigned (pluggable transport, 64-bit sizes,
  binary-search owner lookup, pipelined batched reads).
* ``binding.py`` — ctypes boundary, zero-copy numpy buffers.
* ``store.py`` — the ``DDStore`` API (add/get/get_batch/init/update/
  epochs/replica width groups).
* ``data/`` — sample-major dataset adapters, device-feeding loaders.
* ``parallel/`` — JAX mesh/sharding utilities and collectives.
* ``models/`` — flax model families with sharded train steps.
* ``utils/`` — metrics and logging.
"""

from .binding import (DDStoreError, NativeStore, fault_configure,
                      owner_of)
from .elastic import recover as elastic_recover
from .elastic import rejoin as elastic_rejoin
from .rendezvous import (FileGroup, JaxGroup, PodConfig, ProcessGroup,
                         SingleGroup, ThreadGroup, auto_group,
                         detect_pod_env, parse_nodelist, pod_bootstrap)
from .store import DDStore

__version__ = "0.1.0"

__all__ = [
    "DDStore",
    "DDStoreError",
    "NativeStore",
    "fault_configure",
    "owner_of",
    "ProcessGroup",
    "SingleGroup",
    "ThreadGroup",
    "FileGroup",
    "JaxGroup",
    "auto_group",
    "PodConfig",
    "detect_pod_env",
    "parse_nodelist",
    "pod_bootstrap",
    "elastic_recover",
    "elastic_rejoin",
    "__version__",
]
