"""Are the train steps of the cells another commit has the same programs
here? A script, run by hand from the repo's root, no chip needed:

    python benchmarks/tests/lowered_steps.py [--against REV]

For every one-chip cell of ``REV``'s ``BENCHMARK.json`` (``HEAD``, the parent
of a working tree, by default) it lowers ``make_train_step`` at the cell's
batch and window for a described v5e, once with ``REV``'s ``ddstore_tpu``
(a ``git archive`` of it under this process's ``TMPDIR``) and once with this
tree's, and compares the two by the jaxpr and by the StableHLO module. A
source line is no part of a program: file paths and line numbers go out of
the jaxpr, and a Mosaic call's ``backend_config`` (the kernel's serialised
body, which carries them too) out of the module, so a kernel whose body
changed shows in the jaxpr, where ``pallas_call`` prints it. Exit code 0:
every cell alike, and nothing is left behind; 1: the cells that differ are
named, and the two jaxprs of each are left beside the archive for ``diff``. A PR that changes a cell's
program on purpose reads 1 there, and says so.

``--print TREE`` is the half the script runs in a subprocess a tree: one
line a cell with both hashes."""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def normalised(module: str, jaxpr: str) -> tuple:
    """The two texts without what a moved line or another process
    changes."""
    module = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', module)
    jaxpr = re.sub(r" at [^ ]*ddstore_tpu/", " at ddstore_tpu/", jaxpr)
    jaxpr = re.sub(r" at 0x[0-9a-f]+", "", jaxpr)    # a closure's address
    return module, re.sub(r"(\.py):\d+", r"\1", jaxpr)


def cells_of(tree: str) -> list:
    """``(cell, configuration's file, batch, window)`` of ``tree``'s
    one-chip cells."""
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    out = []
    for cell in bench["workloads"]:
        if cell["chips"] != 1:
            continue
        with open(os.path.join(tree, "benchmarks", "traffic",
                               cell["traffic"] + ".json")) as f:
            traffic = json.load(f)
        out.append((cell["name"], files[cell["config"]],
                    int(traffic["batch"]), int(traffic["seq"])))
    return out


def print_steps(tree: str, cells: list, keep: str) -> None:
    """One line a cell: the hashes of ``tree``'s lowered step."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import ddstore_tpu
    from ddstore_tpu.models import transformer

    at = os.path.realpath(os.path.dirname(ddstore_tpu.__file__))
    if not at.startswith(os.path.realpath(tree) + os.sep):
        raise SystemExit(f"ddstore_tpu was imported from {at}, not {tree}")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    # the path the models take on the chip
    jax.default_backend = lambda: "tpu"
    on = lambda tree_: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        tree_)
    for name, file, batch, seq in cells:
        with open(os.path.join(tree, file)) as f:
            config = json.load(f)
        model = transformer.lm_from_description(
            config, compute_dtype=jnp.dtype(config.get("compute_dtype",
                                                       "bfloat16")))
        lr = float(config.get("lr", 3e-4))
        if config.get("lr_warmup_steps"):
            lr = optax.linear_schedule(0.0, lr, int(config["lr_warmup_steps"]))
        state = on(jax.eval_shape(
            lambda k: transformer.create_train_state(k, model, lr=lr)[0],
            jax.random.key(0)))
        step = transformer.make_train_step(model, optax.adam(lr))
        tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=chip)
        module, jaxpr = normalised(
            step.lower(state, tok, tok, tok).as_text(),
            str(jax.make_jaxpr(step.__wrapped__)(state, tok, tok, tok)))
        with open(os.path.join(keep, name + ".jaxpr"), "w") as f:
            f.write(jaxpr)
        print("lowered", name, *(hashlib.sha256(t.encode()).hexdigest()[:16]
                                 for t in (module, jaxpr)), flush=True)


def _lowered(tree: str, cells: list, keep: str) -> dict:
    os.makedirs(keep, exist_ok=True)
    env = dict(os.environ, PYTHONPATH=tree, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--print", tree,
         "--keep", keep, "--cells", json.dumps(cells)],
        env=env, cwd=tree, check=True, capture_output=True, text=True).stdout
    return {line.split()[1]: line.split()[2:]
            for line in out.splitlines() if line.startswith("lowered ")}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--against", default="HEAD", metavar="REV")
    ap.add_argument("--print", dest="tree")
    ap.add_argument("--keep")
    ap.add_argument("--cells")
    args = ap.parse_args()
    if args.tree:
        print_steps(args.tree, json.loads(args.cells), args.keep)
        return 0
    work = tempfile.mkdtemp(prefix="lowered_steps.")
    other = os.path.join(work, "against")
    os.makedirs(other)
    archive = subprocess.run(["git", "archive", args.against], cwd=ROOT,
                             check=True, capture_output=True).stdout
    subprocess.run(["tar", "-x", "-C", other], input=archive, check=True)
    cells = cells_of(other)
    theirs = _lowered(other, cells, os.path.join(work, "jaxpr.against"))
    mine = _lowered(ROOT, cells, os.path.join(work, "jaxpr.here"))
    differ = [name for name, *_ in cells if theirs[name] != mine[name]]
    for name, *_ in cells:
        print(f"{name}: module {mine[name][0]} jaxpr {mine[name][1]}: "
              f"{'DIFFERS from' if name in differ else 'as'} "
              f"{args.against}", flush=True)
    print(f"{len(cells) - len(differ)} of {len(cells)} cells' steps lower "
          f"to the program {args.against} lowers")
    if not differ:
        shutil.rmtree(work)
        return 0
    print(f"the archive and both sides' jaxprs are under {work}")
    return 1


if __name__ == "__main__":
    sys.exit(main())
