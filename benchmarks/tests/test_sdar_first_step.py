"""What the ``sdar_moe_lm`` family holds its first step to
(``families/sdar_moe_lm.py``): a dry run of the cell on the CPU prints
``correct: true``; the number the harness compares with the reference's loss
stays inside ``loss_rtol`` for the program itself, and leaves it when the
reference is computed with 8-bit matrices or with a part of the mathematics
broken. Toy sizes, float32 program, on the CPU: the readings that set the
limits are the chip's (PERF.md section 6)."""

import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from ddbench import rows, spec

CELL = "sdar-30b-a3b-ep8.s8192.b1"


def test_a_dry_run_of_the_cell_is_correct():
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "2",
         "--trace", "1", "--dry-run"],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["dry_run"] is True and result["metrics"] == {}
    assert "first step against the reference" in proc.stdout


def _first_step(monkeypatch, **control):
    """``(what the first step returned, the reference's loss, loss_rtol)``
    with the reference's ``loss`` given ``control``'s arguments."""
    cell = spec.Cell(spec.load_benchmark(), CELL, dry_run=True)
    family = cell.family()
    reference = spec.load_module("reference", "sdar_moe_lm")
    if control:
        monkeypatch.setattr(reference, "loss", functools.partial(
            reference.loss, **control))
    mesh = Mesh(jax.devices()[:1], ("dp",))
    job = family.build(cell.config, cell.traffic, mesh, 2**31 + 7, True)
    tok, tgt = rows.token_shard(2**31 + 7, 0, job.batch, job.seq,
                                job.lm.vocab)
    assert int(tok.max()) < job.lm.arch.mask_token
    want = job.reference_loss((tok, tgt))
    got = job.step((jnp.asarray(tok), jnp.asarray(tgt)))
    later = job.step((jnp.asarray(tok), jnp.asarray(tgt)))
    assert isinstance(later, jax.Array)     # the step's loss again
    return float(got), want, float(cell.config["loss_rtol"])


def test_the_program_is_inside_both_limits(monkeypatch):
    got, want, rtol = _first_step(monkeypatch)
    assert abs(got - want) / want < 0.01 * rtol


@pytest.mark.parametrize("control", [
    {"matrix_dtype": jnp.float8_e4m3fn}, {"leave_out": ("own_clean_block",)},
    {"leave_out": ("block_causal",)}, {"leave_out": ("weight",)},
    {"leave_out": ("softmax",)}, {"leave_out": ("rotary",)}],
    ids=["e4m3", "own-clean-block", "token-causal", "no-weight", "sigmoid",
         "no-rotary"])
def test_a_control_reference_is_outside(monkeypatch, control):
    got, want, rtol = _first_step(monkeypatch, **control)
    assert abs(got - want) / want > rtol
