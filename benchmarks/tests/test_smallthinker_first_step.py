"""What the ``smallthinker_moe_lm`` family holds its first step to
(``families/smallthinker_moe_lm.py``): a dry run of the cell on the CPU
prints ``correct: true``; the number the harness compares with the
reference's loss stays inside ``loss_rtol`` for the program itself, and
leaves it when the reference is computed with 8-bit matrices or with a
part of the mathematics broken (a window one key wider through the
window's statistics). Toy sizes, float32 program, on the CPU: the readings
that set the limits are the chip's (PERF.md section 6)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from ddbench import rows, spec

CELL = "smallthinker-21b-a3b-ep4.s16384.b1"


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
    assert "the window's statistics" in proc.stdout


def _first_step(monkeypatch, **control):
    """``(what the first step returned, the reference's loss, loss_rtol)``
    with the family's reference given ``control``'s arguments."""
    cell = spec.Cell(spec.load_benchmark(), CELL, dry_run=True)
    family = cell.family()
    if control:
        for name in ("reference_of", "window_lse_error"):
            orig = getattr(family.Job, name)
            kw = control if name == "reference_of" else {
                "leave_out": control.get("leave_out", ())}
            monkeypatch.setattr(
                family.Job, name,
                lambda self, *a, _orig=orig, _kw=kw: _orig(self, *a, **_kw))
    mesh = Mesh(jax.devices()[:1], ("dp",))
    job = family.build(cell.config, cell.traffic, mesh, 2**31 + 7, True)
    tok, tgt = rows.token_shard(2**31 + 7, 0, job.batch, job.seq,
                                job.lm.vocab)
    want = job.reference_loss((tok, tgt))
    got = job.step((jnp.asarray(tok), jnp.asarray(tgt)))
    later = job.step((jnp.asarray(tok), jnp.asarray(tgt)))
    assert isinstance(later, jax.Array)     # the step's loss again
    return float(got), want, float(cell.config["loss_rtol"])


def test_the_program_is_inside_all_three_limits(monkeypatch):
    got, want, rtol = _first_step(monkeypatch)
    assert abs(got - want) / want < 0.01 * rtol


@pytest.mark.parametrize("control", [
    {"matrix_dtype": jnp.float8_e4m3fn}, {"leave_out": ("wide_window",)},
    {"leave_out": ("rotary_full",)}, {"leave_out": ("router_ln2",)},
    {"leave_out": ("silu",)}],
    ids=["e4m3", "wide-window", "rotary-full", "router-ln2", "silu"])
def test_a_control_reference_is_outside(monkeypatch, control):
    got, want, rtol = _first_step(monkeypatch, **control)
    assert abs(got - want) / want > rtol
