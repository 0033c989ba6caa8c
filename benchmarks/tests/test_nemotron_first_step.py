"""What the ``nemotron_h_lm`` family holds its first step to
(``families/nemotron_h_lm.py``, through ``families/lfm2_moe_lm.py``'s
fold): the number the harness compares with the reference's loss stays
inside ``loss_rtol`` for the program itself, and leaves it when the
reference is computed with 8-bit matrices or with a part of the mathematics
left out. Toy sizes, float32 program, on the CPU: the readings that set the
limits are the chip's (PERF.md section 6)."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from ddbench import rows, spec


def _first_step(monkeypatch, **control):
    """``(what the first step returned, the reference's loss, loss_rtol)``
    with the reference's ``loss`` given ``control``'s arguments."""
    cell = spec.Cell(spec.load_benchmark(), "nemotron3-nano-ep16.s8192",
                     dry_run=True)
    family = cell.family()
    reference = spec.load_module("reference", "nemotron_h_lm")
    if control:
        monkeypatch.setattr(reference, "loss", functools.partial(
            reference.loss, **control))
    mesh = Mesh(jax.devices()[:1], ("dp",))
    job = family.build(cell.config, cell.traffic, mesh, 2**31 + 7, True)
    tok, tgt = rows.token_shard(2**31 + 7, 0, job.batch, job.seq,
                                job.lm.vocab)
    want = job.reference_loss((tok, tgt))
    got = job.step((jnp.asarray(tok), jnp.asarray(tgt)))
    later = job.step((jnp.asarray(tok), jnp.asarray(tgt)))
    assert isinstance(later, jax.Array)     # the step's loss again
    return float(got), want, float(cell.config["loss_rtol"])


def test_the_program_is_inside_both_limits(monkeypatch):
    got, want, rtol = _first_step(monkeypatch)
    assert abs(got - want) / want < 0.01 * rtol


@pytest.mark.parametrize("control", [
    {"matrix_dtype": jnp.float8_e4m3fn}, {"leave_out": ("decay",)},
    {"leave_out": ("older_taps",)}, {"leave_out": ("experts",)},
    {"leave_out": ("relu2",)}, {"leave_out": ("norm_groups",)}],
    ids=["e4m3", "no-decay", "no-older-taps", "no-experts", "relu-for-relu2",
         "one-norm-group"])
def test_a_control_reference_is_outside(monkeypatch, control):
    got, want, rtol = _first_step(monkeypatch, **control)
    assert abs(got - want) / want > rtol
