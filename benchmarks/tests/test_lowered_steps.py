"""``lowered_steps.py``, the by-hand comparison of the cells' lowered train
steps with another commit's: what it takes out of the two texts, and the
cells it reads from a tree. (The comparison itself lowers six full-size
steps twice, about 100 s: run the script.)"""

import os

import lowered_steps


def test_a_moved_line_or_another_process_is_no_other_program():
    module = ('%0 = stablehlo.custom_call @tpu_custom_call(%a) '
              '{backend_config = "AbCd/ops/attention.py:412=="} : f32')
    jaxpr = ("pallas_call[name=ddstore_flash_fwd] at "
             "/tmp/x/against/ddstore_tpu/ops/attention.py:412\n"
             "policy=<function save_only_these_names.<locals>.policy at "
             "0x7fa34c1e9120>")
    moved = (module.replace("412", "433").replace("AbCd", "EfGh"),
             jaxpr.replace("412", "433").replace("/tmp/x/against/", "/r/")
             .replace("0x7fa34c1e9120", "0x7fe35c176840"))
    assert lowered_steps.normalised(module, jaxpr) \
        == lowered_steps.normalised(*moved)
    # another kernel name, another primitive: another program
    assert lowered_steps.normalised(module, jaxpr) \
        != lowered_steps.normalised(module, jaxpr.replace("_fwd", "_dq"))
    assert lowered_steps.normalised(module, jaxpr) \
        != lowered_steps.normalised(module.replace("f32", "bf16"), jaxpr)


def test_the_cells_are_the_trees_one_chip_cells_at_their_traffic():
    cells = lowered_steps.cells_of(lowered_steps.ROOT)
    names = [c[0] for c in cells]
    assert "dense-lm-d1024.s8192.sp4" not in names
    assert ("glm47-flash-ep8.s2048",
            "benchmarks/configs/glm47-flash-ep8.json", 8, 2048) in cells
    assert ("sdar-30b-a3b-ep8.s8192.b1",
            "benchmarks/configs/sdar-30b-a3b-ep8.json", 1, 8192) in cells
    for _, file, _, _ in cells:
        assert os.path.exists(os.path.join(lowered_steps.ROOT, file))
