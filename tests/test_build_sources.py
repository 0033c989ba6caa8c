"""Source-list drift guard (ISSUE 5 satellite): the native source list
lives in THREE places that cannot import each other — the on-demand
builder (``ddstore_tpu/_build.py``), ``setup.py`` (cannot import the
package without triggering its lazy build), and the standalone CMake
build. PR 4 found ``worker_pool.cc``/``cma.cc`` missing from setup.py
since PR 1/2 — a wheel built from it would have shipped an unlinkable
library. This test makes the recurrence mechanical: any .cc added to
one list must land in all three (and on disk).
"""

import ast
import os
import re

import pytest

pytestmark = pytest.mark.tier1_required

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "ddstore_tpu", "native")


def _assigned_list(path, name):
    """The string-list literal assigned to ``name`` in a Python file,
    found by AST so formatting/comments can't confuse the parse."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == name:
                    return [ast.literal_eval(e) for e in node.value.elts]
    raise AssertionError(f"{name} not found in {path}")


def _cmake_library_sources():
    with open(os.path.join(NATIVE, "CMakeLists.txt")) as f:
        text = f.read()
    m = re.search(r"add_library\s*\(\s*ddstore_tpu\s+SHARED\s+(.*?)\)",
                  text, re.S)
    assert m, "add_library(ddstore_tpu SHARED ...) not found"
    return [tok for tok in m.group(1).split() if tok.endswith(".cc")]


def test_source_lists_agree():
    build_py = set(_assigned_list(
        os.path.join(REPO, "ddstore_tpu", "_build.py"), "_SOURCES"))
    setup_py = set(_assigned_list(os.path.join(REPO, "setup.py"),
                                  "SOURCES"))
    cmake = set(_cmake_library_sources())
    assert build_py == setup_py, (
        f"_build.py vs setup.py drift: only in _build.py: "
        f"{sorted(build_py - setup_py)}; only in setup.py: "
        f"{sorted(setup_py - build_py)}")
    assert build_py == cmake, (
        f"_build.py vs CMakeLists drift: only in _build.py: "
        f"{sorted(build_py - cmake)}; only in CMake: "
        f"{sorted(cmake - build_py)}")


def test_listed_sources_exist_and_cover_the_tree():
    listed = set(_assigned_list(
        os.path.join(REPO, "ddstore_tpu", "_build.py"), "_SOURCES"))
    for s in listed:
        assert os.path.exists(os.path.join(NATIVE, s)), f"missing {s}"
    # Every .cc in native/ is either linked into the library or an
    # explicitly known standalone (the demo binary). A new translation
    # unit dropped into native/ must be added to the lists — or named
    # here on purpose.
    on_disk = {f for f in os.listdir(NATIVE) if f.endswith(".cc")}
    standalone = {"demo.cc"}
    unaccounted = on_disk - listed - standalone
    assert not unaccounted, (
        f"native/*.cc not in the build lists (add to _build.py "
        f"_SOURCES, setup.py SOURCES, and CMakeLists.txt): "
        f"{sorted(unaccounted)}")


def test_headers_listed_for_cache_keying():
    """_build.py keys its rebuild cache on _SOURCES + _HEADERS content;
    a header missing from _HEADERS means edits to it silently reuse a
    stale cached .so."""
    headers = set(_assigned_list(
        os.path.join(REPO, "ddstore_tpu", "_build.py"), "_HEADERS"))
    on_disk = {f for f in os.listdir(NATIVE) if f.endswith(".h")}
    assert on_disk == headers, (
        f"native/*.h vs _build.py _HEADERS drift: only on disk: "
        f"{sorted(on_disk - headers)}; only in _HEADERS: "
        f"{sorted(headers - on_disk)}")


def test_staleness_is_decided_by_content_not_mtime(tmp_path, monkeypatch):
    """A cached library is fresh only while its sidecar names the digest
    of the current sources + flags: touching a file changes nothing, a
    one-byte edit (or a ``_lib/`` that travelled from another tree, whose
    sidecar names other contents) forces a rebuild."""
    from ddstore_tpu import _build

    native = tmp_path / "native"
    native.mkdir()
    for f in _build._SOURCES + _build._HEADERS:
        (native / f).write_text(f"// {f}\n")
    monkeypatch.setattr(_build, "_NATIVE_DIR", str(native))
    digest = _build._source_digest("")
    lib = tmp_path / "libddstore_tpu.so"
    assert _build._stale(str(lib), digest)            # no library
    lib.write_bytes(b"\x7fELF")
    assert _build._stale(str(lib), digest)            # no sidecar
    (tmp_path / "libddstore_tpu.so.srchash").write_text(digest + "\n")
    assert not _build._stale(str(lib), digest)
    os.utime(native / "store.cc", (1, 1))             # mtime is ignored
    assert _build._source_digest("") == digest
    (native / "wire.h").write_text("// wire.h edited\n")
    edited = _build._source_digest("")
    assert edited != digest and _build._stale(str(lib), edited)
    assert _build._source_digest("address") != edited  # flags are hashed
