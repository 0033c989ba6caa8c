"""On-demand native build for the ddstore_tpu C++ core.

Compiles ddstore_tpu/native/*.cc into a shared library with g++ the first
time the binding is imported (or whenever the cached .so was built from
other source contents). This replaces the reference's `CC=mpicc CXX=mpicxx pip install .`
requirement (/root/reference/README.md:20-32) — no MPI toolchain exists on
TPU-VM hosts, and the library must be usable from a plain checkout.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import tempfile
import threading

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "native")
_LIB_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_lib")
_SOURCES = ["store.cc", "local_transport.cc", "tcp_transport.cc",
            "uring_transport.cc", "worker_pool.cc", "cma.cc", "fault.cc",
            "gateway.cc", "health.cc", "integrity.cc", "metrics_hist.cc",
            "tier.cc", "trace.cc", "capi.cc"]
_HEADERS = ["store.h", "local_transport.h", "tcp_transport.h",
            "uring_transport.h", "wire.h", "worker_pool.h", "cma.h",
            "fault.h", "gateway.h", "health.h", "integrity.h",
            "measure.h", "metrics_hist.h", "tier.h", "trace.h",
            "thread_annotations.h"]
_lock = threading.Lock()

# Sanitizer builds (SURVEY §5: the reference has no TSan/ASan anywhere; the
# shared_mutex-heavy core + serving threads are exactly the code that needs
# them). DDSTORE_SANITIZE=thread|address|undefined selects a
# separately-cached .so so plain and sanitized builds don't evict each
# other. `undefined` (UBSan, ISSUE 8 satellite) catches the shift/
# overflow/alignment class the wire-framing and offset arithmetic are
# full of — and unlike TSan it does not hang under this gVisor kernel.
_SANITIZERS = {"thread": "-fsanitize=thread",
               "address": "-fsanitize=address",
               "undefined": "-fsanitize=undefined"}


def _sanitize_mode() -> str:
    mode = os.environ.get("DDSTORE_SANITIZE", "").strip().lower()
    if mode and mode not in _SANITIZERS:
        raise ValueError(
            f"DDSTORE_SANITIZE={mode!r}: expected one of {set(_SANITIZERS)}")
    return mode


def _lib_path(mode: str) -> str:
    suffix = f"_{mode}" if mode else ""
    return os.path.join(_LIB_DIR, f"libddstore_tpu{suffix}.so")


def _flags(mode: str) -> list:
    flags = ["-O2", "-std=c++17", "-fPIC", "-shared", "-pthread", "-Wall"]
    if mode:
        # -O1 + frame pointers give usable sanitizer reports.
        flags += [_SANITIZERS[mode], "-O1", "-fno-omit-frame-pointer", "-g"]
    return flags


def _source_digest(mode: str) -> str:
    """Hash of everything the library is built from: the contents of
    _SOURCES + _HEADERS and the compile flags. Staleness is decided from
    this, not from mtimes — a tree copy (rsync, git archive, the chip
    tool) does not preserve mtimes, and a ``_lib/`` that travelled from
    another tree must be rebuilt, not loaded."""
    h = hashlib.sha256(" ".join(_flags(mode)).encode())
    for f in _SOURCES + _HEADERS:
        h.update(f.encode() + b"\0")
        with open(os.path.join(_NATIVE_DIR, f), "rb") as fh:
            h.update(fh.read())
    return h.hexdigest()


def _stale(lib_path: str, digest: str) -> bool:
    """True unless ``lib_path`` exists and its sidecar records that it was
    built from exactly ``digest``."""
    if not os.path.exists(lib_path):
        return True
    try:
        with open(lib_path + ".srchash") as f:
            return f.read().strip() != digest
    except OSError:
        return True


def _sweep_strays(max_age_s: float = 600.0) -> None:
    """Remove leaked build-staging files (``_lib/tmp*.so``). A build
    killed between mkstemp and its cleanup leaks the staging file; a
    LIVE concurrent build's temp is at most seconds old, so anything
    older than ``max_age_s`` is provably dead. Runs at EVERY build()
    entry — including the fresh-cache early return, which is where the
    old sweep never fired and four strays accumulated (ISSUE 3)."""
    import glob
    import time as _time
    for stray in glob.glob(os.path.join(_LIB_DIR, "tmp*.so")):
        try:
            if _time.time() - os.path.getmtime(stray) > max_age_s:
                os.unlink(stray)
        except OSError:
            pass


def build(force: bool = False) -> str:
    """Returns the path to the built shared library, compiling if needed."""
    mode = _sanitize_mode()
    lib_path = _lib_path(mode)
    with _lock:
        if os.path.isdir(_LIB_DIR) and os.access(_LIB_DIR, os.W_OK):
            _sweep_strays()
        # Installed wheels bundle the library (setup.py build_native); the
        # site-packages tree may be read-only, so fall back to the bundled
        # lib rather than insisting on a rebuild.
        if os.path.exists(lib_path) and not os.access(_LIB_DIR, os.W_OK):
            return lib_path
        digest = _source_digest(mode)
        if not force and not _stale(lib_path, digest):
            return lib_path
        os.makedirs(_LIB_DIR, exist_ok=True)
        cxx = os.environ.get("DDSTORE_CXX", "g++")
        cmd = [cxx] + _flags(mode)
        cmd += [os.path.join(_NATIVE_DIR, s) for s in _SOURCES]
        # Build to a temp path then rename: concurrent test processes may
        # race on the build, and dlopen of a half-written .so is fatal.
        # The finally below cleans the staging file on every non-killed
        # exit; _sweep_strays above catches the SIGKILL leaks.
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_LIB_DIR)
        os.close(fd)
        try:
            subprocess.run(cmd + ["-o", tmp], check=True, capture_output=True,
                           text=True)
            os.replace(tmp, lib_path)
            # Library first, sidecar second: a crash in between leaves a
            # lib with no (or an old) digest, which reads as stale.
            with open(tmp, "w") as f:
                f.write(digest + "\n")
            os.replace(tmp, lib_path + ".srchash")
        except subprocess.CalledProcessError as e:  # pragma: no cover
            raise RuntimeError(
                f"native build failed:\n{e.stderr}") from e
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return lib_path


def main(argv=None) -> None:
    """``python -m ddstore_tpu._build`` (or ``make native``): the
    reproducible rebuild entry — compiles iff the cached library was built
    from other source contents and prints the library path either way."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m ddstore_tpu._build",
        description="Build the native ddstore_tpu core (stale-aware).")
    ap.add_argument("--force", action="store_true",
                    help="rebuild even when the cached .so is fresh")
    args = ap.parse_args(argv)
    print(build(force=args.force))


if __name__ == "__main__":
    main()
