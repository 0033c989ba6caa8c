"""Real dataset file formats: MNIST idx and QM9-style xyz.

The reference trains on actual MNIST via torchvision's downloader
(/root/reference/examples/vae/vae-ddp.py:202-216); this environment has no
network, so the loaders here read the standard on-disk formats directly
(drop the canonical files in a directory and point the examples at it) and
each has a writer so tests and offline runs can produce bit-faithful
fixtures.

* MNIST idx (yann.lecun.com layout): big-endian magic 0x0801 (labels,
  1-D) / 0x0803 (images, 3-D), optionally gzipped.
* QM9 xyz (quantum-chemistry molecules — the atomistic workload DDStore
  was built for, README.md:200-212): per-molecule text blocks
  ``natoms\\n<comment with float properties>\\n<symbol x y z ...>*``.
  Molecules become :class:`GraphSample`s with one-hot element node
  features, radius-graph edges, and a chosen comment-line property as the
  regression target.
"""

from __future__ import annotations

import gzip
import os
import struct
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .graphs import GraphSample

_IDX_MAGIC_LABELS = 0x0801
_IDX_MAGIC_IMAGES = 0x0803

# QM9's element set; unknown symbols raise (a corrupt file must not train).
QM9_ELEMENTS = ("H", "C", "N", "O", "F")


def _open(path: str, mode: str):
    if path.endswith(".gz"):
        return gzip.open(path, mode)
    return open(path, mode)


def read_idx(path: str) -> np.ndarray:
    """Read an idx-format array (images uint8 (N, R, C); labels (N,))."""
    with _open(path, "rb") as f:
        magic = struct.unpack(">I", f.read(4))[0]
        ndim = magic & 0xFF
        # Layout: two zero bytes, dtype byte (0x08 = ubyte), ndim byte.
        if magic >> 16 != 0 or ((magic >> 8) & 0xFF) != 0x08:
            raise ValueError(f"{path}: bad idx magic {magic:#x}")
        dims = struct.unpack(">" + "I" * ndim, f.read(4 * ndim))
        data = f.read(int(np.prod(dims)))
    arr = np.frombuffer(data, dtype=np.uint8)
    if arr.size != int(np.prod(dims)):
        raise ValueError(f"{path}: truncated idx payload")
    return arr.reshape(dims)


def write_idx(path: str, arr: np.ndarray) -> None:
    """Write uint8 idx (inverse of read_idx; .gz suffix gzips)."""
    arr = np.ascontiguousarray(arr, dtype=np.uint8)
    magic = 0x0800 | arr.ndim
    with _open(path, "wb") as f:
        f.write(struct.pack(">I", magic))
        f.write(struct.pack(">" + "I" * arr.ndim, *arr.shape))
        f.write(arr.tobytes())


_MNIST_FILES = {
    "train": ("train-images-idx3-ubyte", "train-labels-idx1-ubyte"),
    "test": ("t10k-images-idx3-ubyte", "t10k-labels-idx1-ubyte"),
}


def find_mnist(data_dir: str, split: str = "train"
               ) -> Optional[Tuple[str, str]]:
    """Locate the canonical MNIST pair in ``data_dir`` (plain or .gz)."""
    img_name, lbl_name = _MNIST_FILES[split]
    for suffix in ("", ".gz"):
        img = os.path.join(data_dir, img_name + suffix)
        lbl = os.path.join(data_dir, lbl_name + suffix)
        if os.path.exists(img) and os.path.exists(lbl):
            return img, lbl
    return None


def load_mnist(data_dir: str, split: str = "train", normalize: bool = True
               ) -> Tuple[np.ndarray, np.ndarray]:
    """(images (N, 784), labels (N,) int32) from the standard idx files.

    ``normalize=True`` gives float32 in [0,1] (the normalization
    torchvision's ToTensor applies in the reference's pipeline,
    vae-ddp.py:204-209). ``normalize=False`` keeps the raw uint8 pixels
    — the TPU-first hot path: the store holds and the loader stages 4x
    fewer bytes, and the model dequantizes on device with identical
    numerics (uint8/255 is exactly what ToTensor computes)."""
    found = find_mnist(data_dir, split)
    if found is None:
        raise FileNotFoundError(
            f"no MNIST idx files for split {split!r} under {data_dir}")
    img_path, lbl_path = found
    images = read_idx(img_path)
    labels = read_idx(lbl_path)
    if images.ndim != 3 or labels.ndim != 1 or len(images) != len(labels):
        raise ValueError(f"MNIST shape mismatch: {images.shape} vs "
                         f"{labels.shape}")
    flat = images.reshape(len(images), -1)
    if normalize:
        flat = flat.astype(np.float32) / 255.0
    return flat, labels.astype(np.int32)


def synthetic_mnist(n: int, seed: int = 0
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Deterministic MNIST-shaped data for offline environments: blurry
    class-conditioned blobs as uint8 pixels (the real idx files' dtype),
    same on every rank (like a shared download). One generator shared by
    the example and ``chip_smoke.py`` so both train on identical data;
    stored raw, dequantized on device (see models/vae._dequantize)."""
    g = np.random.default_rng(seed)
    labels = g.integers(0, 10, size=n).astype(np.int32)
    centers = g.random((10, 784), dtype=np.float32)
    x = centers[labels] * 0.8 + 0.2 * g.random((n, 784), dtype=np.float32)
    return np.round(x * 255.0).astype(np.uint8), labels


# ---------------------------------------------------------------------------
# QM9 xyz
# ---------------------------------------------------------------------------


def _parse_float(tok: str) -> float:
    # QM9 files occasionally use Mathematica-style "1.23*^-5" exponents.
    return float(tok.replace("*^", "e"))


def _block_start(line: str) -> Optional[int]:
    """natoms header = a single bare-integer token; anything else (QM9's
    frequency/SMILES/InChI trailer lines, blank padding) is not one."""
    toks = line.split()
    if len(toks) != 1:
        return None
    try:
        return int(toks[0])
    except ValueError:
        return None


def read_xyz(path: str) -> List[Tuple[List[str], np.ndarray, np.ndarray]]:
    """Parse one xyz file that may hold many molecule blocks. Returns
    [(symbols, coords (n,3) float32, props (P,) float32), ...]; props are
    the float tokens of the comment line (empty if none parse).

    Handles the real QM9 layout (dsgdb9nsd_*.xyz): per-atom Mulliken
    charge columns are ignored, and the three trailer lines after the atom
    block (harmonic frequencies, SMILES, InChI) are skipped — a new block
    only starts at a bare-integer natoms line."""
    mols = []
    with _open(path, "rt") as f:
        lines = [ln.rstrip("\n") for ln in f]
    i = 0
    while i < len(lines):
        n = _block_start(lines[i])
        if n is None:
            if mols:  # trailer junk between/after blocks
                i += 1
                continue
            if not lines[i].strip():
                i += 1
                continue
            raise ValueError(
                f"{path}: expected natoms header at line {i + 1}, got "
                f"{lines[i]!r}")
        comment = lines[i + 1] if i + 1 < len(lines) else ""
        props = []
        for tok in comment.replace("\t", " ").split():
            try:
                props.append(_parse_float(tok))
            except ValueError:
                continue
        symbols, coords = [], []
        for ln in lines[i + 2: i + 2 + n]:
            parts = ln.replace("\t", " ").split()
            symbols.append(parts[0])
            coords.append([_parse_float(p) for p in parts[1:4]])
        if len(symbols) != n:
            raise ValueError(f"{path}: truncated molecule block at line {i}")
        mols.append((symbols, np.asarray(coords, np.float32),
                     np.asarray(props, np.float32)))
        i += 2 + n
    return mols


def write_xyz(path: str, mols: Sequence[Tuple[Sequence[str], np.ndarray,
                                              Sequence[float]]]) -> None:
    """Inverse of read_xyz (fixtures / offline preprocessing)."""
    with _open(path, "wt") as f:
        for symbols, coords, props in mols:
            f.write(f"{len(symbols)}\n")
            f.write("\t".join(f"{p:.8f}" for p in props) + "\n")
            for s, xyz in zip(symbols, np.asarray(coords)):
                f.write(f"{s}\t" + "\t".join(f"{c:.8f}" for c in xyz) + "\n")


def molecule_to_graph(symbols: Sequence[str], coords: np.ndarray,
                      props: np.ndarray, *, target_index: int = 0,
                      cutoff: float = 1.7) -> GraphSample:
    """Molecule → GraphSample: one-hot element (+ normalized coords) node
    features, bidirectional radius-graph edges with [distance] attributes,
    target = props[target_index]. ``cutoff`` (Å) ~ covalent bonds at 1.7."""
    n = len(symbols)
    fn = len(QM9_ELEMENTS) + 3
    nodes = np.zeros((n, fn), np.float32)
    for i, s in enumerate(symbols):
        try:
            nodes[i, QM9_ELEMENTS.index(s)] = 1.0
        except ValueError:
            raise ValueError(f"unknown element {s!r} (expected one of "
                             f"{QM9_ELEMENTS})") from None
    center = coords - coords.mean(axis=0, keepdims=True)
    nodes[:, len(QM9_ELEMENTS):] = center

    src, dst, dists = [], [], []
    for i in range(n):
        d = np.linalg.norm(coords - coords[i], axis=1)
        for j in np.nonzero((d > 0) & (d <= cutoff))[0]:
            src.append(i)
            dst.append(int(j))
            dists.append(d[j])
    edge_index = np.stack([np.asarray(src, np.int64),
                           np.asarray(dst, np.int64)], axis=1) \
        if src else np.zeros((0, 2), np.int64)
    edge_attr = np.asarray(dists, np.float32)[:, None] \
        if dists else np.zeros((0, 1), np.float32)
    if target_index >= len(props):
        raise ValueError(f"target_index {target_index} out of range for "
                         f"{len(props)} properties")
    y = np.asarray([props[target_index]], np.float32)
    return GraphSample(nodes, edge_index, edge_attr, y)


def load_qm9_dir(data_dir: str, *, target_index: int = 0,
                 cutoff: float = 1.7, limit: Optional[int] = None
                 ) -> List[GraphSample]:
    """Read every .xyz/.xyz.gz under ``data_dir`` (sorted for rank
    determinism) into GraphSamples."""
    paths = sorted(
        os.path.join(data_dir, f) for f in os.listdir(data_dir)
        if f.endswith((".xyz", ".xyz.gz")))
    if not paths:
        raise FileNotFoundError(f"no .xyz files under {data_dir}")
    out: List[GraphSample] = []
    for p in paths:
        for symbols, coords, props in read_xyz(p):
            out.append(molecule_to_graph(symbols, coords, props,
                                         target_index=target_index,
                                         cutoff=cutoff))
            if limit is not None and len(out) >= limit:
                return out
    return out
