"""Real file-format ingestion: MNIST idx and QM9 xyz.

The reference trains on actual on-disk MNIST (torchvision pipeline,
/root/reference/examples/vae/vae-ddp.py:202-216). These tests prove the
from-scratch readers round-trip through their writers, reject corrupt
input loudly, and — via the subprocess end-to-end tests — that the
examples really train from files on disk through the store.
"""

import gzip
import os
import struct
import subprocess
import sys

import numpy as np
import pytest

from ddstore_tpu.data import (find_mnist, load_mnist, load_qm9_dir,
                              molecule_to_graph, read_idx, read_xyz,
                              write_idx, write_xyz)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# MNIST idx
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("suffix", ["", ".gz"])
def test_idx_roundtrip_images(tmp_path, rng, suffix):
    arr = rng.integers(0, 256, size=(7, 28, 28)).astype(np.uint8)
    path = str(tmp_path / f"imgs-idx3-ubyte{suffix}")
    write_idx(path, arr)
    back = read_idx(path)
    assert back.dtype == np.uint8 and back.shape == arr.shape
    np.testing.assert_array_equal(back, arr)
    if suffix == ".gz":  # really gzipped, not just renamed
        with open(path, "rb") as f:
            assert f.read(2) == b"\x1f\x8b"


def test_idx_roundtrip_labels(tmp_path, rng):
    labels = rng.integers(0, 10, size=64).astype(np.uint8)
    path = str(tmp_path / "lbl-idx1-ubyte")
    write_idx(path, labels)
    np.testing.assert_array_equal(read_idx(path), labels)


def test_idx_bad_magic(tmp_path):
    path = str(tmp_path / "bad")
    with open(path, "wb") as f:
        f.write(struct.pack(">I", 0xDEADBEEF) + b"\0" * 16)
    with pytest.raises(ValueError, match="magic"):
        read_idx(path)


def test_idx_truncated_payload(tmp_path, rng):
    arr = rng.integers(0, 256, size=(4, 5, 5)).astype(np.uint8)
    path = str(tmp_path / "trunc")
    write_idx(path, arr)
    with open(path, "rb") as f:
        blob = f.read()
    with open(path, "wb") as f:
        f.write(blob[:-10])
    with pytest.raises(ValueError, match="truncated"):
        read_idx(path)


def _write_mnist_fixture(data_dir, n=32, gz=False, seed=0):
    g = np.random.default_rng(seed)
    images = g.integers(0, 256, size=(n, 28, 28)).astype(np.uint8)
    labels = g.integers(0, 10, size=n).astype(np.uint8)
    sfx = ".gz" if gz else ""
    os.makedirs(data_dir, exist_ok=True)
    write_idx(os.path.join(data_dir, f"train-images-idx3-ubyte{sfx}"), images)
    write_idx(os.path.join(data_dir, f"train-labels-idx1-ubyte{sfx}"), labels)
    return images, labels


@pytest.mark.parametrize("gz", [False, True])
def test_load_mnist(tmp_path, gz):
    images, labels = _write_mnist_fixture(str(tmp_path), n=32, gz=gz)
    assert find_mnist(str(tmp_path)) is not None
    x, y = load_mnist(str(tmp_path))
    assert x.shape == (32, 784) and x.dtype == np.float32
    assert y.shape == (32,) and y.dtype == np.int32
    assert 0.0 <= x.min() and x.max() <= 1.0
    np.testing.assert_allclose(
        x, images.reshape(32, -1).astype(np.float32) / 255.0)
    np.testing.assert_array_equal(y, labels.astype(np.int32))


def test_load_mnist_raw_uint8(tmp_path):
    # normalize=False keeps the idx files' raw pixels: what the example
    # registers in the store (4x fewer bytes; the VAE step
    # dequantizes on device).
    images, _labels = _write_mnist_fixture(str(tmp_path), n=16)
    x, y = load_mnist(str(tmp_path), normalize=False)
    assert x.shape == (16, 784) and x.dtype == np.uint8
    assert y.dtype == np.int32
    np.testing.assert_array_equal(x, images.reshape(16, -1))


def test_load_mnist_missing(tmp_path):
    assert find_mnist(str(tmp_path)) is None
    with pytest.raises(FileNotFoundError):
        load_mnist(str(tmp_path))


def test_load_mnist_length_mismatch(tmp_path, rng):
    _write_mnist_fixture(str(tmp_path), n=8)
    # Overwrite labels with a different length.
    write_idx(os.path.join(str(tmp_path), "train-labels-idx1-ubyte"),
              rng.integers(0, 10, size=9).astype(np.uint8))
    with pytest.raises(ValueError, match="mismatch"):
        load_mnist(str(tmp_path))


# ---------------------------------------------------------------------------
# QM9 xyz
# ---------------------------------------------------------------------------


def _make_mols(rng, n_mols=3):
    mols = []
    for _ in range(n_mols):
        n = int(rng.integers(2, 6))
        symbols = [["H", "C", "N", "O", "F"][int(k)]
                   for k in rng.integers(0, 5, size=n)]
        coords = rng.random((n, 3)).astype(np.float32) * 2.0
        props = rng.random(4).astype(np.float32)
        mols.append((symbols, coords, props))
    return mols


@pytest.mark.parametrize("suffix", [".xyz", ".xyz.gz"])
def test_xyz_roundtrip(tmp_path, rng, suffix):
    mols = _make_mols(rng)
    path = str(tmp_path / ("m" + suffix))
    write_xyz(path, mols)
    back = read_xyz(path)
    assert len(back) == len(mols)
    for (s0, c0, p0), (s1, c1, p1) in zip(mols, back):
        assert list(s0) == list(s1)
        np.testing.assert_allclose(c0, c1, atol=1e-6)
        np.testing.assert_allclose(p0, p1, atol=1e-6)


def test_xyz_mathematica_exponent(tmp_path):
    # QM9 files in the wild use "*^" exponents; both positions must parse.
    path = str(tmp_path / "m.xyz")
    with open(path, "w") as f:
        f.write("1\ngdb 1\t1.23*^-5\t4.0\nC\t0.0\t1.5*^-1\t0.0\n")
    ((symbols, coords, props),) = read_xyz(path)
    assert symbols == ["C"]
    np.testing.assert_allclose(props, [1.0, 1.23e-5, 4.0], atol=1e-9)
    np.testing.assert_allclose(coords[0], [0.0, 0.15, 0.0], atol=1e-7)


def test_xyz_real_qm9_layout(tmp_path):
    # Genuine dsgdb9nsd_*.xyz shape: 5 atom columns (Mulliken charge),
    # 'gdb <id> <props>' comment, and three trailer lines (harmonic
    # frequencies, SMILES, InChI) that must not be parsed as a new block.
    path = str(tmp_path / "dsgdb9nsd_000001.xyz")
    with open(path, "w") as f:
        f.write(
            "5\n"
            "gdb 1\t157.7118\t157.70997\t157.70699\t0.\t13.21\t-0.3877\n"
            "C\t-0.0126981359\t1.0858041578\t0.0080009958\t-0.535689\n"
            "H\t0.002150416\t-0.0060313176\t0.0019761204\t0.133921\n"
            "H\t1.0117308433\t1.4637511618\t0.0002765748\t0.133922\n"
            "H\t-0.540815069\t1.4475266138\t-0.8766437152\t0.133923\n"
            "H\t-0.5238136345\t1.4379326443\t0.9063972942\t0.133923\n"
            "1341.307\t1341.3284\t1341.365\t1562.6731\t1562.7453\n"
            "C\tC\n"
            "InChI=1S/CH4/h1H4\tInChI=1S/CH4/h1H4\n")
    ((symbols, coords, props),) = read_xyz(path)
    assert symbols == ["C", "H", "H", "H", "H"]
    assert coords.shape == (5, 3)
    # props[0] is the gdb serial; props[1] the first physical property.
    np.testing.assert_allclose(props[:3], [1.0, 157.7118, 157.70997],
                               atol=1e-5)
    g = molecule_to_graph(symbols, coords, props, target_index=1)
    assert g.nodes.shape == (5, 8)
    np.testing.assert_allclose(g.y, [157.7118], atol=1e-4)
    # Two molecules per file with trailers between them also parse.
    with open(path) as f:
        blob = f.read()
    two = str(tmp_path / "two.xyz")
    with open(two, "w") as f:
        f.write(blob + blob)
    assert len(read_xyz(two)) == 2


def test_xyz_junk_leading_line_rejected(tmp_path):
    path = str(tmp_path / "bad.xyz")
    with open(path, "w") as f:
        f.write("not-a-count here\nC\t0\t0\t0\n")
    with pytest.raises(ValueError, match="natoms header"):
        read_xyz(path)


def test_xyz_truncated_block(tmp_path):
    path = str(tmp_path / "m.xyz")
    with open(path, "w") as f:
        f.write("3\nprops 1.0\nH\t0\t0\t0\nH\t1\t0\t0\n")  # claims 3, has 2
    with pytest.raises(ValueError, match="truncated"):
        read_xyz(path)


def test_molecule_to_graph_radius_edges():
    # H at distances 1.0 (bond) and 5.0 (no bond) from C.
    symbols = ["C", "H", "H"]
    coords = np.array([[0, 0, 0], [1.0, 0, 0], [5.0, 0, 0]], np.float32)
    g = molecule_to_graph(symbols, coords, np.array([2.5], np.float32),
                          cutoff=1.7)
    assert g.nodes.shape == (3, 8)  # 5 one-hot + 3 coords
    assert g.nodes[0, 1] == 1.0 and g.nodes[1, 0] == 1.0  # C, H one-hot
    # Only the 0<->1 pair is within cutoff, both directions present.
    pairs = {tuple(e) for e in g.edge_index.tolist()}
    assert pairs == {(0, 1), (1, 0)}
    np.testing.assert_allclose(g.edge_attr[:, 0], [1.0, 1.0], atol=1e-6)
    np.testing.assert_allclose(g.y, [2.5])


def test_molecule_to_graph_errors():
    coords = np.zeros((1, 3), np.float32)
    with pytest.raises(ValueError, match="unknown element"):
        molecule_to_graph(["Xx"], coords, np.array([1.0], np.float32))
    with pytest.raises(ValueError, match="target_index"):
        molecule_to_graph(["C"], coords, np.array([1.0], np.float32),
                          target_index=3)


def test_load_qm9_dir(tmp_path, rng):
    mols = _make_mols(rng, n_mols=5)
    write_xyz(str(tmp_path / "b.xyz"), mols[:3])
    write_xyz(str(tmp_path / "a.xyz.gz"), mols[3:])
    graphs = load_qm9_dir(str(tmp_path), target_index=1)
    assert len(graphs) == 5
    # Files are read in sorted order: a.xyz.gz's molecules come first.
    np.testing.assert_allclose(graphs[0].y, [mols[3][2][1]], atol=1e-6)
    assert len(load_qm9_dir(str(tmp_path), limit=2)) == 2
    with pytest.raises(FileNotFoundError):
        load_qm9_dir(str(tmp_path / "empty"))


# ---------------------------------------------------------------------------
# End to end: the examples really train from files on disk
# ---------------------------------------------------------------------------


def _run_example(script, extra, tmp_path):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               DDSTORE_RDV_DIR=str(tmp_path / "rdv"))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script)] + extra,
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


@pytest.mark.slow
def test_vae_example_trains_on_real_idx_files(tmp_path):
    data_dir = str(tmp_path / "mnist")
    _write_mnist_fixture(data_dir, n=256, gz=True, seed=3)
    out = _run_example("vae_mnist.py",
                       ["--data-dir", data_dir, "--epochs", "1",
                        "--steps", "2", "--batch-size", "32",
                        "--samples", "256"], tmp_path)
    assert "epoch 0" in out


@pytest.mark.slow
def test_gnn_example_trains_on_real_xyz_files(tmp_path):
    rng = np.random.default_rng(7)
    data_dir = tmp_path / "qm9"
    data_dir.mkdir()
    write_xyz(str(data_dir / "mols.xyz"), _make_mols(rng, n_mols=24))
    out = _run_example("gnn_molecules.py",
                       ["--data-dir", str(data_dir), "--epochs", "1",
                        "--steps", "2", "--graphs", "24",
                        "--graphs-per-slot", "4"], tmp_path)
    assert "epoch 0" in out
