"""Native C++ host-kernel tests.

The reference has no native layer (SURVEY.md §2.1); these cover the C++
gather/mean kernels against their numpy ground truth, the graceful fallback
when the library is unavailable, and the integration points (mean_serialized
aggregation, sample_batch).
"""

import os
import shutil

import numpy as np
import pytest

from distriflow_tpu import native
from distriflow_tpu.data.dataset import sample_batch
from distriflow_tpu.utils.serialization import mean_serialized, serialize_tree

HAVE_GXX = shutil.which("g++") is not None


@pytest.fixture(scope="module", autouse=True)
def built():
    native.ensure_built()
    yield


def test_build_succeeds_with_compiler():
    if not HAVE_GXX:
        pytest.skip("no g++ in this image")
    assert native.ensure_built(), "native build failed despite g++ present"
    assert native.AVAILABLE


def test_gather_rows_matches_numpy():
    rng = np.random.RandomState(0)
    for shape, dtype in [((100, 17), np.float32), ((64, 8, 8, 3), np.uint8),
                         ((50,), np.int64)]:
        src = (rng.rand(*shape) * 100).astype(dtype)
        idx = rng.randint(0, shape[0], 37)
        np.testing.assert_array_equal(native.gather_rows(src, idx), src[idx])


def test_gather_rows_validates_indices():
    src = np.zeros((4, 2), np.float32)
    with pytest.raises(IndexError):
        native.gather_rows(src, np.array([0, 4]))
    with pytest.raises(IndexError):
        native.gather_rows(src, np.array([-1]))
    with pytest.raises(ValueError):
        native.gather_rows(src, np.array([[0, 1]]))


def test_gather_rows_non_contiguous_source():
    src = np.arange(200, dtype=np.float32).reshape(20, 10)[:, ::2]  # strided view
    idx = np.array([3, 0, 7])
    np.testing.assert_array_equal(native.gather_rows(src, idx), src[idx])


def test_mean_buffers_matches_numpy():
    rng = np.random.RandomState(1)
    bufs = [rng.randn(33, 7).astype(np.float32) for _ in range(5)]
    got = native.mean_buffers(bufs)
    np.testing.assert_allclose(got, np.mean(np.stack(bufs), 0), rtol=1e-6)
    assert got.dtype == np.float32


def test_mean_buffers_validates():
    with pytest.raises(ValueError):
        native.mean_buffers([])
    with pytest.raises(ValueError):
        native.mean_buffers([np.zeros((2,), np.float32), np.zeros((3,), np.float32)])


def test_numpy_fallback_when_unavailable(monkeypatch):
    # a machine with no g++ at all: the one case the numpy paths serve
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_no_compiler", True)
    monkeypatch.setattr(native, "AVAILABLE", False)
    src = np.arange(12, dtype=np.float32).reshape(4, 3)
    np.testing.assert_array_equal(native.gather_rows(src, np.array([2, 0])), src[[2, 0]])
    bufs = [np.full((3,), float(i), np.float32) for i in range(3)]
    np.testing.assert_allclose(native.mean_buffers(bufs), [1.0, 1.0, 1.0])


def _isolated_copy(monkeypatch, tmp_path, source_suffix=b""):
    """Point the loader at a private copy of the source tree so a test can
    edit the source and litter the directory without touching the real one."""
    (tmp_path / "src").mkdir(exist_ok=True)
    with open(native._SRC, "rb") as f:
        (tmp_path / "src" / "distriflow_native.cpp").write_bytes(
            f.read() + source_suffix)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(
        native, "_SRC", str(tmp_path / "src" / "distriflow_native.cpp"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_no_compiler", False)
    monkeypatch.setattr(native, "AVAILABLE", False)


def test_loader_refuses_library_of_another_source(monkeypatch, tmp_path):
    """A library whose source hash differs from the source on disk is never
    opened: it has another name, and when the build for the current source
    fails the loader raises instead of using it."""
    if not HAVE_GXX:
        pytest.skip("no g++ in this image")
    real_lib = native._lib_path()
    assert native.ensure_built() and os.path.exists(real_lib)
    _isolated_copy(monkeypatch, tmp_path, b"\n// edited\n")
    wanted = native._lib_path()
    assert os.path.basename(wanted) != os.path.basename(real_lib)
    # the other revision's (perfectly loadable) library sits right there
    stale = tmp_path / os.path.basename(real_lib)
    shutil.copy(real_lib, stale)

    def refuse(lib_path):
        raise RuntimeError("native build failed (scripted)")

    monkeypatch.setattr(native, "_build", refuse)
    with pytest.raises(RuntimeError, match="native build failed"):
        native.ensure_built()
    assert native._lib is None and not native.AVAILABLE
    # with a working compiler the current source is built under its own
    # name, loaded, and the other revision's file is removed
    monkeypatch.undo()
    _isolated_copy(monkeypatch, tmp_path, b"\n// edited\n")
    assert native.ensure_built()
    assert os.path.exists(wanted) and not stale.exists()
    np.testing.assert_array_equal(
        native.gather_rows(np.arange(6.0).reshape(3, 2), np.array([2, 0])),
        [[4.0, 5.0], [0.0, 1.0]])


def test_failed_build_raises_with_compiler_output(monkeypatch, tmp_path):
    if not HAVE_GXX:
        pytest.skip("no g++ in this image")
    _isolated_copy(monkeypatch, tmp_path, b"\nthis is not C++\n")
    with pytest.raises(RuntimeError, match="native build failed"):
        native.gather_rows(np.zeros((2, 2), np.float32), np.array([0]))
    assert not any(p.suffix == ".so" or p.name.endswith(".tmp")
                   for p in tmp_path.iterdir())


# -- integration points ------------------------------------------------------


def test_mean_serialized_aggregation():
    """The federated hot loop: mean of N serialized gradient trees."""
    rng = np.random.RandomState(2)
    template = {"w": np.zeros((5, 3), np.float32), "b": np.zeros((3,), np.float32)}
    trees = [
        {"w": rng.randn(5, 3).astype(np.float32), "b": rng.randn(3).astype(np.float32)}
        for _ in range(4)
    ]
    updates = [serialize_tree(t) for t in trees]
    got = mean_serialized(updates, template)
    np.testing.assert_allclose(
        got["w"], np.mean([t["w"] for t in trees], 0), rtol=1e-6
    )
    np.testing.assert_allclose(
        got["b"], np.mean([t["b"] for t in trees], 0), rtol=1e-6
    )


def test_mean_serialized_rejects_mismatch():
    a = serialize_tree({"w": np.zeros((2,), np.float32)})
    b = serialize_tree({"w": np.zeros((3,), np.float32)})
    with pytest.raises(ValueError):
        mean_serialized([a, b], {"w": np.zeros((2,), np.float32)})
    c = serialize_tree({"v": np.zeros((2,), np.float32)})
    with pytest.raises(ValueError):
        mean_serialized([a, c], {"w": np.zeros((2,), np.float32)})


def test_sample_batch():
    x = np.arange(40, dtype=np.float32).reshape(10, 4)
    y = np.eye(10, dtype=np.float32)
    idx = np.array([9, 1, 1, 4])
    bx, by = sample_batch(x, y, idx)
    np.testing.assert_array_equal(bx, x[idx])
    np.testing.assert_array_equal(by, y[idx])
