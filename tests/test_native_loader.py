"""Native (C++) round assembler vs the pure-numpy reference path.

The native library is a fast path with identical outputs; every test here
asserts bit-equality against the Python assembly for ragged plans
(short final docs, partial batches, uneven worker shards).
"""

import numpy as np
import pytest

from kubeml_tpu import native
from kubeml_tpu.data.loader import RoundLoader, prefetch_rounds
from kubeml_tpu.data.registry import DatasetRegistry
from kubeml_tpu.models.base import KubeDataset

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")


class _PlainDataset(KubeDataset):
    dataset = "nat"


@pytest.fixture()
def handle(tmp_path):
    reg = DatasetRegistry(root=str(tmp_path / "ds"))
    rng = np.random.RandomState(0)
    # ragged on purpose: 330 train samples -> last doc is short (330 = 5*64+10)
    x = rng.rand(330, 6, 4).astype(np.float32)
    y = rng.randint(0, 5, 330).astype(np.int64)
    xt = rng.rand(90, 6, 4).astype(np.float32)
    yt = rng.randint(0, 5, 90).astype(np.int64)
    return reg.create("nat", x, y, xt, yt)


def _collect(loader, n_workers, k, batch, epoch=0):
    plan = loader.plan(n_workers, k, batch)
    return list(loader.epoch_rounds(plan, epoch))


@pytest.mark.parametrize("n_workers,k,batch", [
    (3, 2, 16), (5, -1, 32), (2, 4, 8), (1, 1, 64), (4, 3, 10)])
def test_native_rounds_match_python(handle, n_workers, k, batch):
    ds = _PlainDataset()
    nat = RoundLoader(handle, ds, n_lanes=2, seed=7, use_native=True)
    ref = RoundLoader(handle, ds, n_lanes=2, seed=7, use_native=False)
    assert nat._native_train, "native path not active"
    got = _collect(nat, n_workers, k, batch)
    want = _collect(ref, n_workers, k, batch)
    assert len(got) == len(want) and len(got) > 0
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.batch["x"], w.batch["x"])
        np.testing.assert_array_equal(g.batch["y"], w.batch["y"])
        np.testing.assert_array_equal(g.sample_mask, w.sample_mask)
        np.testing.assert_array_equal(g.step_mask, w.step_mask)
        np.testing.assert_array_equal(g.worker_mask, w.worker_mask)
        np.testing.assert_array_equal(g.rngs, w.rngs)
        assert g.round_index == w.round_index


def test_native_eval_matches_python(handle):
    ds = _PlainDataset()
    nat = RoundLoader(handle, ds, n_lanes=2, seed=1, use_native=True)
    ref = RoundLoader(handle, ds, n_lanes=2, seed=1, use_native=False)
    bg, mg = nat.eval_batches(3, 16)
    bw, mw = ref.eval_batches(3, 16)
    np.testing.assert_array_equal(bg["x"], bw["x"])
    np.testing.assert_array_equal(bg["y"], bw["y"])
    np.testing.assert_array_equal(mg, mw)


def test_custom_transform_falls_back(handle):
    class Scaled(_PlainDataset):
        def transform_train(self, data, labels):
            return {"x": data * 2.0, "y": labels}

    loader = RoundLoader(handle, Scaled(), n_lanes=2, use_native=True)
    assert not loader._native_train          # hook present -> numpy path
    assert loader._native_eval               # test hook untouched
    rb = next(iter(loader.epoch_rounds(loader.plan(2, 2, 16), 0)))
    raw, _ = handle.doc_range("train", 0, 1)
    np.testing.assert_allclose(rb.batch["x"][0, 0, 0], raw[0] * 2.0)


def test_prefetch_preserves_sequence(handle):
    ds = _PlainDataset()
    loader = RoundLoader(handle, ds, n_lanes=2, seed=3)
    plan = loader.plan(3, 2, 16)
    direct = list(loader.epoch_rounds(plan, 1))
    fetched = list(prefetch_rounds(loader.epoch_rounds(plan, 1), depth=2))
    assert len(direct) == len(fetched)
    for d, f in zip(direct, fetched):
        np.testing.assert_array_equal(d.batch["x"], f.batch["x"])
        np.testing.assert_array_equal(d.rngs, f.rngs)


def test_prefetch_propagates_errors():
    def gen():
        yield from ()
        raise RuntimeError("assembly failed")

    with pytest.raises(RuntimeError, match="assembly failed"):
        list(prefetch_rounds(gen()))


def test_assemble_round_cycle_pads():
    # 5 samples cycled into 2 steps x 4 slots: [0,1,2,3,4,0,1,2]
    x = np.arange(5, dtype=np.float32).reshape(5, 1)
    y = np.arange(5, dtype=np.int64)
    xo, yo, sm, stm, wm = native.assemble_round(
        x, y, np.array([0]), np.array([0]), np.array([5]), np.array([2]),
        W=2, S=2, B=4)
    np.testing.assert_array_equal(
        xo[0].ravel(), [0, 1, 2, 3, 4, 0, 1, 2])
    np.testing.assert_array_equal(
        yo[0].ravel(), [0, 1, 2, 3, 4, 0, 1, 2])
    np.testing.assert_array_equal(sm[0].ravel(), [1, 1, 1, 1, 1, 0, 0, 0])
    np.testing.assert_array_equal(stm, [[1, 1], [0, 0]])
    np.testing.assert_array_equal(wm, [1, 0])
    assert not xo[1].any() and not yo[1].any()


def test_failed_native_build_warns_once_and_falls_back(monkeypatch, caplog):
    """A host without a working compiler keeps the numpy path, but says
    so: the build error is logged once at warning level, not swallowed."""
    import logging
    import subprocess

    from kubeml_tpu import native

    def no_compiler():
        raise subprocess.CalledProcessError(
            1, ["g++"], stderr=b"g++: fatal error: no input files")

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_failed", False)
    monkeypatch.setattr(native, "_SO", native._SO + ".missing")
    monkeypatch.setattr(native, "_build", no_compiler)
    with caplog.at_level(logging.WARNING, logger="kubeml_tpu.native"):
        assert native.available() is False
        assert native.available() is False   # second probe: no new attempt
    warnings = [r for r in caplog.records
                if r.name == "kubeml_tpu.native"]
    assert len(warnings) == 1
    assert "no input files" in warnings[0].getMessage()
    assert "numpy path" in warnings[0].getMessage()
