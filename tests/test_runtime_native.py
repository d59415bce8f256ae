"""Native (C++) recordio codec + prefetch loader vs the pure-Python path.

Reference analog: recordio round-trip tests backing go/master task dispatch
and the DataProvider double-buffer tests (gserver/tests).
"""

import os
import pickle
import struct
import zlib

import numpy as np
import pytest

from paddle_tpu.runtime import loader as rt_loader
from paddle_tpu.runtime import native, recordio


def _records(n):
    return [{"i": i, "x": list(range(i % 5))} for i in range(n)]


@pytest.fixture
def rio_file(tmp_path):
    path = str(tmp_path / "data.rio")
    recordio.write_records(path, _records(257), chunk_records=50)
    return path


class TestNativeCodec:
    def test_native_lib_builds(self):
        assert native.get() is not None, "g++ build of recordio.cc failed"

    def test_library_is_keyed_by_source_and_compiler_line(
            self, tmp_path, monkeypatch):
        """A leftover binary is never loaded: the file name carries a
        hash of recordio.cc and the compiler line (a copied tree
        rewrites mtimes, so mtimes prove nothing); a build that fails
        is logged once, loudly, and get() answers None."""
        import shutil
        assert native.get() is not None
        so = native._so_path()
        assert os.path.exists(so) and so.endswith(".so")
        # another source or another flag set = another file name
        src2 = tmp_path / "recordio.cc"
        shutil.copy(native._SRC, src2)
        with open(src2, "a") as f:
            f.write("// changed\n")
        monkeypatch.setattr(native, "_SRC", str(src2))
        assert native._so_path() != so
        monkeypatch.setattr(native, "_SRC", os.path.join(
            os.path.dirname(so), "recordio.cc"))
        monkeypatch.setattr(native, "_CXX", native._CXX + ["-O0"])
        assert native._so_path() != so
        # a failing build: loud, once, pure-Python takes over
        monkeypatch.setattr(native, "_CXX", ["g++", "--no-such-flag"])
        monkeypatch.setattr(native, "_DIR", str(tmp_path))
        monkeypatch.setattr(native, "_tried", False)
        monkeypatch.setattr(native, "_lib", None)
        import logging
        seen = []
        handler = logging.Handler(level=logging.ERROR)
        handler.emit = lambda rec: seen.append(rec.getMessage())
        native.log.addHandler(handler)
        try:
            assert native.get() is None
            assert native.get() is None
        finally:
            native.log.removeHandler(handler)
        assert len(seen) == 1, seen
        assert "NATIVE RECORDIO UNAVAILABLE" in seen[0]
        assert "pure-Python codec takes over" in seen[0]
        assert not list(tmp_path.glob("*.so*"))

    def test_roundtrip(self, rio_file):
        got = list(recordio.read_records(rio_file))
        assert got == _records(257)

    def test_chunk_offsets_match_python_scan(self, rio_file):
        native_offsets = recordio.chunk_offsets(rio_file)
        # force the python scan path
        lib, native._lib = native._lib, None
        try:
            py_offsets = recordio.chunk_offsets(rio_file)
        finally:
            native._lib = lib
        assert native_offsets == py_offsets
        assert len(native_offsets) == 6          # ceil(257/50)
        assert sum(n for _, n in native_offsets) == 257

    def test_python_written_file_native_read(self, tmp_path):
        """Cross-compat: python writer ↔ native reader and vice versa."""
        path = str(tmp_path / "py.rio")
        lib, native._lib = native._lib, None
        try:
            recordio.write_records(path, _records(10), chunk_records=4)
        finally:
            native._lib = lib
        assert list(recordio.read_records(path)) == _records(10)

    def test_native_written_file_python_read(self, rio_file):
        lib, native._lib = native._lib, None
        try:
            got = list(recordio.read_records(rio_file))
        finally:
            native._lib = lib
        assert got == _records(257)

    def test_corrupt_crc_detected(self, rio_file):
        with open(rio_file, "r+b") as f:
            f.seek(recordio.HEADER.size + 10)   # inside first payload
            f.write(b"\xff\xff")
        with pytest.raises(IOError):
            list(recordio.read_chunk(rio_file, 0))


class TestPrefetchLoader:
    def test_yields_all_records(self, rio_file):
        got = list(rt_loader.PrefetchLoader(rio_file, num_threads=3))
        # multi-threaded chunk reads may interleave chunk order
        key = lambda r: r["i"]
        assert sorted(got, key=key) == _records(257)

    def test_single_thread_preserves_order(self, rio_file):
        got = list(rt_loader.PrefetchLoader(rio_file, num_threads=1))
        assert got == _records(257)

    def test_shuffle_changes_chunk_order(self, rio_file):
        a = list(rt_loader.PrefetchLoader(rio_file, shuffle=True, seed=1,
                                          num_threads=1))
        b = list(rt_loader.PrefetchLoader(rio_file, shuffle=True, seed=2,
                                          num_threads=1))
        assert sorted(r["i"] for r in a) == list(range(257))
        assert [r["i"] for r in a] != [r["i"] for r in b]

    def test_python_fallback(self, rio_file):
        lib, native._lib = native._lib, None
        try:
            got = list(rt_loader.PrefetchLoader(rio_file, num_threads=2))
        finally:
            native._lib = lib
        assert sorted(r["i"] for r in got) == list(range(257))

    def test_reader_creator_restartable(self, rio_file):
        reader = rt_loader.reader_creator(rio_file, num_threads=1)
        assert len(list(reader())) == 257
        assert len(list(reader())) == 257       # second epoch works


class TestDenseBatchLoader:
    """Native whole-batch assembly over fixed-layout raw records
    (loader_next_batch + DenseBatchLoader + dense_batch_reader)."""

    def _write(self, tmp_path, n=300, dim=5):
        import numpy as np
        from paddle_tpu.runtime import loader as rl
        path = str(tmp_path / "dense.rio")
        rng = np.random.RandomState(0)
        feats = rng.rand(n, dim).astype(np.float32)
        labels = rng.randint(0, 7, n).astype(np.int32)
        count = rl.write_dense(path, zip(feats, labels), dim,
                               chunk_records=64)
        assert count == n
        return path, feats, labels

    def test_roundtrip_batches(self, tmp_path):
        import numpy as np
        from paddle_tpu.runtime import loader as rl
        path, feats, labels = self._write(tmp_path)
        # num_threads=1: exact file order (multi-thread decode
        # interleaves records across chunks by design)
        reader = rl.dense_batch_reader(path, 5, 128, num_threads=1)
        got_f, got_l = [], []
        sizes = []
        for f, l in reader():
            sizes.append(len(l))
            got_f.append(np.array(f))
            got_l.append(np.array(l))
        assert sizes == [128, 128, 44]          # short tail kept
        np.testing.assert_array_equal(np.concatenate(got_f), feats)
        np.testing.assert_array_equal(np.concatenate(got_l), labels)

    def test_python_fallback_matches(self, tmp_path, monkeypatch):
        import numpy as np
        from paddle_tpu.runtime import loader as rl, native
        path, feats, labels = self._write(tmp_path)
        native_batches = [np.array(l)
                          for _, l in rl.dense_batch_reader(
                              path, 5, 64, num_threads=1)()]
        monkeypatch.setattr(native, "get", lambda: None)
        py_batches = [np.array(l)
                      for _, l in rl.dense_batch_reader(
                          path, 5, 64, num_threads=1)()]
        assert len(native_batches) == len(py_batches)
        for a, b in zip(native_batches, py_batches):
            np.testing.assert_array_equal(a, b)

    def test_size_mismatch_rejected(self, tmp_path):
        from paddle_tpu.runtime import loader as rl, recordio
        path = str(tmp_path / "bad.rio")
        recordio.write_records(path, [b"abc", b"defgh"], raw=True)
        with pytest.raises(IOError):
            list(rl.DenseBatchLoader(path, 3, 2))

    def test_partial_batch_survives_mid_batch_error(self, tmp_path):
        """A mid-batch size mismatch must not discard the records already
        assembled: they are yielded first, the error surfaces on the next
        native call (round-4 advisor finding)."""
        from paddle_tpu.runtime import loader as rl, recordio
        path = str(tmp_path / "bad2.rio")
        recordio.write_records(path, [b"abc", b"xyz", b"defgh"], raw=True)
        got = []
        with pytest.raises(IOError, match="partial batch of 2"):
            for b in rl.DenseBatchLoader(path, 3, 4):
                got.append(b.copy())
        assert len(got) == 1 and len(got[0]) == 2
        assert bytes(got[0][0]) + bytes(got[0][1]) in (b"abcxyz", b"xyzabc")

    def test_drop_last(self, tmp_path):
        from paddle_tpu.runtime import loader as rl
        path, feats, labels = self._write(tmp_path, n=100)
        sizes = [len(l) for _, l in
                 rl.dense_batch_reader(path, 5, 64, drop_last=True)()]
        assert sizes == [64]

    def test_trains_through_sgd(self, tmp_path):
        """End-to-end: the native batch path feeds trainer.SGD via the
        pre-batched DataFeeder fast path (no per-sample assembly)."""
        import numpy as np
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu import layer
        from paddle_tpu.runtime import loader as rl

        dim, classes, n = 12, 3, 192
        rng = np.random.RandomState(0)
        protos = rng.randn(classes, dim).astype(np.float32)
        labels = rng.randint(0, classes, n).astype(np.int32)
        feats = protos[labels] + rng.randn(n, dim).astype(np.float32) * 0.2
        path = str(tmp_path / "train.rio")
        rl.write_dense(path, zip(feats, labels), dim, chunk_records=32)

        x = layer.data("x", paddle.data_type.dense_vector(dim))
        y = layer.data("y", paddle.data_type.integer_value(classes))
        out = layer.fc(x, classes, act=paddle.activation.Softmax(),
                       name="nb_fc")
        cost = layer.classification_cost(out, y, name="nb_cost")
        params = paddle.parameters.create(cost,
                                          paddle.utils.rng.KeySource(1))
        trainer = paddle.trainer.SGD(
            cost=cost, parameters=params,
            update_equation=paddle.optimizer.Momentum(momentum=0.9,
                                                      learning_rate=0.5))
        costs = []
        trainer.train(
            reader=rl.dense_batch_reader(path, dim, 64, drop_last=True),
            num_passes=6,
            event_handler=lambda e: costs.append(e.cost) if isinstance(
                e, paddle.event.EndIteration) else None)
        assert costs[-1] < costs[0] * 0.5, costs
