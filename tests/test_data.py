"""Data I/O stack tests: recordio, mx.io iterators, gluon.data, mx.image.

Mirrors the reference's ``tests/python/unittest/test_recordio.py``,
``test_io.py``, ``test_gluon_data.py`` coverage (SURVEY.md §4 test strategy).
"""
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio as rio
from mxnet_tpu.gluon.data import (ArrayDataset, SimpleDataset, DataLoader,
                                  BatchSampler, SequentialSampler,
                                  RandomSampler, IntervalSampler,
                                  FilterSampler, RecordFileDataset)
from mxnet_tpu.gluon.data.vision import (MNIST, FashionMNIST, CIFAR10,
                                         ImageRecordDataset, transforms as T)


@pytest.fixture
def rec_file(tmp_path):
    rec = str(tmp_path / "data.rec")
    idx = str(tmp_path / "data.idx")
    w = rio.MXIndexedRecordIO(idx, rec, "w")
    rng = onp.random.RandomState(0)
    for i in range(8):
        img = (rng.rand(20, 24, 3) * 255).astype(onp.uint8)
        w.write_idx(i, rio.pack_img(rio.IRHeader(0, float(i % 3), i, 0), img))
    w.close()
    return rec


class TestRecordIO:
    def test_sequential_roundtrip(self, tmp_path):
        path = str(tmp_path / "seq.rec")
        payloads = [bytes([i]) * (i * 7 + 1) for i in range(10)]
        with rio.MXRecordIO(path, "w") as w:
            for p in payloads:
                w.write(p)
        r = rio.MXRecordIO(path, "r")
        got = []
        while True:
            s = r.read()
            if s is None:
                break
            got.append(s)
        assert got == payloads

    def test_indexed_random_access(self, rec_file):
        idx = rec_file[:-4] + ".idx"
        r = rio.MXIndexedRecordIO(idx, rec_file, "r")
        assert r.keys == list(range(8))
        h, img = rio.unpack_img(r.read_idx(5))
        assert float(h.label) == 2.0
        assert img.shape == (20, 24, 3)

    def test_pack_vector_label(self):
        h = rio.IRHeader(0, [1.0, 2.0, 3.0], 7, 0)
        s = rio.pack(h, b"payload")
        h2, payload = rio.unpack(s)
        assert h2.flag == 3
        onp.testing.assert_allclose(onp.asarray(h2.label), [1, 2, 3])
        assert payload == b"payload"


class TestIO:
    def test_ndarrayiter_pad_and_discard(self):
        data = onp.arange(50, dtype=onp.float32).reshape(25, 2)
        it = mx.io.NDArrayIter(data, onp.zeros(25), batch_size=10,
                               last_batch_handle="pad")
        batches = list(it)
        assert len(batches) == 3 and batches[-1].pad == 5
        it = mx.io.NDArrayIter(data, onp.zeros(25), batch_size=10,
                               last_batch_handle="discard")
        assert len(list(it)) == 2

    def test_ndarrayiter_provide(self):
        it = mx.io.NDArrayIter(onp.zeros((4, 3)), onp.zeros(4), batch_size=2)
        assert it.provide_data[0].shape == (2, 3)
        assert it.provide_data[0].name == "data"
        assert it.provide_label[0].name == "softmax_label"

    def test_resize_iter(self):
        it = mx.io.NDArrayIter(onp.zeros((6, 2)), onp.zeros(6), batch_size=2)
        r = mx.io.ResizeIter(it, 7)
        assert len(list(r)) == 7

    def test_prefetching_iter(self):
        it = mx.io.NDArrayIter(onp.arange(12, dtype=onp.float32).reshape(6, 2),
                               onp.zeros(6), batch_size=2)
        p = mx.io.PrefetchingIter(it)
        batches = list(p)
        assert len(batches) == 3
        p.reset()
        assert len(list(p)) == 3

    def test_csviter(self, tmp_path):
        data_csv = str(tmp_path / "d.csv")
        onp.savetxt(data_csv, onp.arange(12).reshape(4, 3), delimiter=",")
        it = mx.io.CSVIter(data_csv=data_csv, data_shape=(3,), batch_size=2)
        b = next(iter(it))
        assert b.data[0].shape == (2, 3)


class TestDataset:
    def test_array_dataset(self):
        ds = ArrayDataset(onp.arange(10), onp.arange(10) * 2)
        assert len(ds) == 10
        a, b = ds[3]
        assert int(a) == 3 and int(b) == 6

    def test_transform_first(self):
        ds = ArrayDataset(onp.arange(4, dtype=onp.float32), onp.arange(4))
        ds2 = ds.transform_first(lambda x: x * 10)
        x, y = ds2[2]
        assert float(x) == 20.0 and int(y) == 2

    def test_filter_shard_take(self):
        ds = SimpleDataset(list(range(10)))
        assert len(ds.filter(lambda x: x % 2 == 0)) == 5
        assert list(ds.shard(3, 0)[i] for i in range(len(ds.shard(3, 0)))) == [0, 3, 6, 9]
        assert len(ds.take(4)) == 4

    def test_record_file_dataset(self, rec_file):
        ds = RecordFileDataset(rec_file)
        assert len(ds) == 8
        h, _ = rio.unpack(ds[2])
        assert float(h.label) == 2.0

    def test_image_record_dataset(self, rec_file):
        ds = ImageRecordDataset(rec_file)
        img, label = ds[4]
        assert img.shape == (20, 24, 3)
        assert label == 1.0


class TestSampler:
    def test_sequential_random(self):
        assert list(SequentialSampler(5)) == [0, 1, 2, 3, 4]
        assert sorted(RandomSampler(5)) == [0, 1, 2, 3, 4]

    def test_batch_sampler(self):
        bs = BatchSampler(SequentialSampler(7), 3, "keep")
        assert [len(b) for b in bs] == [3, 3, 1]
        bs = BatchSampler(SequentialSampler(7), 3, "discard")
        assert [len(b) for b in bs] == [3, 3]
        bs = BatchSampler(SequentialSampler(7), 3, "rollover")
        assert [len(b) for b in bs] == [3, 3]
        assert [len(b) for b in bs] == [3, 3]  # rolled-over 1 + first 2

    def test_interval_filter(self):
        assert list(IntervalSampler(6, 2)) == [0, 2, 4, 1, 3, 5]
        ds = SimpleDataset(list(range(6)))
        assert list(FilterSampler(lambda x: x > 3, ds)) == [4, 5]


class TestDataLoader:
    def test_basic(self):
        ds = ArrayDataset(onp.random.rand(20, 3).astype(onp.float32),
                          onp.arange(20, dtype=onp.float32))
        dl = DataLoader(ds, batch_size=6, last_batch="keep")
        shapes = [x.shape for x, _ in dl]
        assert shapes == [(6, 3), (6, 3), (6, 3), (2, 3)]
        assert len(dl) == 4

    def test_workers_match_serial(self):
        ds = ArrayDataset(onp.arange(30, dtype=onp.float32).reshape(10, 3),
                          onp.arange(10, dtype=onp.float32))
        serial = [x.asnumpy() for x, _ in DataLoader(ds, batch_size=5)]
        threaded = [x.asnumpy() for x, _ in DataLoader(ds, batch_size=5,
                                                       num_workers=3)]
        for a, b in zip(serial, threaded):
            onp.testing.assert_array_equal(a, b)

    def test_vision_pipeline(self):
        ds = MNIST(train=True, synthetic=32).transform_first(
            T.Compose([T.ToTensor(), T.Normalize(0.13, 0.31)]))
        xb, yb = next(iter(DataLoader(ds, batch_size=8, shuffle=True)))
        assert xb.shape == (8, 1, 28, 28)
        assert str(xb.dtype) == "float32"

    def test_cifar_synthetic(self):
        ds = CIFAR10(train=False, synthetic=16)
        x, y = ds[0]
        assert x.shape == (32, 32, 3)
        assert 0 <= y < 10


class TestImage:
    def test_imdecode_imencode_roundtrip(self):
        img = (onp.random.rand(16, 16, 3) * 255).astype(onp.uint8)
        enc = mx.image.imencode(img, img_fmt=".png")
        dec = mx.image.imdecode(enc)
        onp.testing.assert_array_equal(dec.asnumpy(), img)

    def test_resize_crop(self):
        img = mx.nd.array((onp.random.rand(20, 30, 3) * 255).astype(onp.uint8),
                          dtype="uint8")
        assert mx.image.imresize(img, 8, 10).shape == (10, 8, 3)
        assert mx.image.resize_short(img, 10).shape == (10, 15, 3)
        out, _ = mx.image.center_crop(img, (12, 12))
        assert out.shape == (12, 12, 3)
        out, _ = mx.image.random_crop(img, (8, 8))
        assert out.shape == (8, 8, 3)

    def test_augmenter_list(self):
        augs = mx.image.CreateAugmenter((3, 16, 16), rand_crop=True,
                                        rand_mirror=True, mean=True, std=True)
        img = mx.nd.array((onp.random.rand(20, 20, 3) * 255).astype(onp.uint8),
                          dtype="uint8")
        for a in augs:
            img = a(img)
        assert img.shape == (16, 16, 3)

    def test_image_iter(self, rec_file):
        it = mx.image.ImageIter(batch_size=4, data_shape=(3, 16, 16),
                                path_imgrec=rec_file, shuffle=True)
        b = it.next()
        assert b.data[0].shape == (4, 3, 16, 16)
        assert b.label[0].shape == (4,)

    def test_det_iter(self, tmp_path):
        rec = str(tmp_path / "det.rec")
        idx = str(tmp_path / "det.idx")
        w = rio.MXIndexedRecordIO(idx, rec, "w")
        rng = onp.random.RandomState(1)
        for i in range(4):
            img = (rng.rand(20, 20, 3) * 255).astype(onp.uint8)
            # label: [header_w=2, obj_w=5, cls, xmin, ymin, xmax, ymax]
            label = [2, 5, 1, 0.1, 0.1, 0.6, 0.7]
            w.write_idx(i, rio.pack_img(rio.IRHeader(0, label, i, 0), img))
        w.close()
        it = mx.image.ImageDetIter(batch_size=2, data_shape=(3, 16, 16),
                                   path_imgrec=rec, rand_mirror=True)
        b = it.next()
        assert b.data[0].shape == (2, 3, 16, 16)
        assert b.label[0].shape[0] == 2 and b.label[0].shape[2] == 5


def _dev_id(arr):
    return list(arr._data.devices())[0].id


class TestMultiWorkerIter:
    """Satellites: ordering, last_batch modes, explicit prefetch, early-
    break cleanup, timeout raise (ISSUE 3)."""

    def _ds(self, n=17):
        return ArrayDataset(onp.arange(3 * n, dtype=onp.float32).reshape(n, 3),
                            onp.arange(n, dtype=onp.float32))

    def test_order_matches_serial_across_worker_counts(self):
        ds = self._ds()
        serial = [x.asnumpy() for x, _ in DataLoader(ds, batch_size=4,
                                                     last_batch="keep")]
        for nw in (1, 2, 4):
            threaded = [x.asnumpy() for x, _ in
                        DataLoader(ds, batch_size=4, last_batch="keep",
                                   num_workers=nw)]
            assert len(threaded) == len(serial)
            for a, b in zip(serial, threaded):
                onp.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("last_batch,want", [("keep", 5),
                                                 ("discard", 4),
                                                 ("rollover", 4)])
    def test_last_batch_modes_with_workers(self, last_batch, want):
        dl = DataLoader(self._ds(17), batch_size=4, last_batch=last_batch,
                        num_workers=2)
        assert len([b for b in dl]) == want

    def test_explicit_prefetch_honored(self):
        it = iter(DataLoader(self._ds(), batch_size=4, num_workers=4,
                             prefetch=1))
        assert it._prefetch == 1  # not silently raised to 2*num_workers
        it2 = iter(DataLoader(self._ds(), batch_size=4, num_workers=4))
        assert it2._prefetch == 8  # default stays 2*num_workers
        it.shutdown()
        it2.shutdown()

    def test_early_break_shuts_down_executor(self):
        import gc
        dl = DataLoader(self._ds(), batch_size=2, num_workers=2)
        it = iter(dl)
        next(it)  # abandon the epoch after one batch
        executor = it._executor
        del it  # queued work items hold a bound-method cycle → needs gc
        gc.collect()
        assert executor._shutdown

    def test_timeout_raises_with_batch_index(self):
        import time as _time

        class SlowDataset(SimpleDataset):
            def __getitem__(self, idx):
                _time.sleep(1.5)
                return super().__getitem__(idx)

        dl = DataLoader(SlowDataset(list(range(8))), batch_size=2,
                        num_workers=1, timeout=0.2)
        with pytest.raises(mx.MXNetError, match="batch 0"):
            next(iter(dl))

    def test_worker_error_propagates_and_cleans_up(self):
        class BadDataset(SimpleDataset):
            def __getitem__(self, idx):
                raise ValueError("boom")

        it = iter(DataLoader(BadDataset(list(range(8))), batch_size=2,
                             num_workers=1))
        with pytest.raises(ValueError, match="boom"):
            next(it)
        assert it._executor._shutdown


class TestDevicePrefetch:
    """Tentpole: device-resident / pre-sharded prefetched batches
    (ISSUE 3).  Runs on the 8-device virtual CPU platform."""

    def _ds(self, n=16):
        return ArrayDataset(onp.arange(3 * n, dtype=onp.float32).reshape(n, 3),
                            onp.arange(n, dtype=onp.float32))

    def _serial(self, ds, bs=4):
        return [x.asnumpy() for x, _ in DataLoader(ds, batch_size=bs)]

    def test_batches_device_resident_and_bit_identical(self):
        ds = self._ds()
        ref = self._serial(ds)
        dl = DataLoader(ds, batch_size=4, device=mx.Context("cpu", 1))
        got = list(dl)
        assert len(got) == len(ref)
        for (x, y), r in zip(got, ref):
            assert _dev_id(x) == 1 and _dev_id(y) == 1
            onp.testing.assert_array_equal(x.asnumpy(), r)

    def test_multiworker_device_order_and_residency(self):
        ds = self._ds()
        ref = self._serial(ds)
        for dp in (2, 8):  # ring path (2 < prefetch) and worker-place path
            dl = DataLoader(ds, batch_size=4, num_workers=2,
                            device=mx.Context("cpu", 2), device_prefetch=dp)
            for (x, _), r in zip(dl, ref):
                assert _dev_id(x) == 2
                onp.testing.assert_array_equal(x.asnumpy(), r)

    def test_env_zero_restores_synchronous_path(self, monkeypatch):
        monkeypatch.setenv("MXNET_DEVICE_PREFETCH", "0")
        ds = self._ds()
        dl = DataLoader(ds, batch_size=4, device=mx.Context("cpu", 1),
                        device_prefetch=4)
        it = iter(dl)
        from mxnet_tpu.gluon.data.dataloader import DevicePrefetchIter
        assert isinstance(it, DevicePrefetchIter)
        assert it._depth == 0 and it._thread is None  # no ring, no thread
        for (x, _), r in zip(it, self._serial(ds)):
            assert _dev_id(x) == 1  # placement still honored
            onp.testing.assert_array_equal(x.asnumpy(), r)

    def test_sharded_placement_over_device_list(self):
        ctxs = [mx.Context("cpu", i) for i in range(4)]
        dl = DataLoader(self._ds(), batch_size=8, device=ctxs)
        xb, yb = next(iter(dl))
        sh = xb._data.sharding
        assert len(sh.device_set) == 4 and not sh.is_fully_replicated
        shapes = {tuple(s.data.shape) for s in xb._data.addressable_shards}
        assert shapes == {(2, 3)}

    def test_split_and_load_uses_resident_shards(self):
        from mxnet_tpu.gluon.utils import split_and_load
        ctxs = [mx.Context("cpu", i) for i in range(4)]
        xb, _ = next(iter(DataLoader(self._ds(), batch_size=8, device=ctxs)))
        full = xb.asnumpy()
        parts = split_and_load(xb, ctxs)
        for i, p in enumerate(parts):
            assert _dev_id(p) == i
            onp.testing.assert_array_equal(p.asnumpy(), full[2 * i:2 * i + 2])

    def test_partial_tail_batch_replicates(self):
        ctxs = [mx.Context("cpu", i) for i in range(4)]
        batches = list(DataLoader(self._ds(14), batch_size=4, device=ctxs,
                                  last_batch="keep"))
        tail = batches[-1][0]
        assert tail.shape == (2, 3)  # 14 = 3*4 + 2
        assert tail._data.sharding.is_fully_replicated

    def test_early_break_cleans_both_layers(self):
        dl = DataLoader(self._ds(), batch_size=2, num_workers=2,
                        device=mx.Context("cpu", 1), device_prefetch=1)
        it = iter(dl)
        next(it)
        inner = it._source
        it.close()
        assert inner._closed and inner._executor._shutdown

    def test_explicit_sharding_object(self):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        mesh = Mesh(onp.array(jax.devices()[:2]), ("dp",))
        sh = NamedSharding(mesh, PartitionSpec("dp"))
        xb, _ = next(iter(DataLoader(self._ds(), batch_size=4, device=sh)))
        assert xb._data.sharding == sh

    def test_standalone_iter_over_plain_iterable(self):
        from mxnet_tpu.gluon.data import DevicePrefetchIter
        src = [onp.full((2, 2), i, onp.float32) for i in range(5)]
        out = list(DevicePrefetchIter(iter(src), mx.Context("cpu", 3),
                                      depth=2))
        assert len(out) == 5
        for i, x in enumerate(out):
            assert _dev_id(x) == 3
            onp.testing.assert_array_equal(x.asnumpy(), src[i])

    def test_source_error_propagates(self):
        from mxnet_tpu.gluon.data import DevicePrefetchIter

        def bad():
            yield onp.zeros((2, 2), onp.float32)
            raise RuntimeError("pipeline broke")

        it = DevicePrefetchIter(bad(), mx.Context("cpu", 0), depth=2)
        next(it)
        with pytest.raises(RuntimeError, match="pipeline broke"):
            next(it)
        with pytest.raises(StopIteration):  # terminal, must not block
            next(it)

    def test_next_after_exhaustion_raises_not_hangs(self):
        from mxnet_tpu.gluon.data import DevicePrefetchIter
        it = DevicePrefetchIter(iter([onp.zeros((2,), onp.float32)]),
                                mx.Context("cpu", 0), depth=2)
        assert len(list(it)) == 1
        for _ in range(2):  # repeated next() past the single end marker
            with pytest.raises(StopIteration):
                next(it)

    def test_io_prefetching_iter_producer_error_propagates(self):
        class BadIter(mx.io.DataIter):
            def next(self):
                raise RuntimeError("decode failed")

        p = mx.io.PrefetchingIter(BadIter(batch_size=2),
                                  device=mx.Context("cpu", 1))
        with pytest.raises(RuntimeError, match="decode failed"):
            p.next()

    def test_io_env_zero_keeps_hostside_thread_without_device(self,
                                                              monkeypatch):
        monkeypatch.setenv("MXNET_DEVICE_PREFETCH", "0")
        it = mx.io.NDArrayIter(
            onp.arange(12, dtype=onp.float32).reshape(6, 2), onp.zeros(6),
            batch_size=2)
        p = mx.io.PrefetchingIter(it)  # no device: escape hatch inert
        assert not p._sync and p._thread is not None
        assert len(list(p)) == 3

    def test_io_prefetching_iter_device(self):
        it = mx.io.NDArrayIter(
            onp.arange(12, dtype=onp.float32).reshape(6, 2), onp.zeros(6),
            batch_size=2)
        p = mx.io.PrefetchingIter(it, device=mx.Context("cpu", 5))
        bs = list(p)
        assert len(bs) == 3
        assert all(_dev_id(b.data[0]) == 5 for b in bs)
        p.reset()
        assert len(list(p)) == 3

    def test_io_prefetching_iter_env_zero_sync(self, monkeypatch):
        monkeypatch.setenv("MXNET_DEVICE_PREFETCH", "0")
        it = mx.io.NDArrayIter(
            onp.arange(12, dtype=onp.float32).reshape(6, 2), onp.zeros(6),
            batch_size=2)
        p = mx.io.PrefetchingIter(it, device=mx.Context("cpu", 4))
        assert p._sync and p._thread is None
        bs = list(p)
        assert len(bs) == 3 and all(_dev_id(b.data[0]) == 4 for b in bs)

    def test_estimator_wraps_epoch_iterator(self, monkeypatch):
        import jax
        from mxnet_tpu import context, gluon
        from mxnet_tpu.gluon.contrib.estimator import Estimator
        from mxnet_tpu.gluon.data.dataloader import DevicePrefetchIter
        net = gluon.nn.Dense(2, in_units=3)
        data = [(onp.ones((2, 3), onp.float32), onp.zeros((2, 2), onp.float32))]
        # accelerator context: ring engaged (no accelerator here, so the
        # host devices stand in for it)
        monkeypatch.setattr(context, "_ACCEL_CACHE", jax.local_devices())
        est = Estimator(net, gluon.loss.L2Loss(),
                        context=mx.Context("tpu", 0))
        it = est._prefetched(data)
        assert isinstance(it, DevicePrefetchIter)
        batches = list(it)
        assert len(batches) == 1 and isinstance(batches[0][0], mx.nd.NDArray)
        # host context: inert, plain iteration
        est2 = Estimator(net, gluon.loss.L2Loss(),
                         context=mx.Context("cpu", 0))
        assert not isinstance(est2._prefetched(data), DevicePrefetchIter)

    def test_nd_array_ctx_single_hop(self):
        a = mx.nd.array(onp.arange(6, dtype=onp.int64), ctx=mx.Context("cpu", 3))
        assert str(a.dtype) == "int32" and _dev_id(a) == 3  # canonicalized
        b = mx.nd.array([1.5, 2.5], ctx=mx.Context("cpu", 2))
        assert str(b.dtype) == "float32" and _dev_id(b) == 2


class TestInputPipelineBenchSmoke:
    """The overlap measurement can't bit-rot: --smoke runs the h2d stage
    at tiny sizes with no PIL/native dependency (ISSUE 3 CI satellite)."""

    def test_smoke_mode_emits_overlap_rows(self, capsys):
        import json
        import benchmark.input_pipeline_bench as bench
        assert bench.main(["--smoke"]) == 0
        rows = [json.loads(l) for l in
                capsys.readouterr().out.strip().splitlines()]
        stages = {r["stage"] for r in rows}
        assert {"h2d_input_only", "h2d_compute_only", "h2d_step_sync",
                "h2d_step_overlap"} <= stages
        overlap = next(r for r in rows if r["stage"] == "h2d_step_overlap")
        assert overlap["ms_per_step"] > 0 and overlap["speedup_vs_sync"] > 0


class TestBatchify:
    def test_pad_variable_lengths(self):
        from mxnet_tpu.gluon.data import batchify
        seqs = [onp.arange(3), onp.arange(5), onp.arange(2)]
        out, lens = batchify.Pad(pad_val=-1, ret_length=True)(seqs)
        assert out.shape == (3, 5)
        onp.testing.assert_array_equal(lens.asnumpy(), [3, 5, 2])
        onp.testing.assert_array_equal(out.asnumpy()[2], [0, 1, -1, -1, -1])

    def test_tuple_composition_with_loader(self):
        from mxnet_tpu.gluon.data import ArrayDataset, DataLoader, batchify
        seqs = [onp.arange(n, dtype=onp.float32) for n in (2, 4, 3, 5)]
        labels = onp.arange(4, dtype=onp.float32)
        ds = ArrayDataset(seqs, labels)
        fn = batchify.Tuple(batchify.Pad(), batchify.Stack())
        xb, yb = next(iter(DataLoader(ds, batch_size=4, batchify_fn=fn)))
        assert xb.shape == (4, 5)
        assert yb.shape == (4,)

    def test_stack_casts_64bit(self):
        from mxnet_tpu.gluon.data import batchify
        out = batchify.Stack()([onp.array([1, 2]), onp.array([3, 4])])
        assert str(out.dtype) in ("int32", "int64")


class TestIteratorConcurrency:
    """Regression net for the TL004 lock discipline (ISSUE 5 satellite):
    hammer concurrent ``next()`` + ``shutdown()``/``close()`` from
    multiple threads — no deadlock, no IndexError off the shared deque,
    no leaked executor, no consumer stranded in ``queue.get()``."""

    def _consume(self, it, errs):
        from concurrent.futures import CancelledError
        from mxnet_tpu.base import MXNetError
        try:
            while True:
                try:
                    next(it)
                except StopIteration:
                    return
        except (CancelledError, MXNetError):
            return  # a future cancelled / timed out by shutdown is fine
        except BaseException as e:  # noqa: BLE001 — recorded for assert
            errs.append(e)

    def _hammer(self, make_iter, closer, rounds=12, consumers=2):
        import threading
        import time
        for i in range(rounds):
            it = make_iter()
            errs = []
            threads = [threading.Thread(target=self._consume,
                                        args=(it, errs), daemon=True)
                       for _ in range(consumers)]
            for t in threads:
                t.start()
            # vary the interleaving: sometimes mid-epoch, sometimes late
            time.sleep(0.001 * (i % 4))
            closer(it)
            for t in threads:
                t.join(timeout=10)
            assert not any(t.is_alive() for t in threads), \
                f"round {i}: consumer thread deadlocked after close"
            assert not errs, f"round {i}: {errs!r}"
            yield it

    def test_multiworker_next_vs_shutdown(self):
        ds = SimpleDataset(list(range(64)))
        def make():
            return iter(DataLoader(ds, batch_size=4, num_workers=2,
                                   prefetch=3))
        for it in self._hammer(make, lambda it: it.shutdown()):
            # no executor leak: the pool must be torn down
            assert it._executor._shutdown
            # ring closed: further next() terminates, never hangs
            with pytest.raises(StopIteration):
                next(it)

    # depth=1 is the tight case: a straggler batch can fill the single
    # queue slot between close()'s drain and the producer noticing
    # _stop, so the injected _END pill must evict-and-retry, never drop
    @pytest.mark.parametrize("depth", [1, 2])
    def test_device_prefetch_next_vs_close(self, depth):
        from mxnet_tpu.gluon.data.dataloader import DevicePrefetchIter

        def make():
            def src():
                for j in range(64):
                    yield onp.full((2,), j, onp.float32)
            return DevicePrefetchIter(src(), mx.Context("cpu", 0),
                                      depth=depth)

        for it in self._hammer(make, lambda it: it.close()):
            assert it._thread is None  # producer joined, not leaked
            with pytest.raises(StopIteration):
                next(it)

    def test_stacked_loader_close_midway(self):
        """DataLoader(num_workers>0, device=...) stacks the device ring
        over the worker pool; breaking out mid-epoch must unwind BOTH
        layers from __del__/close without deadlock."""
        from mxnet_tpu.gluon.data.dataloader import DevicePrefetchIter
        ds = SimpleDataset(list(range(48)))
        for _ in range(6):
            loader = DataLoader(ds, batch_size=4, num_workers=2,
                                device=mx.Context("cpu", 0),
                                device_prefetch=1, prefetch=4)
            it = iter(loader)
            assert isinstance(it, DevicePrefetchIter)
            next(it)
            inner = it._source
            it.close()
            assert inner._executor._shutdown
