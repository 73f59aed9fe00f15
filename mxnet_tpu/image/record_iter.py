"""ImageRecordIter — threaded RecordIO decode+augment pipeline.

Reference: src/io/iter_image_recordio_2.cc (ImageRecordIOParser2 :50 —
chunked reads + OMP-parallel JPEG decode :138-171 + shuffle :173-190)
feeding BatchLoader + PrefetcherIter.

Python/TPU analog: worker THREADS decode+augment (PIL releases the GIL),
a bounded queue prefetches assembled batches, device transfer is async.

When the native IO plane is built (`make -C native` →
native/build/libmxnet_tpu_io.so, sources native/record_iter.cc +
native/image_decode.cc), ImageRecordIter transparently selects it: OMP
JPEG decode + bounded prefetch queue in C++, the reference's host hot
loop.  Set MXNET_TPU_NATIVE_IO=0 to force the pure-Python path.
"""
from __future__ import annotations

import logging
import queue
import random
import threading

import numpy as np

from ..io.io import DataBatch, DataDesc, DataIter
from ..ndarray.ndarray import array as nd_array
from .. import recordio
from .image import CreateAugmenter, imdecode


class ImageRecordIter(DataIter):
    """reference io.ImageRecordIter params (subset with same names)."""

    def __init__(self, path_imgrec, data_shape, batch_size,
                 path_imgidx=None, label_width=1, shuffle=False,
                 shuffle_chunk_size=0, part_index=0, num_parts=1,
                 preprocess_threads=4, prefetch_buffer=4,
                 rand_crop=False, rand_mirror=False, mean_r=0.0, mean_g=0.0,
                 mean_b=0.0, std_r=1.0, std_g=1.0, std_b=1.0, resize=0,
                 data_name="data", label_name="softmax_label",
                 round_batch=True, seed=0, **kwargs):
        super().__init__(batch_size)
        self.data_shape = tuple(int(x) for x in data_shape)
        self.label_width = label_width
        self.data_name = data_name
        self.label_name = label_name
        mean = None
        std = None
        if mean_r or mean_g or mean_b:
            mean = np.array([mean_r, mean_g, mean_b])
        if std_r != 1 or std_g != 1 or std_b != 1:
            std = np.array([std_r, std_g, std_b])
        self.auglist = CreateAugmenter(self.data_shape, resize=resize,
                                       rand_crop=rand_crop,
                                       rand_mirror=rand_mirror,
                                       mean=mean, std=std)
        import os
        idx_path = path_imgidx or os.path.splitext(path_imgrec)[0] + ".idx"
        have_idx = os.path.isfile(idx_path)

        # Prefer the native C++ pipeline when built: same parameter surface,
        # decode+augment under OMP with a bounded prefetch queue.
        self._native = None
        if os.environ.get("MXNET_TPU_NATIVE_IO", "1") != "0":
            from ..io.native import load_native, NativeRecordIter
            if load_native() is not None:
                self._native = NativeRecordIter(
                    path_imgrec, self.data_shape, batch_size,
                    idx_path=idx_path if have_idx else None,
                    label_width=label_width, threads=preprocess_threads,
                    shuffle=shuffle, seed=seed, resize_short=resize,
                    rand_crop=rand_crop, rand_mirror=rand_mirror,
                    mean=None if mean is None else tuple(float(v) for v in mean),
                    std=None if std is None else tuple(float(v) for v in std),
                    prefetch=prefetch_buffer, part_index=part_index,
                    num_parts=num_parts if have_idx else 1)
                logging.info("ImageRecordIter(%s): native C++ decode "
                             "pipeline", path_imgrec)
                return
            logging.info("ImageRecordIter(%s): native IO library not built "
                         "(make -C native) — decoding in Python",
                         path_imgrec)

        from ..resilience.retry import call_with_retry
        if have_idx:
            self._rec = call_with_retry(
                recordio.MXIndexedRecordIO, idx_path, path_imgrec, "r",
                exceptions=(OSError,), desc="open %s" % path_imgrec)
            keys = list(self._rec.keys)
        else:
            # sequential scan to index offsets
            self._rec = call_with_retry(
                recordio.MXRecordIO, path_imgrec, "r",
                exceptions=(OSError,), desc="open %s" % path_imgrec)
            keys = None
        self._keys = keys
        if keys is not None and num_parts > 1:
            n = len(keys) // num_parts
            self._keys = keys[part_index * n:(part_index + 1) * n]
        self.shuffle = shuffle
        self._threads = preprocess_threads
        self._prefetch = prefetch_buffer
        self._rng = random.Random(seed)
        self._order = None
        self._lock = threading.Lock()
        self._epoch = -1      # reset() below brings it to 0
        self.reset()

    @property
    def provide_data(self):
        return [DataDesc(self.data_name, (self.batch_size,) +
                         self.data_shape)]

    @property
    def provide_label(self):
        shape = (self.batch_size,) if self.label_width == 1 else \
            (self.batch_size, self.label_width)
        return [DataDesc(self.label_name, shape)]

    def reset(self):
        if self._native is not None:
            self._native.reset()
            return
        self._epoch += 1
        if self._keys is not None:
            self._order = list(self._keys)
            if self.shuffle:
                self._rng.shuffle(self._order)
        else:
            self._rec.reset()
        self._cursor = 0

    # -- exact-resume state ----------------------------------------------
    def state_dict(self):
        """Checkpointable position: cursor, epoch, this epoch's shuffled
        key order, and the shuffle-RNG state (so FUTURE epochs reshuffle
        identically).  Requires the indexed pure-Python pipeline."""
        from ..base import MXNetError
        if self._native is not None:
            raise MXNetError(
                "exact-resume iterator state needs the Python RecordIO "
                "pipeline; set MXNET_TPU_NATIVE_IO=0")
        if self._order is None:
            raise MXNetError(
                "exact-resume iterator state needs an indexed record file "
                "(.idx) — the sequential-scan path has no cursor to save")
        with self._lock:
            return {"kind": "ImageRecordIter",
                    "cursor": int(self._cursor),
                    "epoch": int(self._epoch),
                    "order": np.asarray(self._order, np.int64),
                    "rng_state": self._rng.getstate()}

    def load_state_dict(self, state):
        from ..base import MXNetError
        if state.get("kind") != "ImageRecordIter":
            raise ValueError("state is for %r, not ImageRecordIter"
                             % state.get("kind"))
        if self._native is not None:
            raise MXNetError(
                "exact-resume iterator state needs the Python RecordIO "
                "pipeline; set MXNET_TPU_NATIVE_IO=0")
        order = [int(k) for k in np.asarray(state["order"])]
        missing = set(order) - set(self._keys or [])
        if missing:
            raise ValueError(
                "iterator state mismatch: %d saved record keys not in this "
                "record file (e.g. %r)" % (len(missing),
                                           sorted(missing)[:3]))
        with self._lock:
            self._order = order
            self._cursor = int(state["cursor"])
            self._epoch = int(state["epoch"])
            rng_state = state.get("rng_state")
            if rng_state is not None:
                version, internal, gauss = rng_state
                self._rng.setstate(
                    (int(version), tuple(int(v) for v in internal), gauss))

    def _read_record(self):
        """One raw record, retried with backoff on transient IO errors
        (network filesystems drop reads under load; see resilience/retry).
        The chaos ``io_error`` fault fires INSIDE the retried callable so
        fault drills prove the retry path, not a mock of it."""
        from ..resilience import chaos
        from ..resilience.retry import call_with_retry
        with self._lock:
            if self._order is not None:
                if self._cursor >= len(self._order):
                    return None
                key = self._order[self._cursor]
                self._cursor += 1

                def read_one():
                    chaos.maybe_io_error("record %s" % key)
                    return self._rec.read_idx(key)
            else:
                def read_one():
                    chaos.maybe_io_error("record stream read")
                    return self._rec.read()
            return call_with_retry(read_one, exceptions=(OSError,),
                                   desc="RecordIO read")

    def _decode_one(self, raw):
        header, img_bytes = recordio.unpack(raw)
        img = imdecode(img_bytes)
        for aug in self.auglist:
            img = aug(img)
        label = np.asarray(header.label).reshape(-1)
        return img.asnumpy(), label

    def next(self):
        from .. import telemetry
        from ..telemetry import memory as _memory
        with telemetry.span("data/next", cat="io",
                            metric="data.next_seconds"):
            batch = self._next_batch()
            # memory plane: bucket the decoded batch buffers
            _memory.tag(list(batch.data) + list(batch.label or []),
                        "batch", label="ImageRecordIter")
            return batch

    def _next_batch(self):
        if self._native is not None:
            data, label, pad = self._native.next()   # raises StopIteration
            out_label = label[:, 0] if self.label_width == 1 else label
            return DataBatch([nd_array(data)], [nd_array(out_label)], pad=pad)
        c, h, w = self.data_shape
        bs = self.batch_size
        data = np.zeros((bs, h, w, c), np.float32)
        label = np.zeros((bs, self.label_width), np.float32)
        raws = []
        for _ in range(bs):
            r = self._read_record()
            if r is None:
                break
            raws.append(r)
        if not raws:
            raise StopIteration
        pad = bs - len(raws)

        if self._threads > 1 and len(raws) > 1:
            results = [None] * len(raws)

            def worker(start, step):
                for idx in range(start, len(raws), step):
                    results[idx] = self._decode_one(raws[idx])

            threads = [threading.Thread(target=worker, args=(t, self._threads))
                       for t in range(self._threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        else:
            results = [self._decode_one(r) for r in raws]

        for i, (img, lab) in enumerate(results):
            data[i] = img.reshape(h, w, c)
            label[i, :len(lab[:self.label_width])] = lab[:self.label_width]
        for j in range(len(raws), bs):
            data[j] = data[j % len(raws)]
            label[j] = label[j % len(raws)]
        out_label = label[:, 0] if self.label_width == 1 else label
        return DataBatch([nd_array(data.transpose(0, 3, 1, 2))],
                         [nd_array(out_label)], pad=pad)


def ImageRecordUInt8Iter(*args, **kwargs):
    """uint8 variant (reference ImageRecordUInt8Iter) — same pipeline."""
    return ImageRecordIter(*args, **kwargs)
