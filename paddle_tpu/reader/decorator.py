"""Reader decorators (reference: python/paddle/reader/decorator.py:36-215 —
map_readers, shuffle, chain, compose, buffered, firstn, xmap_readers,
multiprocess_reader; and python/paddle/fluid/reader/ batch).

A reader is a zero-arg callable returning an iterator over samples.  The
decorators compose exactly as in the reference; `buffered` runs a background
thread so host-side preprocessing overlaps TPU steps (the role of
operators/reader/buffered_reader.cc)."""

from __future__ import annotations

import itertools
import queue
import random as _random
import threading
from typing import Any, Callable, Iterable, List

ReaderCreator = Callable[[], Iterable[Any]]


def map_readers(func, *readers):
    def reader():
        rs = [r() for r in readers]
        for vals in zip(*rs):
            yield func(*vals)

    return reader


def shuffle(reader, buf_size):
    def data_reader():
        buf = []
        for e in reader():
            buf.append(e)
            if len(buf) >= buf_size:
                _random.shuffle(buf)
                for b in buf:
                    yield b
                buf = []
        if buf:
            _random.shuffle(buf)
            for b in buf:
                yield b

    return data_reader


def chain(*readers):
    def reader():
        for r in readers:
            yield from r()

    return reader


def compose(*readers, **kwargs):
    check_alignment = kwargs.pop("check_alignment", True)

    def make_tuple(x):
        if isinstance(x, tuple):
            return x
        return (x,)

    def reader():
        rs = [r() for r in readers]
        if not check_alignment:
            for outputs in zip(*rs):
                yield sum(list(map(make_tuple, outputs)), ())
        else:
            for outputs in itertools.zip_longest(*rs):
                if any(o is None for o in outputs):
                    raise RuntimeError("readers have different lengths")
                yield sum(list(map(make_tuple, outputs)), ())

    return reader


def buffered(reader, size):
    """Background-thread prefetch buffer.  Reader exceptions propagate to the
    consumer (not swallowed as end-of-data)."""

    class _End:
        pass

    class _Error:
        def __init__(self, exc):
            self.exc = exc

    def data_reader():
        r = reader()
        q: queue.Queue = queue.Queue(maxsize=size)

        def read_worker():
            try:
                for d in r:
                    q.put(d)
                q.put(_End)
            except BaseException as e:  # noqa: BLE001 - forwarded to consumer
                q.put(_Error(e))

        t = threading.Thread(target=read_worker, daemon=True)
        t.start()
        while True:
            e = q.get()
            if e is _End:
                break
            if isinstance(e, _Error):
                raise e.exc
            yield e

    return data_reader


def firstn(reader, n):
    def data_reader():
        for i, item in enumerate(reader()):
            if i == n:
                break
            yield item

    return data_reader


def batch(reader, batch_size, drop_last=False):
    """Group samples into lists (reference: paddle.batch)."""

    def batch_reader():
        b = []
        for item in reader():
            b.append(item)
            if len(b) == batch_size:
                yield b
                b = []
        if b and not drop_last:
            yield b

    return batch_reader


def xmap_readers(mapper, reader, process_num, buffer_size, order=False):
    """Parallel map over samples with worker threads."""

    class _End:
        pass

    def data_reader():
        in_q: queue.Queue = queue.Queue(buffer_size)
        out_q: queue.Queue = queue.Queue(buffer_size)

        def feed():
            for i, d in enumerate(reader()):
                in_q.put((i, d))
            for _ in range(process_num):
                in_q.put(_End)

        def work():
            try:
                while True:
                    item = in_q.get()
                    if item is _End:
                        break
                    i, d = item
                    out_q.put((i, mapper(d)))
                out_q.put(_End)
            except BaseException as e:  # noqa: BLE001 - forwarded to consumer
                out_q.put(e)

        threading.Thread(target=feed, daemon=True).start()
        workers = [threading.Thread(target=work, daemon=True) for _ in range(process_num)]
        for w in workers:
            w.start()

        finished = 0
        pending = {}
        next_idx = 0
        while finished < process_num:
            item = out_q.get()
            if item is _End:
                finished += 1
                continue
            if isinstance(item, BaseException):
                raise item
            if not order:
                yield item[1]
            else:
                pending[item[0]] = item[1]
                while next_idx in pending:
                    yield pending.pop(next_idx)
                    next_idx += 1
        if order:
            for i in sorted(pending):
                yield pending[i]

    return data_reader


def cache(reader):
    all_data: List[Any] = []
    filled = [False]

    def data_reader():
        if not filled[0]:
            for d in reader():
                all_data.append(d)
            filled[0] = True
        yield from all_data

    return data_reader


def device_put_chunked(v):
    """Host->device copy; large slabs chunk along dim 0 and transfer on a
    small thread pool — concurrent puts parallelize the host->device
    link (FLAGS_prefetch_chunk_mb / FLAGS_prefetch_threads).  Whether
    chunking pays on a co-located host: not measured."""
    import numpy as np
    import jax.numpy as jnp

    if hasattr(v, "devices"):  # already a device array
        return v
    from ..flags import FLAGS

    chunk_bytes = FLAGS.prefetch_chunk_mb << 20
    arr = np.asarray(v)
    if arr.nbytes > chunk_bytes and arr.shape and arr.shape[0] > 1:
        import concurrent.futures as cf

        n = min(arr.shape[0], max(2, arr.nbytes // chunk_bytes))
        chunks = np.array_split(arr, n, axis=0)
        with cf.ThreadPoolExecutor(FLAGS.prefetch_threads) as pool:
            parts = list(pool.map(jnp.asarray, chunks))
        return jnp.concatenate(parts, axis=0)
    return jnp.asarray(arr)


def double_buffer(batch_reader, capacity=2):
    """Device-prefetch double buffering (reference:
    operators/reader/buffered_reader.cc — pre-copies batches to the device
    on a side stream; layers/io.py:1002 double_buffer).

    A daemon thread converts upcoming batches to device arrays
    (jnp.asarray = host->HBM copy) while the main thread's current step
    computes; Executor._to_device_array passes device-resident feeds
    through untouched, so the copy never lands on the critical path.
    Works on feed dicts ({name: ndarray}) and tuples/lists of ndarrays.
    """

    def _put(item):
        if isinstance(item, dict):
            return {k: device_put_chunked(v) for k, v in item.items()}
        if isinstance(item, (tuple, list)):
            return type(item)(device_put_chunked(v) for v in item)
        return device_put_chunked(item)

    class _Err:
        def __init__(self, exc):
            self.exc = exc

    def reader():
        import queue
        import threading

        q = queue.Queue(maxsize=capacity)
        end = object()

        def work():
            try:
                for item in batch_reader():
                    q.put(_put(item))
            except Exception as e:  # propagate into the consuming thread
                q.put(_Err(e))
            q.put(end)

        t = threading.Thread(target=work, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is end:
                break
            if isinstance(item, _Err):
                raise item.exc
            yield item

    return reader


class StatefulReader:
    """A reader creator with a RESUMABLE epoch/offset cursor (checkpoint v2
    state provider — io.CheckpointManager.register_state).

    Wraps any reader creator; each __call__ yields one epoch while the
    cursor tracks (epoch, items yielded this epoch).  After
    load_state_dict, the NEXT epoch iterated fast-forwards past `offset`
    items, so a resumed run consumes exactly the samples the killed run
    never saw — required for bit-exact kill/resume (the underlying reader
    must be deterministic for a given epoch, as shuffle(seeded) readers
    are).

        sreader = StatefulReader(my_creator)
        mgr.register_state("reader", sreader)
        for feed in sreader():       # one epoch, cursor maintained
            ...
    """

    def __init__(self, reader_creator: ReaderCreator):
        self.creator = reader_creator
        self.epoch = 0
        self.offset = 0
        self._pending_skip = 0

    def __call__(self):
        skip, self._pending_skip = self._pending_skip, 0
        n = 0
        for item in self.creator():
            n += 1
            if n <= skip:
                continue
            self.offset = n
            yield item
        self.epoch += 1
        self.offset = 0

    def state_dict(self) -> dict:
        return {"epoch": int(self.epoch), "offset": int(self.offset)}

    def load_state_dict(self, d: dict) -> None:
        self.epoch = int(d["epoch"])
        self.offset = int(d["offset"])
        self._pending_skip = self.offset
