"""Async prefetch, streaming schedule, dispatcher, and stateful resume
(reference analogs: ``MpDeviceLoaderWrapper`` ``data_loader.py:632``,
``DataLoaderDispatcher`` :682, StatefulDataLoader support :449)."""

import itertools
import time

import numpy as np
import pytest

from accelerate_tpu.data_loader import (
    BatchSampler,
    BatchSamplerShard,
    DataLoaderDispatcher,
    DataLoaderShard,
    SequentialSampler,
    prepare_data_loader,
    skip_first_batches,
)


class _Dataset:
    def __init__(self, n, delay=0.0):
        self.n = n
        self.delay = delay

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if self.delay:
            time.sleep(self.delay)
        return {"x": np.float32(i)}


def _shard_loader(n=32, batch_size=4, prefetch=2, delay=0.0, num_processes=1):
    sampler = BatchSampler(SequentialSampler(n), batch_size=batch_size)
    shard = BatchSamplerShard(sampler, num_processes=num_processes, process_index=0)
    return DataLoaderShard(
        _Dataset(n, delay=delay), batch_sampler=shard, sharding=None,
        prefetch_batches=prefetch,
    )


def test_prefetch_and_sync_paths_yield_identical_batches():
    a = [b["x"].tolist() for b in _shard_loader(prefetch=2)]
    b = [b["x"].tolist() for b in _shard_loader(prefetch=0)]
    assert a == b
    assert len(a) == 8


def test_prefetch_overlaps_collate_with_consumer():
    """While the consumer holds batch ``i`` and asks for nothing, the
    prefetch thread goes on collating: the dataset is read to the end of
    batch ``i + 2``. The synchronous path, whose one batch of lookahead is
    read only when the consumer asks, never gets that far. Judged by the
    order of events: no wall-clock bound (the timeout below only keeps a
    loader that stopped prefetching from hanging the suite)."""
    import threading

    n, bs = 24, 4
    n_batches = n // bs
    read = [threading.Event() for _ in range(n)]

    class _Recording(_Dataset):
        def __getitem__(self, i):
            read[i].set()
            return super().__getitem__(i)

    def loader(prefetch):
        for event in read:
            event.clear()
        sampler = BatchSampler(SequentialSampler(n), batch_size=bs)
        shard = BatchSamplerShard(sampler, num_processes=1, process_index=0)
        return DataLoaderShard(
            _Recording(n), batch_sampler=shard, sharding=None, prefetch_batches=prefetch,
        )

    def last_of(batch):
        return read[(batch + 1) * bs - 1]

    count = 0
    for i, _ in enumerate(loader(prefetch=3)):
        if i + 2 < n_batches:
            assert last_of(i + 2).wait(timeout=60), (
                f"holding batch {i}, batch {i + 2} was never collated: no overlap")
        count += 1
    assert count == n_batches

    # the control: without the thread nothing is read while the consumer works
    for i, _ in enumerate(loader(prefetch=0)):
        if i + 2 < n_batches:
            assert last_of(i + 1).is_set() and not read[(i + 2) * bs].is_set()


def test_prefetch_propagates_exceptions():
    class _Bad(_Dataset):
        def __getitem__(self, i):
            if i >= 8:
                raise RuntimeError("boom at 8")
            return {"x": np.float32(i)}

    sampler = BatchSampler(SequentialSampler(16), batch_size=4)
    shard = BatchSamplerShard(sampler, num_processes=1, process_index=0)
    loader = DataLoaderShard(_Bad(16), batch_sampler=shard, sharding=None, prefetch_batches=2)
    with pytest.raises(RuntimeError, match="boom"):
        list(loader)


def test_early_break_stops_producer_thread():
    import threading

    before = {t.name for t in threading.enumerate()}
    loader = _shard_loader(n=64, batch_size=4, prefetch=2)
    for i, _ in enumerate(loader):
        if i == 1:
            break
    time.sleep(0.3)
    leaked = [
        t for t in threading.enumerate()
        if t.name == "dataloader-prefetch" and t.is_alive() and t.name not in before
    ]
    assert not leaked


def test_streaming_schedule_is_lazy():
    """The round-robin shard must not consume the whole sampler up front."""
    consumed = []

    class _CountingSampler:
        batch_size = 4
        drop_last = False

        def __len__(self):
            return 1000

        def __iter__(self):
            for i in range(1000):
                consumed.append(i)
                yield list(range(i * 4, i * 4 + 4))

    shard = BatchSamplerShard(_CountingSampler(), num_processes=2, process_index=0)
    it = iter(shard)
    next(it)
    assert len(consumed) < 10, f"schedule materialised {len(consumed)} batches eagerly"


def test_streaming_schedule_matches_reference_semantics():
    """Pin the even_batches wraparound math (reference data_loader.py:189-256)
    across uneven tails."""
    for n, bs, P in [(10, 3, 2), (17, 4, 4), (8, 4, 2), (7, 2, 4), (3, 2, 4)]:
        sampler = BatchSampler(SequentialSampler(n), batch_size=bs)
        per_proc = [
            list(BatchSamplerShard(sampler, num_processes=P, process_index=p))
            for p in range(P)
        ]
        lens = {len(x) for x in per_proc}
        assert len(lens) == 1, f"uneven counts {lens} for n={n},bs={bs},P={P}"
        for batches in per_proc:
            assert all(len(b) == bs for b in batches)
        # every dataset index appears at least once
        seen = set(itertools.chain.from_iterable(itertools.chain.from_iterable(per_proc)))
        assert seen == set(range(n))


def test_dispatcher_single_process_matches_shard():
    loader = prepare_data_loader(
        _Dataset(32), num_processes=1, process_index=0, put_on_device=False,
        dispatch_batches=True,
    )
    assert isinstance(loader, DataLoaderDispatcher)
    xs = list(itertools.chain.from_iterable(b["x"].tolist() for b in loader))
    assert xs == [float(i) for i in range(32)]


def test_dispatcher_iterable_dataset():
    class _Stream:
        def __iter__(self):
            return iter({"x": np.float32(i)} for i in range(12))

    loader = prepare_data_loader(
        _Stream(), num_processes=1, process_index=0, put_on_device=False,
        dispatch_batches=True,
    )
    xs = list(itertools.chain.from_iterable(b["x"].tolist() for b in loader))
    assert xs == [float(i) for i in range(12)]


def test_state_dict_roundtrip_resumes_mid_epoch():
    loader = _shard_loader(n=32, batch_size=4)
    seen = []
    state = None
    for i, batch in enumerate(loader):
        seen.append(batch["x"].tolist())
        if i == 2:
            state = loader.state_dict()
            break
    assert state["batches_yielded"] == 3

    fresh = _shard_loader(n=32, batch_size=4)
    fresh.load_state_dict(state)
    rest = [b["x"].tolist() for b in fresh]
    full = [b["x"].tolist() for b in _shard_loader(n=32, batch_size=4)]
    assert seen + rest == full


def test_state_dict_after_full_epoch_does_not_reskip():
    loader = _shard_loader(n=16, batch_size=4)
    list(loader)
    state = loader.state_dict()
    assert state["batches_yielded"] == 0
    fresh = _shard_loader(n=16, batch_size=4)
    fresh.load_state_dict(state)
    assert len(list(fresh)) == 4


def test_skip_first_batches_still_works_with_prefetch():
    loader = _shard_loader(n=32, batch_size=4)
    skipped = skip_first_batches(loader, 3)
    xs = [b["x"].tolist() for b in skipped]
    assert xs[0] == [12.0, 13.0, 14.0, 15.0]
    assert len(xs) == 5
