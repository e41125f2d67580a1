"""The CSR sidecar's CRC check on several threads: each value equals
``zlib.crc32`` bit for bit, whatever the member's size, and the counter
says which way each member was checked."""

import random
import zlib

import numpy as np
import pytest

from spark_examples_tpu import obs
from spark_examples_tpu.genomics import sources as S

CHUNK = 64 << 10


@pytest.fixture()
def small_chunks(monkeypatch):
    monkeypatch.setattr(S, "_CRC_CHUNK_BYTES", CHUNK)
    monkeypatch.setattr(S, "_crc_threads", lambda: 8)


def _payload(size: int, seed: int = 0) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def _counted(mode: str) -> float:
    key = f'ingest_sidecar_crc_bytes_total{{mode="{mode}"}}'
    return obs.get_registry().snapshot()["counters"].get(key, 0.0)


@pytest.mark.parametrize("size", [
    0, 1, 5 * 1024 - 1, CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK,
    3 * CHUNK + 7, 9 * CHUNK + 3,
])
def test_parallel_crc_equals_zlib(small_chunks, size):
    data = _payload(size, seed=size)
    view = memoryview(data)
    mode = "parallel" if size >= 2 * CHUNK else "serial"
    before = _counted(mode)
    assert S._crc32_parallel(view) == zlib.crc32(data)
    assert _counted(mode) - before == size


def test_parallel_crc_on_a_shared_pool_and_an_offset_view(small_chunks):
    """The loader's form: one pool for every member, each member a slice
    of the file's view that starts at an odd offset."""
    from concurrent.futures import ThreadPoolExecutor

    data = _payload(5 * CHUNK + 11, seed=7)
    view = memoryview(data)
    with ThreadPoolExecutor(8) as pool:
        for a, b in ((3, 4 * CHUNK), (CHUNK + 1, len(data)), (0, 2 * CHUNK)):
            assert S._crc32_parallel(view[a:b], pool) == zlib.crc32(
                data[a:b])


@pytest.mark.parametrize("seed", range(4))
def test_combine_equals_zlib_over_random_splits(seed):
    rng = random.Random(seed)
    data = _payload(rng.randint(1, 3 * CHUNK), seed=seed)
    cuts = [0, len(data)] + [rng.randint(0, len(data)) for _ in range(8)]
    for cut in cuts:
        a, b = data[:cut], data[cut:]
        assert S._crc32_combine(zlib.crc32(a), zlib.crc32(b), len(b)) == (
            zlib.crc32(data)), cut


def test_combine_of_an_empty_tail_is_the_head():
    crc = zlib.crc32(b"sidecar")
    assert S._crc32_combine(crc, 0, 0) == crc
    assert S._crc32_combine(0, crc, len(b"sidecar")) == crc

