"""Smoke test that the virtual 8-device CPU mesh is actually wired up."""

import os

import pytest

import jax


def test_virtual_device_count():
    assert jax.default_backend() == "cpu"
    assert jax.device_count() == 8


def test_device_prefetch_order_and_error():
    import numpy as np
    import pytest

    from spark_examples_tpu.arrays.feed import device_prefetch

    blocks = [np.full((4, 4), i, np.int8) for i in range(7)]
    out = [int(np.asarray(b)[0, 0]) for b in device_prefetch(iter(blocks))]
    assert out == list(range(7))

    def failing():
        yield np.zeros((2, 2), np.int8)
        raise IOError("ingest died")

    it = device_prefetch(failing())
    next(it)
    with pytest.raises(IOError, match="ingest died"):
        list(it)


def test_device_prefetch_abandoned_consumer_releases_producer():
    import threading
    import time

    import numpy as np

    from spark_examples_tpu.arrays.feed import device_prefetch

    started = threading.Event()
    n_produced = []

    def blocks():
        for i in range(100):
            started.set()
            n_produced.append(i)
            yield np.zeros((64, 64), np.int8)

    it = device_prefetch(blocks(), depth=2)
    next(it)
    started.wait(5)
    it.close()  # consumer abandons mid-stream
    deadline = time.time() + 5
    while time.time() < deadline and threading.active_count() > 20:
        time.sleep(0.05)
    time.sleep(0.3)
    produced_after_close = len(n_produced)
    time.sleep(0.5)
    # Producer must have stopped: no further blocks drawn from the source.
    assert len(n_produced) <= produced_after_close + 1


def test_device_prefetch_producer_exception_at_depth_gt_2():
    """A producer failure must surface in the consumer at ANY staging
    depth — with depth > 2 several good blocks are already queued ahead
    of the error, and all of them must still be delivered first."""
    import numpy as np
    import pytest

    from spark_examples_tpu.arrays.feed import device_prefetch

    def failing():
        for i in range(5):
            yield np.full((3, 3), i, np.int8)
        raise IOError("builder worker died")

    it = device_prefetch(failing(), depth=4)
    got = []
    with pytest.raises(IOError, match="builder worker died"):
        for b in it:
            got.append(int(np.asarray(b)[0, 0]))
    assert got == [0, 1, 2, 3, 4]  # nothing staged was dropped


def test_device_prefetch_consumer_cancel_at_depth_gt_2():
    """Abandoning the consumer mid-stream with a deep queue must stop
    the producer promptly: with depth > 2 a blocked q.put holds MORE
    staged device blocks alive, so a leak here is depth× worse."""
    import threading
    import time

    import numpy as np

    from spark_examples_tpu.arrays.feed import device_prefetch

    started = threading.Event()
    n_produced = []

    def blocks():
        for i in range(1000):
            started.set()
            n_produced.append(i)
            yield np.zeros((32, 32), np.int8)

    it = device_prefetch(blocks(), depth=5)
    next(it)
    started.wait(5)
    it.close()  # consumer abandons with ~depth blocks staged
    deadline = time.time() + 5
    stable_at = None
    while time.time() < deadline:
        n = len(n_produced)
        time.sleep(0.3)
        if len(n_produced) == n:
            stable_at = n
            break
    # Producer stopped well short of the stream (bounded by the window
    # in flight when close() landed), not at exhaustion.
    assert stable_at is not None and stable_at < 1000


def test_device_prefetch_cancel_while_producer_blocked_in_fetch():
    """Consumer cancellation against an UNBOUNDED-LATENCY producer (the
    cold-stream shape: a shard fetch that may take arbitrarily long on
    a slow wire). close() must return promptly even though the producer
    thread is parked INSIDE its fetch, and once the fetch finally
    returns the producer must notice the cancel and stop — no further
    frames drawn, no thread leak."""
    import threading
    import time

    import numpy as np

    from spark_examples_tpu.arrays.feed import device_prefetch

    in_fetch = threading.Event()
    release = threading.Event()
    produced = []

    def frames():
        produced.append(0)
        yield np.zeros((8, 8), np.int8)
        in_fetch.set()
        # The unbounded-latency fetch (bounded here only so a failing
        # implementation can't hang the suite).
        release.wait(30)
        produced.append(1)
        yield np.zeros((8, 8), np.int8)
        produced.append(2)
        yield np.zeros((8, 8), np.int8)

    it = device_prefetch(frames(), depth=2)
    next(it)
    assert in_fetch.wait(5)
    t0 = time.monotonic()
    it.close()  # cancel while frame 2 is still "on the wire"
    assert time.monotonic() - t0 < 1.0  # close never waits on the fetch
    release.set()  # the slow fetch lands AFTER the cancel
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and len(produced) < 2:
        time.sleep(0.05)
    time.sleep(0.3)
    # The in-flight fetch may complete (it was already running) but the
    # producer must stop there: frame 3 is never drawn.
    assert len(produced) <= 2


def test_device_prefetch_producer_exception_with_frames_in_flight():
    """A producer that dies while staged frames are still in flight
    (queued, unconsumed — the consumer hasn't even started draining):
    every already-staged frame must still be delivered, in order, and
    the failure must surface AFTER them — never a silent drop, never a
    lost exception."""
    import time

    import numpy as np
    import pytest

    from spark_examples_tpu.arrays.feed import device_prefetch

    def failing():
        for i in range(3):
            yield np.full((4, 4), i, np.int8)
        raise IOError("wire fetch died mid-stream")

    it = device_prefetch(failing(), depth=3)
    # Let the producer fill the queue AND die before the consumer takes
    # a single frame — the frames are "in flight" when the error lands.
    time.sleep(0.5)
    got = []
    with pytest.raises(IOError, match="wire fetch died mid-stream"):
        for b in it:
            got.append(int(np.asarray(b)[0, 0]))
    assert got == [0, 1, 2]


def test_int8_int32_gramian_exact():
    """int8 x int8 -> int32 einsum (the MXU int-matmul path) is exact and
    matches the f32 path."""
    import numpy as np
    import jax.numpy as jnp

    from spark_examples_tpu.ops import gramian

    rng = np.random.default_rng(0)
    x = (rng.random((64, 512)) < 0.4).astype(np.int8)
    g_int = gramian(x, compute_dtype=jnp.int8, accum_dtype=jnp.int32)
    g_f32 = gramian(x)
    assert g_int.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(g_int), np.asarray(g_f32))


def test_gramian_packed_transfer_path_bit_identical():
    """The bit-packed transfer path (8x fewer host->device bytes) must be
    bit-identical to the dense path, including non-multiple-of-8 block
    widths whose packbits pad bits unpack to inert zero columns."""
    import numpy as np
    import jax.numpy as jnp

    from spark_examples_tpu.ops.gramian import (
        gramian_blockwise,
        pack_indicator_block,
        unpack_indicator_block,
    )

    rng = np.random.default_rng(3)
    for n, v in ((17, 96), (33, 100)):
        blocks = [
            (rng.random((n, v)) < 0.2).astype(np.int8) for _ in range(3)
        ]
        dense = np.asarray(gramian_blockwise(blocks, n))
        packed = np.asarray(gramian_blockwise(blocks, n, packed=True))
        np.testing.assert_array_equal(dense, packed)
        xp = pack_indicator_block(blocks[0])
        np.testing.assert_array_equal(
            np.asarray(unpack_indicator_block(jnp.asarray(xp), v)),
            blocks[0],
        )


def test_pack_indicator_block_rejects_non_indicator_values():
    """Packing collapses any nonzero to 1; a dosage-valued (0/1/2) block
    must be rejected loudly instead of silently producing a wrong Gramian
    (round-3 advisor finding on the hard-coded packed default)."""
    import numpy as np
    import pytest

    from spark_examples_tpu.ops.gramian import pack_indicator_block

    ok = np.zeros((4, 16), dtype=np.int8)
    ok[1, 3] = 1
    pack_indicator_block(ok)  # 0/1 passes
    bad = ok.copy()
    bad[2, 5] = 2
    with pytest.raises(ValueError, match="0/1 indicator"):
        pack_indicator_block(bad)
    neg = ok.copy()
    neg[0, 0] = -1
    with pytest.raises(ValueError, match="0/1 indicator"):
        pack_indicator_block(neg)
    # Fractional dosages sit inside [0, 1] but still collapse to 1 under
    # astype(bool) — the guard must be an exact-0/1 check, not a range
    # check (round-4 re-review finding).
    frac = np.zeros((4, 16), dtype=np.float32)
    frac[1, 2] = 0.5
    with pytest.raises(ValueError, match="0/1 indicator"):
        pack_indicator_block(frac)


def test_gramian_env_escape_hatch_per_call(monkeypatch):
    """SPARK_EXAMPLES_TPU_GRAMIAN is resolved OUTSIDE jit on every call:
    flipping it after a first (cached) trace must still take effect, and
    an invalid value must raise even after prior successful calls — the
    round-3 review found the original trace-time read silently froze the
    first call's choice into the jit cache."""
    import numpy as np
    import jax.numpy as jnp

    from spark_examples_tpu.ops.gramian import (
        gramian,
        resolve_gramian_compute_dtype,
    )

    x = (np.random.default_rng(0).random((16, 32)) < 0.4).astype(np.int8)
    g_auto = np.asarray(gramian(x))  # traces+caches the int8 auto path
    assert resolve_gramian_compute_dtype(x.dtype, jnp.float32) == jnp.int8

    monkeypatch.setenv("SPARK_EXAMPLES_TPU_GRAMIAN", "f32")
    assert (
        resolve_gramian_compute_dtype(x.dtype, jnp.float32) == jnp.float32
    )
    np.testing.assert_array_equal(np.asarray(gramian(x)), g_auto)

    monkeypatch.setenv("SPARK_EXAMPLES_TPU_GRAMIAN", "bogus")
    try:
        gramian(x)
    except ValueError as e:
        assert "SPARK_EXAMPLES_TPU_GRAMIAN" in str(e)
    else:
        raise AssertionError("invalid env value must raise per call")


def test_debug_numerics_and_range_guard():
    import numpy as np
    import jax.numpy as jnp
    import pytest

    from spark_examples_tpu.utils.debug import (
        assert_exact_f32_range,
        debug_numerics,
    )

    assert_exact_f32_range(jnp.ones((3, 3)))
    with pytest.raises(AssertionError, match="2\\^24"):
        assert_exact_f32_range(jnp.full((2, 2), float(1 << 24)))
    with debug_numerics():
        with pytest.raises(FloatingPointError):
            _ = jnp.log(jnp.zeros(2)) * 0  # -inf triggers debug_infs


def test_graft_entry_contract():
    """The driver contract: entry() compiles; dryrun_multichip works for
    even, odd, and prime device counts."""
    import sys, os
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (128, 2)
    for n in (1, 3, 8):
        ge.dryrun_multichip(n)


def test_compile_cache_env_dir_is_used_verbatim(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR names the cache, and the program sets no
    other directory (JAX itself reads the variable)."""
    from spark_examples_tpu.utils.compile_cache import (
        compilation_cache_dir,
        enable_persistent_cache,
    )

    want = str(tmp_path / "x")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    monkeypatch.delenv("SPARK_EXAMPLES_TPU_COMPILE_CACHE", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compilation_cache_dir() == want
    assert enable_persistent_cache() == want
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists(want)  # JAX creates it on first write


def test_compile_cache_default_is_checkout_jax_cache(monkeypatch):
    """Unset: the fixed ``<checkout>/.jax_cache`` (no per-host, -process
    or -time name); ``SPARK_EXAMPLES_TPU_COMPILE_CACHE=0`` turns it off."""
    from spark_examples_tpu.utils.compile_cache import compilation_cache_dir

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("SPARK_EXAMPLES_TPU_COMPILE_CACHE", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compilation_cache_dir() == os.path.join(root, ".jax_cache")
    monkeypatch.setenv("SPARK_EXAMPLES_TPU_COMPILE_CACHE", "0")
    assert compilation_cache_dir() is None


@pytest.fixture
def restore_cache_switch():
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    was_dir = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    jax.config.update("jax_compilation_cache_dir", was_dir)
    compilation_cache.reset_cache()


def test_compile_cache_off_switch_beats_env_dir(
    monkeypatch, tmp_path, restore_cache_switch
):
    """``SPARK_EXAMPLES_TPU_COMPILE_CACHE=0`` turns JAX's own switch off,
    so no cache is used even where ``JAX_COMPILATION_CACHE_DIR`` (which
    JAX read at import) names one."""
    from spark_examples_tpu.utils.compile_cache import enable_persistent_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
    monkeypatch.setenv("SPARK_EXAMPLES_TPU_COMPILE_CACHE", "0")
    assert enable_persistent_cache() is None
    assert jax.config.jax_enable_compilation_cache is False
    monkeypatch.delenv("SPARK_EXAMPLES_TPU_COMPILE_CACHE")
    assert enable_persistent_cache() == str(tmp_path / "x")
    assert jax.config.jax_enable_compilation_cache is True


def test_compile_cache_unusable_default_runs_without_cache(
    monkeypatch, tmp_path, restore_cache_switch, caplog
):
    """A default directory that cannot be made (a read-only or odd
    install) is logged and the run goes on with no persistent cache."""
    from spark_examples_tpu.utils import compile_cache

    blocker = tmp_path / "not-a-dir"
    blocker.write_text("")
    monkeypatch.setattr(compile_cache, "_CHECKOUT_ROOT", str(blocker))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("SPARK_EXAMPLES_TPU_COMPILE_CACHE", raising=False)
    with caplog.at_level("WARNING"):
        assert compile_cache.enable_persistent_cache() is None
    assert "running without a persistent cache" in caplog.text
    assert jax.config.jax_enable_compilation_cache is False
