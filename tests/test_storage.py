"""Tests for the storage pool + resource manager.

Models ``tests/cpp/storage_test.cc`` (alloc/free reuse round-trip) and the
resource-manager seeding behavior of ``src/resource.cc``."""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.storage import Storage, device_memory_stats, _round_size
from mxnet_tpu.resource import Resource, ResourceManager, ResourceRequest


def test_round_size_buckets():
    assert _round_size(1) == 32
    assert _round_size(32) == 32
    assert _round_size(33) == 64
    assert _round_size(1000) == 1024


def test_alloc_free_reuse():
    st = Storage.get()
    ctx = mx.cpu(7)  # private bucket for this test
    base = st.used_memory(ctx)
    h1 = st.alloc(1000, ctx)
    assert h1.size == 1000 and h1.data.nbytes == 1024
    assert st.used_memory(ctx) - base == 1024
    buf_id = id(h1.data)
    st.free(h1)
    assert st.used_memory(ctx) == base
    assert st.pooled_memory(ctx) >= 1024
    # same-bucket alloc must recycle the pooled block (storage_test.cc's
    # "reuse" assertion)
    h2 = st.alloc(900, ctx)
    assert id(h2.data) == buf_id
    st.free(h2)
    assert st.peak_memory(ctx) - base >= 1024


def test_double_free_safe_and_release_all():
    st = Storage.get()
    ctx = mx.cpu(8)
    h = st.alloc(64, ctx)
    st.free(h)
    st.free(h)  # no-op
    assert st.used_memory(ctx) == 0
    st.release_all(ctx)
    assert st.pooled_memory(ctx) == 0
    h2 = st.alloc(64, ctx)
    st.direct_free(h2)
    assert st.used_memory(ctx) == 0 and h2.data is None


def test_device_memory_stats_shape():
    stats = device_memory_stats(mx.cpu())
    assert isinstance(stats, dict)  # CPU backend may report nothing


def test_temp_space_grows_monotonically():
    res = ResourceManager.get().request(
        mx.cpu(9), ResourceRequest(ResourceRequest.kTempSpace))
    a = res.get_space(100)
    assert a.nbytes >= 100
    b = res.get_space(50)   # smaller request reuses the same buffer
    assert b.nbytes >= 50
    c = res.get_host_space((4, 5), np.float32)
    assert c.shape == (4, 5) and c.dtype == np.float32


def test_random_resource_reproducible():
    mgr = ResourceManager.get()
    res = mgr.request(mx.cpu(9), ResourceRequest(ResourceRequest.kRandom))
    res.seed(42)
    import jax
    k1 = res.get_key()
    k2 = res.get_key()
    assert not np.array_equal(jax.random.key_data(k1),
                              jax.random.key_data(k2))
    res.seed(42)
    k1b = res.get_key()
    np.testing.assert_array_equal(jax.random.key_data(k1),
                                  jax.random.key_data(k1b))


def test_manager_shares_per_context():
    mgr = ResourceManager.get()
    r1 = mgr.request(mx.cpu(9), ResourceRequest(ResourceRequest.kTempSpace))
    r2 = mgr.request(mx.cpu(9), ResourceRequest(ResourceRequest.kTempSpace))
    assert r1 is r2


def test_storage_concurrent_double_free():
    import threading
    from mxnet_tpu.storage import Storage
    st = Storage.get()
    ctx = mx.cpu(11)
    h = st.alloc(128, ctx)
    threads = [threading.Thread(target=st.free, args=(h,)) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    # exactly one free must take effect
    assert st.used_memory(ctx) == 0
    assert st.pooled_memory(ctx) == 128  # one 128B bucket entry, not 8
