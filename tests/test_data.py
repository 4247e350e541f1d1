"""Input pipeline (apex_tpu.data): ImageFolder contract, DP sharding,
augmentation determinism, on-device normalization.

Reference contract: ``examples/imagenet/main_amp.py:207-232`` (ImageFolder
+ RandomResizedCrop/flip + DistributedSampler) and ``fast_collate``/
prefetcher normalize (``:48-63,256-276``).
"""

import numpy as np
import pytest

from apex_tpu.data import (
    ImageFolder,
    ImageFolderLoader,
    center_crop_resize,
    normalize_on_device,
    random_resized_crop,
    synthetic_image_batches,
)


@pytest.fixture(scope="module")
def image_root(tmp_path_factory):
    """Tiny 2-class x 8-image folder tree (PNG, varied sizes)."""
    from PIL import Image

    root = tmp_path_factory.mktemp("imgs")
    rng = np.random.RandomState(0)
    for cls in ("cat", "dog"):
        d = root / cls
        d.mkdir()
        for i in range(8):
            h, w = rng.randint(40, 80), rng.randint(40, 80)
            arr = rng.randint(0, 256, (h, w, 3), dtype=np.uint8)
            Image.fromarray(arr).save(d / f"img_{i}.png")
    return str(root)


def test_image_folder_scan(image_root):
    ds = ImageFolder(image_root)
    assert ds.classes == ["cat", "dog"]  # sorted subdirs
    assert ds.class_to_idx == {"cat": 0, "dog": 1}
    assert len(ds) == 16
    img, label = ds.load(0)
    assert label == 0 and img.mode == "RGB"
    _, label_last = ds.load(15)
    assert label_last == 1


def test_transforms_shapes_and_determinism(image_root):
    ds = ImageFolder(image_root)
    img, _ = ds.load(3)
    a = random_resized_crop(np.random.RandomState(7), img, 32)
    b = random_resized_crop(np.random.RandomState(7), img, 32)
    c = random_resized_crop(np.random.RandomState(8), img, 32)
    assert a.shape == (32, 32, 3) and a.dtype == np.uint8
    np.testing.assert_array_equal(a, b)  # same seed, same crop
    assert not np.array_equal(a, c)      # different seed, different crop

    e = center_crop_resize(img, 32)
    assert e.shape == (32, 32, 3) and e.dtype == np.uint8
    np.testing.assert_array_equal(e, center_crop_resize(img, 32))


def test_loader_dp_sharding(image_root):
    """Global batches carry dp disjoint per-rank rows; epoch-deterministic."""
    ds = ImageFolder(image_root)
    mk = lambda: ImageFolderLoader(  # noqa: E731
        ds, local_batch=2, data_parallel_size=2, image_size=16, seed=1)
    x, y = next(iter(mk()))
    assert x.shape == (4, 16, 16, 3) and x.dtype == np.uint8
    assert y.shape == (4,) and y.dtype == np.int32
    x2, y2 = next(iter(mk()))
    np.testing.assert_array_equal(x, x2)  # same consumed_samples, same batch
    np.testing.assert_array_equal(y, y2)

    # the two rank windows come from disjoint sampler buckets: one epoch of
    # per-rank sample indices must not intersect
    loader = mk()
    rank_indices = [set(), set()]
    for per_rank in zip(*loader.samplers):
        for r, ids in enumerate(per_rank):
            rank_indices[r].update(ids)
    assert rank_indices[0] and rank_indices[1]
    assert not rank_indices[0] & rank_indices[1], rank_indices
    assert loader.consumed_samples > 0  # iterating advanced the epoch state


def test_loader_prefetch_determinism(image_root):
    """Prefetch depth never changes the delivered batch stream (samples,
    order, or augmentation)."""
    ds = ImageFolder(image_root)
    mk = lambda pf: ImageFolderLoader(  # noqa: E731
        ds, local_batch=2, data_parallel_size=2, image_size=16, seed=1,
        prefetch=pf)
    import itertools

    with mk(0) as sync_loader, mk(3) as pf_loader:
        sync_batches = list(itertools.islice(iter(sync_loader), 3))
        pf_batches = list(itertools.islice(iter(pf_loader), 3))
    for (xs, ys), (xp, yp) in zip(sync_batches, pf_batches):
        np.testing.assert_array_equal(xs, xp)
        np.testing.assert_array_equal(ys, yp)


def test_loader_prefetch_consumed_samples(image_root):
    """consumed_samples counts *yielded* batches only, and an abandoned
    iterator rewinds its in-flight batches (checkpoint-resume contract)."""
    ds = ImageFolder(image_root)
    with ImageFolderLoader(ds, local_batch=2, data_parallel_size=2,
                           image_size=16, seed=1, prefetch=2) as loader:
        it = iter(loader)
        a = next(it)
        assert loader.consumed_samples == 4  # one global batch delivered
        b = next(it)
        assert loader.consumed_samples == 8
        it.close()  # abandon with batches still in flight
        assert loader.consumed_samples == 8
        # a fresh iterator resumes at the first undelivered batch: it must
        # not replay batch 1 or 2
        c = next(iter(loader))
        assert loader.consumed_samples == 12
    assert not (np.array_equal(a[0], c[0]) or np.array_equal(b[0], c[0]))


def test_loader_prefetch_overlaps_decode(image_root, monkeypatch):
    """With a slow consumer, prefetch hides decode latency: total wall
    time ~= consumer time, not consumer + decode.  Slowness is injected
    at the decode-core seam (``_decode_one`` — the one function both
    worker backends run), since decode no longer flows through
    ``dataset.load``."""
    import time

    from apex_tpu.data import image_folder as ifm

    ds = ImageFolder(image_root)
    real_decode = ifm._decode_one

    def slow_decode(spec, index, marker):
        time.sleep(0.05)
        return real_decode(spec, index, marker)

    monkeypatch.setattr(ifm, "_decode_one", slow_decode)

    def run(pf):
        with ImageFolderLoader(ds, local_batch=4, image_size=16,
                               seed=1, workers=4, prefetch=pf) as loader:
            it = iter(loader)
            next(it)  # warm: first batch always pays full decode latency
            t0 = time.perf_counter()
            for _ in range(2):
                time.sleep(0.1)  # the "train step"
                next(it)
            return time.perf_counter() - t0

    # sync: each step pays 0.1 consumer + ~0.05 decode; prefetch: decode
    # hides under the consumer sleep.  Generous margins for CI jitter.
    assert run(2) < run(0) - 0.05


def test_normalize_on_device_matches_numpy():
    import jax

    x = np.random.RandomState(0).randint(
        0, 256, (2, 8, 8, 3), dtype=np.uint8)
    out = jax.jit(normalize_on_device)(x)
    mean = np.array([0.485, 0.456, 0.406], np.float32)
    std = np.array([0.229, 0.224, 0.225], np.float32)
    ref = (x.astype(np.float32) / 255.0 - mean) / std
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_prefetch_to_device_sharding_and_order():
    """Batches come back on-device, dp-sharded, in order, depth ahead."""
    import itertools

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import parallel
    from apex_tpu.data import prefetch_to_device

    mesh = parallel.initialize_model_parallel()
    try:
        host = list(itertools.islice(synthetic_image_batches(8, 8, 10), 4))
        dev = list(prefetch_to_device(iter(host), mesh, depth=2))
        assert len(dev) == 4
        want = NamedSharding(mesh, P(("dcn", "dp"), None, None, None))
        for (hx, hy), (dx, dy) in zip(host, dev):
            assert dx.sharding.is_equivalent_to(want, dx.ndim)
            np.testing.assert_array_equal(np.asarray(dx), hx)
            np.testing.assert_array_equal(np.asarray(dy), hy)
    finally:
        parallel.mesh.destroy_model_parallel()


def test_prefetch_to_device_resume_composition(image_root):
    """The documented resume recipe: re-wrapping a restored loader with
    prefetch_to_device continues the exact batch stream (the loader
    rewinds its own in-flight decode; the device wrapper adds no state)."""
    import itertools

    from apex_tpu.data import prefetch_to_device

    ds = ImageFolder(image_root)

    def run(consumed, n):
        with ImageFolderLoader(ds, local_batch=4, image_size=16, seed=3,
                               prefetch=2, consumed_samples=consumed) as ld:
            dev = prefetch_to_device(ld, depth=2)
            out = [(np.asarray(x), np.asarray(y))
                   for x, y in itertools.islice(dev, n)]
            # checkpoint the WRAPPER's count: the loader's own runs ahead
            # by the device queue (dev.in_flight batches)
            assert dev.consumed_samples == ld.consumed_samples - (
                dev.in_flight * 4)
            return out, dev.consumed_samples

    full, _ = run(0, 3)
    head, consumed = run(0, 1)
    assert consumed == 4  # one delivered batch, despite prefetch depth 2
    # crash/restore: a fresh loader + wrapper from the checkpointed
    # consumed_samples picks up at the first undelivered batch
    tail, _ = run(consumed, 2)
    for (ax, ay), (bx, by) in zip(full[1:], tail):
        np.testing.assert_array_equal(ax, bx)
        np.testing.assert_array_equal(ay, by)


def test_prefetch_to_device_plain_device_put():
    """Without a mesh, falls back to plain device_put; depth=0 works."""
    import jax

    from apex_tpu.data import prefetch_to_device

    host = [np.arange(6, dtype=np.float32).reshape(2, 3) + i
            for i in range(3)]
    out = list(prefetch_to_device(host, depth=0))
    assert len(out) == 3
    for h, d in zip(host, out):
        assert isinstance(d, jax.Array)
        np.testing.assert_array_equal(np.asarray(d), h)


def test_synthetic_batches_contract():
    it = synthetic_image_batches(4, 16, 10)
    x, y = next(it)
    assert x.shape == (4, 16, 16, 3) and x.dtype == np.uint8
    assert y.shape == (4,) and y.dtype == np.int32
    assert y.max() < 10


# ---------------------------------------------------------------------------
# ISSUE 8: process-pool backend, per-host sharding, double-buffered
# prefetch stall metric, composition enforcement, data service
# ---------------------------------------------------------------------------


def test_process_backend_matches_thread_backend(image_root):
    """The process pool delivers the SAME batches (samples, order,
    augmentation) as the thread pool — the decode core is one pure
    function, so the backend is a pure throughput knob."""
    import itertools

    ds = ImageFolder(image_root)

    def batches(backend):
        with ImageFolderLoader(ds, local_batch=2, data_parallel_size=2,
                               image_size=16, seed=1, workers=2,
                               backend=backend) as loader:
            return list(itertools.islice(iter(loader), 3))

    for (xt, yt), (xp, yp) in zip(batches("thread"), batches("process")):
        np.testing.assert_array_equal(xt, xp)
        np.testing.assert_array_equal(yt, yp)


def test_unknown_backend_rejected(image_root):
    with pytest.raises(ValueError, match="backend"):
        ImageFolderLoader(ImageFolder(image_root), local_batch=2,
                          backend="dali")


def test_dp_ranks_host_shard_window(image_root):
    """A dp_ranks-restricted loader yields exactly its ranks' windows of
    the full global batch, with GLOBAL consumed_samples — each host
    decodes only its own shards, one checkpoint integer resumes all."""
    ds = ImageFolder(image_root)
    with ImageFolderLoader(ds, local_batch=2, data_parallel_size=2,
                           image_size=16, seed=1) as full, \
            ImageFolderLoader(ds, local_batch=2, data_parallel_size=2,
                              image_size=16, seed=1,
                              dp_ranks=[1]) as host1:
        xf, yf = next(iter(full))
        x1, y1 = next(iter(host1))
    assert x1.shape == (2, 16, 16, 3)
    np.testing.assert_array_equal(x1, xf[2:])
    np.testing.assert_array_equal(y1, yf[2:])
    assert host1.consumed_samples == full.consumed_samples == 4
    with pytest.raises(ValueError, match="dp_ranks"):
        ImageFolderLoader(ds, local_batch=2, data_parallel_size=2,
                          dp_ranks=[2])


def test_host_dp_ranks_and_local_placement():
    """host_dp_ranks covers all shards in a single process, and
    dp_shard_batch(local_ranks=...) assembles the identical global
    array; a rank set that misses an addressable shard raises."""
    from apex_tpu import parallel
    from apex_tpu.parallel.distributed import dp_shard_batch, host_dp_ranks

    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=2)  # dp=4, tp=2: shards replicate on tp
    try:
        ranks = host_dp_ranks(mesh)
        assert ranks == [0, 1, 2, 3]
        x = np.arange(8 * 3, dtype=np.float32).reshape(8, 3)
        y = np.float32(0.5)  # scalar leaf replicates
        ga, sa = dp_shard_batch((x, y), mesh)
        gb, sb = dp_shard_batch((x, y), mesh, local_ranks=ranks)
        np.testing.assert_array_equal(np.asarray(ga), np.asarray(gb))
        assert ga.sharding.is_equivalent_to(gb.sharding, ga.ndim)
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))
        with pytest.raises(ValueError, match="local_ranks"):
            dp_shard_batch(x[:2], mesh, local_ranks=[0])
    finally:
        parallel.mesh.destroy_model_parallel()


def test_prefetch_records_stall_metric(image_root):
    """Every delivered batch records its blocking wait into the
    data/stall_ms gauge + span_ms/data/next_wait histogram."""
    from apex_tpu.data import prefetch_to_device
    from apex_tpu.observability.metrics import MetricRegistry

    reg = MetricRegistry(rank=0, world=1)
    ds = ImageFolder(image_root)
    with ImageFolderLoader(ds, local_batch=4, image_size=16,
                           seed=1) as loader:
        dev = prefetch_to_device(loader, depth=2, place=lambda b: b,
                                 registry=reg)
        for _ in range(3):
            next(dev)
        dev.close(close_source=False)
    assert reg.gauge("data/stall_ms").value is not None
    hist = reg.histogram("span_ms/data/next_wait")
    assert hist.count == 3
    assert hist.mean is not None and hist.mean >= 0.0


def test_nested_prefetcher_rejected():
    from apex_tpu.data import prefetch_to_device

    inner = prefetch_to_device([np.zeros(2)], depth=0)
    with pytest.raises(TypeError, match="nested"):
        prefetch_to_device(inner)


def test_prefetcher_plain_iterator_has_no_resume_surface():
    """A plain iterator wraps fine for streaming, but consumed_samples
    names the composition contract instead of mis-counting."""
    from apex_tpu.data import prefetch_to_device

    dev = prefetch_to_device(iter([np.zeros(2), None, np.ones(2)]),
                             depth=0, place=lambda b: b)
    with pytest.raises(AttributeError, match="composition order"):
        dev.consumed_samples
    # a legitimately-None item is DELIVERED, not conflated with
    # exhaustion (the old next(it, None) bug)
    out = list(dev)
    assert len(out) == 3 and out[1] is None


def test_prefetcher_close_passthrough_and_rewind(image_root):
    """close() stops the transfer thread, rewinds undelivered batches on
    the source samplers, and shuts the loader's decode pool — the leak
    satellite.  After close, loader and wrapper agree."""
    from apex_tpu.data import prefetch_to_device

    ds = ImageFolder(image_root)
    loader = ImageFolderLoader(ds, local_batch=4, image_size=16, seed=3,
                               prefetch=2)
    dev = prefetch_to_device(loader, depth=2, place=lambda b: b)
    next(dev)
    dev.close()  # passthrough: also closes the loader
    assert dev.consumed_samples == 4
    assert loader.consumed_samples == 4
    # the decode pool is really closed: submitting to it must fail
    with pytest.raises(RuntimeError):
        loader._pool.submit(int, 0)
    # idempotent
    dev.close()


def _image_loader_factory(root: str, consumed: int):
    """Module-level (picklable) DataService factory."""
    from apex_tpu.data import ImageFolder, ImageFolderLoader

    return ImageFolderLoader(ImageFolder(root), local_batch=4,
                             image_size=16, seed=1, workers=2,
                             consumed_samples=consumed)


def test_data_service_streams_and_resumes(image_root):
    """DataService: the loader lives in a dedicated process; batches,
    the resume surface, and prefetch_to_device composition all match the
    in-process loader."""
    import functools

    from apex_tpu.data import DataService, prefetch_to_device

    factory = functools.partial(_image_loader_factory, image_root)
    with _image_loader_factory(image_root, 0) as ref_loader:
        ref = [next(iter(ref_loader))]
        it = iter(ref_loader)
    with DataService(factory) as svc:
        assert (svc.local_batch, svc.dp) == (4, 1)
        x, y = next(svc)
        np.testing.assert_array_equal(x, ref[0][0])
        np.testing.assert_array_equal(y, ref[0][1])
        assert svc.consumed_samples == 4
        # crosses the epoch boundary without ending the stream
        for _ in range(4):
            next(svc)
        assert svc.consumed_samples == 20
    # resume mid-stream: a fresh service continues bit-exact
    with DataService(factory) as a:
        first = [next(a) for _ in range(3)]
    with DataService(factory, consumed_samples=8) as b:
        cont = next(b)
    np.testing.assert_array_equal(cont[0], first[2][0])
    np.testing.assert_array_equal(cont[1], first[2][1])
    # prefetch composes on top (the documented stack)
    with DataService(factory) as svc:
        dev = prefetch_to_device(svc, depth=1, place=lambda t: t)
        next(dev)
        assert dev.consumed_samples == 4
        # close_source=False must leave the service alive even though a
        # self-iterating source IS its own iterator (the re-wrap shape)
        dev.close(close_source=False)
        next(svc)
        dev2 = prefetch_to_device(svc, depth=1, place=lambda t: t)
        next(dev2)
        dev2.close()  # full close reaps the service


def _process_loader_factory(root: str, consumed: int):
    from apex_tpu.data import ImageFolder, ImageFolderLoader

    return ImageFolderLoader(ImageFolder(root), local_batch=4,
                             image_size=16, seed=1, workers=2,
                             backend="process",
                             consumed_samples=consumed)


def test_data_service_hosts_process_backend_loader(image_root):
    """The documented composition: a DataService whose loader itself
    runs a process pool.  Requires the service process to be
    NON-daemonic (daemonic processes may not have children) — pinned
    here because the failure mode is a fatal relayed AssertionError on
    the first batch."""
    import functools

    from apex_tpu.data import DataService

    factory = functools.partial(_process_loader_factory, image_root)
    with DataService(factory) as svc:
        x, y = next(svc)
        assert x.shape == (4, 16, 16, 3) and y.shape == (4,)
        assert svc.consumed_samples == 4
    # matches the in-process loader bitwise
    with _process_loader_factory(image_root, 0) as ref:
        xr, yr = next(iter(ref))
    np.testing.assert_array_equal(x, xr)
    np.testing.assert_array_equal(y, yr)


def test_data_service_relays_loader_errors():
    import functools

    from apex_tpu.data import DataService

    factory = functools.partial(_image_loader_factory, "/nonexistent/dir")
    with DataService(factory) as svc:
        with pytest.raises(Exception):
            next(svc)
