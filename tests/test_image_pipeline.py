"""Image input pipeline: decode (netpbm native + PNG/JPEG via Pillow),
augmentation transforms, and the input-vs-compute throughput statement
(VERDICT.md round 3 ask 8; SURVEY.md:124 'the ImageNet input path')."""

import os
import time

import numpy as np
import pytest

from deeplearning4j_tpu.data.image_transform import (
    BrightnessTransform,
    CropImageTransform,
    FlipImageTransform,
    PipelineImageTransform,
    RandomCropTransform,
    ResizeImageTransform,
    RotateImageTransform,
)
from deeplearning4j_tpu.data.records import (
    ImageRecordReader,
    RecordReaderDataSetIterator,
)


def _img(h=8, w=10, c=3, seed=0):
    return np.random.RandomState(seed).rand(h, w, c).astype(np.float32) * 255


# ---------------------------------------------------------------------------
# transforms
# ---------------------------------------------------------------------------

def test_flip_modes():
    x = _img()
    assert np.array_equal(FlipImageTransform(mode=1)(x), x[:, ::-1])
    assert np.array_equal(FlipImageTransform(mode=0)(x), x[::-1])
    assert np.array_equal(FlipImageTransform(mode=-1)(x), x[::-1, ::-1])


def test_crop_and_random_crop():
    x = _img(12, 12)
    out = CropImageTransform(top=2, left=1, bottom=3, right=2)(x)
    assert out.shape == (7, 9, 3)
    np.testing.assert_array_equal(out, x[2:9, 1:10])

    rc = RandomCropTransform(height=5, width=6)
    rng = np.random.RandomState(0)
    for _ in range(5):
        out = rc.call(x, rng)
        assert out.shape == (5, 6, 3)
    with pytest.raises(ValueError):
        RandomCropTransform(height=20, width=5)(x)


def test_rotate_right_angle_exact_and_arbitrary():
    x = _img(6, 6)
    assert np.array_equal(RotateImageTransform(angle=90)(x), np.rot90(x))
    assert np.array_equal(RotateImageTransform(angle=180)(x), np.rot90(x, 2))
    out = RotateImageTransform(angle=30)(x)  # PIL bilinear path
    assert out.shape == x.shape
    assert np.isfinite(out).all()


def test_pipeline_probability_and_order():
    x = _img()
    always = PipelineImageTransform(
        FlipImageTransform(mode=1), FlipImageTransform(mode=1))
    np.testing.assert_array_equal(always(x), x)  # double flip = identity
    never = PipelineImageTransform((BrightnessTransform(delta=100.0), 0.0))
    np.testing.assert_array_equal(never(x), x)


def test_device_batch_augmentation():
    import jax

    from deeplearning4j_tpu.data.image_transform import (
        batch_random_crop, batch_random_flip,
    )

    x = np.random.RandomState(0).rand(4, 3, 12, 12).astype(np.float32)
    key = jax.random.PRNGKey(0)
    flipped = np.asarray(jax.jit(batch_random_flip)(x, key))
    for i in range(4):
        ok_same = np.array_equal(flipped[i], x[i])
        ok_flip = np.array_equal(flipped[i], x[i][..., ::-1])
        assert ok_same or ok_flip
    cropped = jax.jit(
        lambda a, k: batch_random_crop(a, k, 8, 8))(x, key)
    assert cropped.shape == (4, 3, 8, 8)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _write_ppm(path, arr):
    h, w, _ = arr.shape
    with open(path, "wb") as f:
        f.write(f"P6 {w} {h} 255\n".encode())
        f.write(arr.astype(np.uint8).tobytes())


def test_png_and_jpeg_decode(tmp_path):
    PIL = pytest.importorskip("PIL.Image")
    rng = np.random.RandomState(0)
    arr = rng.randint(0, 256, (10, 12, 3), np.uint8)
    for cls in ("a", "b"):
        os.makedirs(tmp_path / cls, exist_ok=True)
    PIL.fromarray(arr).save(str(tmp_path / "a" / "x.png"))
    PIL.fromarray(arr).save(str(tmp_path / "b" / "y.jpg"), quality=95)
    _write_ppm(str(tmp_path / "a" / "z.ppm"), arr)

    reader = ImageRecordReader(10, 12, 3, root=str(tmp_path))
    recs = list(reader)
    assert len(recs) == 3
    assert reader.labels() == ["a", "b"]
    png_rec = recs[0][0]  # a/x.png sorts first
    # all decoders normalize to [0, 1] (the native netpbm convention)
    np.testing.assert_allclose(png_rec, arr.astype(np.float32) / 255.0,
                               atol=0.5 / 255.0)


def test_reader_applies_augmentation(tmp_path):
    rng = np.random.RandomState(0)
    arr = rng.randint(0, 256, (10, 10, 3), np.uint8)
    os.makedirs(tmp_path / "a", exist_ok=True)
    _write_ppm(str(tmp_path / "a" / "x.ppm"), arr)
    reader = ImageRecordReader(
        10, 10, 3, root=str(tmp_path),
        transform=FlipImageTransform(mode=1))
    rec = next(iter(reader))[0]
    np.testing.assert_allclose(rec, arr[:, ::-1].astype(np.float32) / 255.0,
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# throughput: input path vs compute step
# ---------------------------------------------------------------------------

def test_input_pipeline_throughput_vs_resnet_step(tmp_path, capsys):
    """The honest input-bound-vs-compute-bound statement: measure the
    augmented 224x224 input path (decode+flip+crop+batch) and compare to
    the last TPU-measured ResNet-50 step rate. Asserts a conservative
    host-throughput floor; prints the ratio for the record."""
    rng = np.random.RandomState(0)
    os.makedirs(tmp_path / "a", exist_ok=True)
    n = 48
    for i in range(n):
        _write_ppm(str(tmp_path / "a" / f"{i}.ppm"),
                   rng.randint(0, 256, (256, 256, 3), np.uint8))
    aug = PipelineImageTransform(
        (FlipImageTransform(mode=1), 0.5),
        RandomCropTransform(height=224, width=224))
    reader = ImageRecordReader(224, 224, 3, root=str(tmp_path), transform=aug)
    it = RecordReaderDataSetIterator(reader, batch_size=16, label_index=1,
                                     num_classes=1)
    start = time.perf_counter()
    seen = sum(ds.features.shape[0] for ds in it)
    rate = seen / (time.perf_counter() - start)
    assert seen == n
    assert rate > 30  # single slow core; TPU feeding needs parallel workers
    resnet_tpu_sps = 1794.89  # 2026-07, another machine's v5e (ROADMAP S1)
    with capsys.disabled():
        print(f"\n[input-pipeline] {rate:.0f} img/s host vs "
              f"{resnet_tpu_sps:.0f} samples/s ResNet-50/TPU -> "
              f"need ~{resnet_tpu_sps / rate:.1f} input workers")


# ---- round-5 input-pipeline (VERDICT r4 ask 2) ----------------------------


def _make_ppm_tree(tmp_path, n=12, size=32):
    rng = np.random.RandomState(0)
    header = f"P6 {size} {size} 255\n".encode()
    for cls in ("a", "b"):
        (tmp_path / cls).mkdir(exist_ok=True)
    for i in range(n):
        body = rng.randint(0, 256, (size, size, 3), np.uint8).tobytes()
        (tmp_path / "ab"[i % 2] / f"{i}.ppm").write_bytes(header + body)
    return str(tmp_path)


def test_uint8_reader_matches_float_reader(tmp_path):
    from deeplearning4j_tpu.data.image_transform import CropImageTransform
    from deeplearning4j_tpu.data.records import ImageRecordReader

    root = _make_ppm_tree(tmp_path, n=6)
    crop = CropImageTransform(top=4, left=4, bottom=4, right=4)
    u8 = list(ImageRecordReader(24, 24, 3, root=root, transform=crop,
                                output_dtype="uint8"))
    f32 = list(ImageRecordReader(24, 24, 3, root=root, transform=crop))
    assert len(u8) == len(f32) == 6
    for (a, la), (b, lb) in zip(u8, f32):
        assert a.dtype == np.uint8 and b.dtype == np.float32
        assert la == lb
        np.testing.assert_allclose(a.astype(np.float32) / 255.0, b,
                                   atol=1e-6)


def test_uint8_reader_rejects_value_transforms(tmp_path):
    from deeplearning4j_tpu.data.image_transform import BrightnessTransform
    from deeplearning4j_tpu.data.records import ImageRecordReader

    root = _make_ppm_tree(tmp_path, n=2)
    reader = ImageRecordReader(32, 32, 3, root=root,
                               transform=BrightnessTransform(delta=0.1),
                               output_dtype="uint8")
    with pytest.raises(ValueError, match="uint8"):
        next(iter(reader))


def test_parallel_reader_preserves_order_and_content(tmp_path):
    from deeplearning4j_tpu.data.records import ImageRecordReader

    root = _make_ppm_tree(tmp_path, n=16)
    serial = list(ImageRecordReader(32, 32, 3, root=root,
                                    output_dtype="uint8"))
    parallel = list(ImageRecordReader(32, 32, 3, root=root,
                                      output_dtype="uint8", workers=4))
    assert len(serial) == len(parallel) == 16
    for (a, la), (b, lb) in zip(serial, parallel):
        np.testing.assert_array_equal(a, b)
        assert la == lb


def test_uint8_batches_flow_to_device_augment_and_fit(tmp_path):
    """End-to-end: u8 files -> RecordReader -> async prefetch+device_put ->
    jitted on-device augment (crop+cast+scale) -> train step. The host
    never touches a float pixel (SURVEY.md §3.1 I/O-overlap boundary)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.data.image_transform import batch_random_crop
    from deeplearning4j_tpu.data.iterators import (
        AsyncDataSetIterator, MappedDataSetIterator, device_put_dataset,
    )
    from deeplearning4j_tpu.data.records import (
        ImageRecordReader, RecordReaderDataSetIterator,
    )
    from deeplearning4j_tpu.nn import (
        Activation, InputType, LossFunction, NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.layers import (
        ConvolutionLayer, GlobalPoolingLayer, OutputLayer, PoolingType,
    )
    from deeplearning4j_tpu.nn.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.train.solver import Solver

    root = _make_ppm_tree(tmp_path, n=8, size=32)
    reader = ImageRecordReader(32, 32, 3, root=root, output_dtype="uint8")
    base = RecordReaderDataSetIterator(reader, batch_size=4, label_index=1,
                                       num_classes=2)
    key = jax.random.PRNGKey(0)

    def prep(features):  # [b, h, w, c] u8 -> [b, c, 24, 24] f32 in [0,1]
        x = jnp.transpose(jnp.asarray(features), (0, 3, 1, 2))
        x = x.astype(jnp.float32) / 255.0
        return batch_random_crop(x, key, 24, 24)

    it = MappedDataSetIterator(
        AsyncDataSetIterator(base, device_put_fn=device_put_dataset),
        feature_fn=jax.jit(prep))

    lb = (NeuralNetConfiguration.builder().seed(3).list()
          .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3)))
          .layer(GlobalPoolingLayer(pooling_type=PoolingType.AVG))
          .layer(OutputLayer(n_out=2, loss=LossFunction.MCXENT,
                             activation=Activation.SOFTMAX)))
    lb.set_input_type(InputType.convolutional(24, 24, 3))
    net = MultiLayerNetwork(lb.build()).init()
    solver = Solver(net)
    n = 0
    for ds in it:
        assert ds.features.dtype == jnp.float32
        score = float(solver.fit_batch(ds.features, ds.labels)[0])
        assert np.isfinite(score)
        n += ds.features.shape[0]
    assert n == 8


def test_record_iterator_multi_epoch_reset(tmp_path):
    """Regression: reset() must clear the protocol lookahead so wrappers
    like MultipleEpochsIterator see every epoch, not just the first."""
    from deeplearning4j_tpu.data.iterators import MultipleEpochsIterator
    from deeplearning4j_tpu.data.records import (
        ImageRecordReader, RecordReaderDataSetIterator,
    )

    root = _make_ppm_tree(tmp_path, n=8)
    reader = ImageRecordReader(32, 32, 3, root=root, output_dtype="uint8")
    base = RecordReaderDataSetIterator(reader, batch_size=4, label_index=1,
                                       num_classes=2)
    assert base.batch_size() == 4
    it = MultipleEpochsIterator(base, epochs=3)
    it.reset()
    n = 0
    while it.has_next():
        n += it.next().features.shape[0]
    assert n == 24  # 8 images x 3 epochs
    assert it.batch_size() == 4


def test_uint8_netpbm_parser_comments_maxval_trailing(tmp_path):
    """The u8 fast-path netpbm parser must match the native float parser's
    front-anchored semantics: '#' comments, maxval rescale, and files with
    trailing bytes after the raster."""
    from deeplearning4j_tpu.data.records import ImageRecordReader

    rng = np.random.RandomState(0)
    px = rng.randint(0, 256, (8, 8, 3), np.uint8)
    (tmp_path / "a").mkdir()
    # comment line + trailing newline after raster
    body = b"P6\n# a comment\n8 8\n255\n" + px.tobytes() + b"\n"
    (tmp_path / "a" / "x.ppm").write_bytes(body)
    r = ImageRecordReader(8, 8, 3, root=str(tmp_path), output_dtype="uint8")
    got = next(iter(r))[0]
    np.testing.assert_array_equal(got, px)
    # maxval 127 rescales to the full byte range
    px7 = (px // 2).astype(np.uint8)
    (tmp_path / "a" / "x.ppm").write_bytes(
        b"P6 8 8 127\n" + px7.tobytes())
    r2 = ImageRecordReader(8, 8, 3, root=str(tmp_path), output_dtype="uint8")
    got2 = next(iter(r2))[0]
    assert got2.max() > 200  # rescaled toward 255
    # ROUNDED rescale: the uint8 fast path must match the float decoder
    # within rounding (ADVICE round-5 item 2 — floor division diverged
    # by up to 1 LSB)
    rf = ImageRecordReader(8, 8, 3, root=str(tmp_path),
                           output_dtype="float32")
    fgot = next(iter(rf))[0]  # [0,1] floats
    np.testing.assert_array_equal(got2, np.rint(fgot * 255).astype(np.uint8))
    # 16-bit rejected loudly
    (tmp_path / "a" / "x.ppm").write_bytes(
        b"P6 8 8 65535\n" + (b"\0" * (8 * 8 * 3 * 2)))
    r3 = ImageRecordReader(8, 8, 3, root=str(tmp_path), output_dtype="uint8")
    with pytest.raises(ValueError, match="16-bit"):
        next(iter(r3))
