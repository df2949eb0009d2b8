"""The three infeed strategies, fastest host work first.

The north-star bottleneck is host-side decode/resize + host->device
transfer (BASELINE.md), so the framework offers three ways to feed a
model, trading host CPU work for device work:

1. classic     readImages -> host resize/pack (C++ shim) -> model
2. fused host  readImagesPacked: JPEG decode + resize + NHWC pack in
               ONE native call per partition -> TensorTransformer
3. device      readImages -> pack at native size (zero-copy Arrow
               views) -> deviceResizeFrom: bilinear resize fused INTO
               the model's XLA program (Pallas kernel on real TPU) —
               host CPUs only decode
4. yuv420      readImagesPacked(packedFormat="yuv420"): ship planar
               YCbCr 4:2:0 at 1.5 B/px — HALF the link bytes — with
               chroma upsample + BT.601 reconstruction + resize fused
               on-device (standard 4:2:0 JPEGs stream out of libjpeg
               raw, skipping host chroma work entirely)

The packed readers (2 and 4) additionally prescale in the DCT domain
by default (scaledDecode=True: libjpeg decodes at the largest
power-of-two shrink still covering the target — PIL-draft semantics,
most IDCT work skipped; scaledDecode=False restores full-res-decode
pixels).

Run on CPU:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/fast_infeed.py
"""

import tempfile

import numpy as np
from PIL import Image

import sparkdl_tpu
from sparkdl_tpu.data.engine import LocalEngine
from sparkdl_tpu.image import imageIO
from sparkdl_tpu.models.zoo import getModelFunction
from sparkdl_tpu.utils import StageMetrics


def make_images(n=12, hw=(48, 64)):
    d = tempfile.mkdtemp(prefix="sparkdl_tpu_infeed_")
    rng = np.random.default_rng(7)
    for i in range(n):
        arr = rng.integers(0, 255, (*hw, 3), dtype=np.uint8)
        Image.fromarray(arr, "RGB").save(f"{d}/img_{i}.jpg", quality=92)
    return d


def main():
    d = make_images()
    metrics = StageMetrics()
    engine = LocalEngine(stage_metrics=metrics)

    # 1. classic: host resize inside packImageBatch
    df = sparkdl_tpu.readImages(d, numPartitions=3, engine=engine)
    classic = sparkdl_tpu.DeepImageFeaturizer(
        modelName="TestNet", inputCol="image", outputCol="f"
    ).transform(df).tensor("f")

    # 2. fused host reader: decode+resize+pack in one native call,
    #    then a tensor column straight into the model
    packed = imageIO.readImagesPacked(d, (32, 32), numPartitions=3,
                                      engine=engine)
    fused = sparkdl_tpu.TensorTransformer(
        modelFunction=getModelFunction("TestNet", featurize=True),
        inputMapping={"image": "image"},
        outputMapping={"features": "f"},
    ).transform(packed).tensor("f")

    # 3. device resize: host only decodes; resample runs on-device,
    #    fused into the model program
    device = sparkdl_tpu.DeepImageFeaturizer(
        modelName="TestNet", inputCol="image", outputCol="f",
        deviceResizeFrom=(48, 64)
    ).transform(df).tensor("f")

    # 4. half-the-bytes 4:2:0 ship, reconstruction fused on-device
    from sparkdl_tpu.transformers.utils import deviceResizeModel, single_io
    mf420 = deviceResizeModel(getModelFunction("TestNet", featurize=True),
                              (24, 24), packedFormat="yuv420")
    in420, out420 = single_io(mf420)
    packed420 = imageIO.readImagesPacked(d, (24, 24), numPartitions=3,
                                         packedFormat="yuv420",
                                         engine=engine)
    yuv = sparkdl_tpu.TensorTransformer(
        modelFunction=mf420, inputMapping={"image": in420},
        outputMapping={out420: "f"},
    ).transform(packed420).tensor("f")

    assert classic.shape == fused.shape == device.shape == yuv.shape
    # different resamplers (host bilinear / native fused / device AA
    # bilinear) agree closely on features
    c = np.corrcoef(classic.ravel(), device.ravel())[0, 1]
    c420 = np.corrcoef(classic.ravel(), yuv.ravel())[0, 1]
    print(f"feature shape {classic.shape}; "
          f"classic-vs-device correlation {c:.4f}; "
          f"classic-vs-yuv420 {c420:.4f}")
    print("per-stage metrics (rows/sec):")
    print(metrics.report())


if __name__ == "__main__":
    main()
