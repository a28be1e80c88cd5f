"""ViT batch-inference throughput (BASELINE.json config 5: ViT-class image
classification through Ray-Data-style streaming into a device actor pool).

Pipeline measured end-to-end: read_images (decode+resize) -> ImageNormalizer
-> map_batches(ViTPredictor actors). On a TPU host the predictor actor
reserves a chip and runs ViT-L/16 there (bf16); on a host without a chip it
is a host benchmark of the same pipeline with a scaled-down model, and says
so in its "device" and "model" fields. Writes benchmarks/VIT_INFER.json
(the committed copy is a host run: "device": "cpu").

Run from the repo root: python benchmarks/vit_infer.py
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import tempfile
import time


def make_images(n: int, hw: int, out_dir: str) -> str:
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(0)
    for i in range(n):
        arr = rng.integers(0, 255, (hw, hw, 3), np.uint8)
        Image.fromarray(arr).save(os.path.join(out_dir, f"im_{i:05d}.jpg"),
                                  quality=85)
    return out_dir


class VitPredictor:
    """Stateful device predictor: params live on the device across batches
    (reference actor_pool_map_operator.py:289 GPU-actor UDFs)."""

    def __init__(self, use_tpu: bool):
        import functools

        import jax

        from ray_tpu.models import vit

        self.cfg = (vit.vit_l16() if use_tpu
                    else vit.vit_tiny(image_size=224, patch_size=16,
                                      num_classes=1000))
        self.params = jax.jit(
            lambda k: vit.init_params(k, self.cfg))(jax.random.key(0))
        self.fwd = jax.jit(functools.partial(vit.forward, cfg=self.cfg))

    def __call__(self, batch):
        import numpy as np

        logits = np.asarray(self.fwd(self.params, batch["image"]))
        return {"pred": logits.argmax(-1)}


def main():
    # The pool actor's worker decides the device, not this driver: with a
    # chip the actor reserves it (num_tpus=1) and its worker can only run
    # JAX on the tpu platform; without one it is a plain worker pinned to
    # the cpu platform (core/worker_env.py). The driver never touches JAX.
    from ray_tpu.util.accelerators import detect_tpu_chips

    use_tpu = detect_tpu_chips() > 0
    n_images, batch = (512, 32) if use_tpu else (96, 16)

    import ray_tpu
    from ray_tpu import data as rd
    from ray_tpu.data.preprocessors import ImageNormalizer

    ray_tpu.init(num_cpus=4)
    with tempfile.TemporaryDirectory() as d:
        make_images(n_images, 224, d)
        ds = rd.read_images(d, size=(224, 224))
        ds = ImageNormalizer().transform(ds)
        ds = ds.map_batches(
            VitPredictor, batch_size=batch, concurrency=1,
            fn_constructor_kwargs={"use_tpu": use_tpu},
            batch_format="numpy",
            num_tpus=1 if use_tpu else None,
        )
        # Warm pass compiles the model inside the pool actor.
        t0 = time.perf_counter()
        rows = ds.take_all()
        dt = time.perf_counter() - t0
    assert len(rows) == n_images
    out = {
        "metric": "vit_infer_images_per_s",
        "value": round(n_images / dt, 1),
        "unit": "images/s",
        "model": "ViT-L/16" if use_tpu else "ViT-tiny(224)",
        "images": n_images,
        "batch_size": batch,
        "device": "tpu" if use_tpu else "cpu",
        "wall_s": round(dt, 2),
        "note": "end-to-end: decode+resize -> normalize -> device actor "
                "pool (includes first-batch compile)",
    }
    print(json.dumps(out), flush=True)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "VIT_INFER.json"), "w") as f:
        json.dump(out, f, indent=1)
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
