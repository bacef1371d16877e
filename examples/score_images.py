"""Frozen-model image scoring through the verbs — the reference's flagship
workload (``/root/reference/src/main/python/tensorframes_snippets/read_image.py:108-167``:
restore a conv-net checkpoint, freeze it, feed a DataFrame of encoded image
bytes through ``tfs.map_rows`` with
``feed_dict={'DecodeJpeg/contents': 'image_data'}``).

The TPU-native shape of the same pipeline:

* the frame holds a **binary column** of encoded bytes;
* a ``host_stage`` decodes bytes -> uint8 pixels on the host (XLA cannot
  host string tensors — the reference documents the same Binary limitation,
  ``datatypes.scala:571-622``);
* the device program (here Inception-v3: activations stored in bf16,
  float32 accumulation and logits) normalises and scores; outputs come
  back as new columns.

Run: ``python examples/score_images.py``  (uses tiny random "images"; swap
``decode`` for a real JPEG decoder and ``inception.init`` for restored
weights in a real deployment).
"""

import numpy as np

import jax.numpy as jnp

import _bootstrap  # noqa: F401  (checkout path shim; examples/ is on sys.path when run directly)

import tensorframes_tpu as tfs
from tensorframes_tpu.models import inception

SIDE = inception.INPUT_SIZE


def decode(cells):
    """Encoded bytes -> [n, SIDE, SIDE, 3] uint8 (stand-in codec)."""
    return np.stack(
        [np.frombuffer(c, np.uint8).reshape(SIDE, SIDE, 3) for c in cells]
    )


def main(n_rows: int = 8) -> None:
    rng = np.random.RandomState(0)
    raw = rng.randint(0, 256, size=(n_rows, SIDE, SIDE, 3), dtype=np.uint8)
    frame = tfs.analyze(
        tfs.TensorFrame.from_arrays(
            {"image_data": [im.tobytes() for im in raw],
             "uri": [f"img_{i}.raw".encode() for i in range(n_rows)]},
            num_blocks=2,
        )
    )

    params = inception.init(0, dtype=jnp.bfloat16)
    program = tfs.Program.wrap(
        inception.scoring_program(params, dtype=jnp.bfloat16),
        fetches=["prediction", "score"],
        feed_dict={"image": "image_data"},
    )

    scored = tfs.map_blocks(
        program, frame, host_stage={"image": decode}
    )
    for row in scored.collect():
        print(
            f"{row['uri'].decode():>10}  class={int(row['prediction']):4d}  "
            f"log_prob={float(row['score']):.3f}"
        )


if __name__ == "__main__":
    main()
