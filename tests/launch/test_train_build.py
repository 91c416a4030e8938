"""The trainer's AlexNet build: its stream yields batches already split
for the replicas, the image program writing the split layout itself."""
import jax
import numpy as np

from repro.launch import train
from repro.numerics import get_policy


def _build(*argv):
    ap = train.build_parser()
    args = ap.parse_args(["--arch", "alexnet", "--smoke", *argv])
    return train.build_alexnet(args, ap.error, get_policy(args.numerics))


def test_alexnet_stream_is_split_for_the_replicas():
    """``make_stream(2)`` gives (2, B/2, ...) leaves, images on the device,
    with the numbers of ``make_stream(1)`` batch after batch: the split is
    a view of the host batch made before the crop draws."""
    build = _build("--batch", "4")
    split, flat = build.make_stream(2), build.make_stream(1)
    crop = build.cfg.image_size
    for _ in range(2):
        s, f = next(split), next(flat)
        assert isinstance(s["images"], jax.Array)
        assert s["images"].shape == (2, 2, crop, crop, 3)
        assert f["images"].shape == (1, 4, crop, crop, 3)
        assert s["labels"].shape == (2, 2)
        np.testing.assert_array_equal(np.asarray(s["images"]).reshape(
            f["images"].shape), np.asarray(f["images"]))
        np.testing.assert_array_equal(s["labels"].reshape(1, 4),
                                      f["labels"])
