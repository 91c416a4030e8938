"""Weights and device-made data from ``--seed``, owned by the benchmark.

A seed may exceed 32 bits; ``jax.random.PRNGKey`` keeps only the low 32
bits of a Python int when 64-bit mode is off, so the seed enters every
program as two uint32 words, folded into the key on the device.  The
words are arguments, not constants, so one compiled program serves every
seed.  The plain reference draws its weights and batches through these
same functions, which belong to the benchmark and not to the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# pixel band of the benchmark's images: 4 uint8 levels, so that the
# mean-subtracted inputs have unit scale (see the configs' "assumed")
PIXEL_LO, PIXEL_LEVELS = 126, 4


def seed_words(seed: int) -> np.ndarray:
    """Any integer seed -> two uint32 words (high, low)."""
    s = int(seed) % (1 << 64)
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


def key_of(words, stream: int):
    """PRNG key for one named stream of a seed (traceable)."""
    key = jax.random.fold_in(jax.random.PRNGKey(stream), words[0])
    return jax.random.fold_in(key, words[1])


def param_shapes(cfg: dict):
    """[(name, shape, fan_in, kind)] in the program's parameter order."""
    out, c_in, hw = [], cfg["in_channels"], cfg["image_size"]
    for i, cs in enumerate(cfg["convs"]):
        cig = c_in // cs["groups"]
        out.append((f"conv{i + 1}",
                    (cs["kernel"], cs["kernel"], cig, cs["out_channels"]),
                    cs["kernel"] ** 2 * cig, "conv"))
        hw = (hw + 2 * cs["padding"] - cs["kernel"]) // cs["stride"] + 1
        if cs["pool"]:
            hw = (hw - 3) // 2 + 1
        c_in = cs["out_channels"]
    dims = [(hw * hw * c_in, cfg["fc_dim"]), (cfg["fc_dim"], cfg["fc_dim"]),
            (cfg["fc_dim"], cfg["n_classes"])]
    for i, (di, do) in enumerate(dims):
        out.append((f"fc{i + 1}", (di, do), di, "fc"))
    return out


@functools.partial(jax.jit, static_argnames=("shapes",))
def _init(words, shapes):
    key = key_of(words, 0)
    params = {"convs": [], "fcs": []}
    for i, (_, shape, fan_in, kind) in enumerate(shapes):
        k = jax.random.fold_in(key, i)
        scale = (2.0 / fan_in) ** 0.5 if kind == "conv" else fan_in ** -0.5
        leaf = {"w": jax.random.normal(k, shape, jnp.float32) * scale,
                "b": jnp.zeros((shape[-1],), jnp.float32)}
        params["convs" if kind == "conv" else "fcs"].append(leaf)
    return params


def init_params(cfg: dict, seed: int):
    """The weights of ``seed``: one jitted call on the default device,
    float32, in the program's tree layout."""
    return _init(jnp.asarray(seed_words(seed)), tuple(param_shapes(cfg)))


@functools.partial(jax.jit, static_argnames=("n", "n_labels", "lead",
                                             "size", "channels",
                                             "n_classes"))
def _images(words, n, n_labels, lead, size, channels, n_classes):
    kx, ky = jax.random.split(key_of(words, 1))
    lv = jax.random.randint(kx, (n,) + lead + (size, size, channels), 0,
                            PIXEL_LEVELS)
    images = lv.astype(jnp.float32) - (PIXEL_LEVELS - 1) / 2
    labels = jax.random.randint(ky, (n_labels,) + lead, 0, n_classes,
                                jnp.int32)
    return (tuple(images[i] for i in range(n)),
            tuple(labels[i] for i in range(n_labels)))


def resident_batches(cfg: dict, seed: int, n: int, n_labels: int,
                     batch: int, n_rep: int = None):
    """``n`` distinct preprocessed image batches (already mean-subtracted
    and cropped, float32) and ``n_labels`` label vectors (int32), made on
    the default device in one call: global ``(B, ...)`` arrays, or
    ``(n_rep, B/n_rep, ...)`` ones in the replica layout — the same
    values either way.  Step i pairs images ``i % n`` with labels
    ``i % n_labels``."""
    lead = (batch,) if n_rep is None else (n_rep, batch // n_rep)
    return _images(jnp.asarray(seed_words(seed)), n, n_labels, lead,
                   cfg["crop"], cfg["in_channels"], cfg["n_classes"])


def image_pool(cfg: dict, seed: int, n: int, batch: int, size: int):
    """Host pool of ``n`` uint8 batches of ``size``² images, their int32
    labels, and the pool's float32 mean image, drawn on the host in bulk
    from the seed: raw 64-bit generator output, read as bytes and masked
    into the pixel band."""
    rng = np.random.default_rng([2, int(seed) % (1 << 64)])
    shape = (n, batch, size, size, cfg["in_channels"])
    count = int(np.prod(shape))
    raw = rng.bit_generator.random_raw(-(-count // 8)).view(np.uint8)
    pix = (raw[:count] & (PIXEL_LEVELS - 1)).reshape(shape)
    pix += PIXEL_LO
    lab = rng.integers(0, cfg["n_classes"], size=(n, batch), dtype=np.int32)
    total = pix.reshape(n * batch, -1).sum(axis=0, dtype=np.uint32)
    mean = (total / np.float32(n * batch)).astype(np.float32)
    return pix, lab, mean.reshape(shape[2:])
