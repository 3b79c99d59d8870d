"""Synthetic class-conditional images, made on the device from the seed.

Each class is a smoothed random template; a sample is its class template
shifted by up to 2 pixels each way, plus Gaussian noise, clipped to
[0, 1].  Train and test share the templates.  One jitted call makes both
splits; the program gets them as host arrays, as it would load a dataset.
The configuration gives ``image_shape`` (H, W, C), ``n_classes``,
``n_train`` and ``n_test``.  A minibatch is ``{"images": (..., H, W, C)
float32, "labels": (...) int32}``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

PROGRAM_TASK = "make_image_task"


class Split(NamedTuple):
    x: np.ndarray      # (N, H, W, C) float32 in [0, 1]
    y: np.ndarray      # (N,) int32


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _make(key, n_train: int, n_test: int, shape: tuple, n_classes: int):
    """Both splits, each image flat in (C, H, W) order: an array whose last
    axis is a few channels would be padded many times over on a TPU."""
    h, w, c = shape
    kt, kd = jax.random.split(key)
    t = 0.5 + 0.5 * jax.random.normal(kt, (n_classes, c, h, w))
    for _ in range(2):
        t = (t + jnp.roll(t, 1, 2) + jnp.roll(t, -1, 2)
             + jnp.roll(t, 1, 3) + jnp.roll(t, -1, 3)) / 5.0

    def split(k, n):
        ky, ks, kn = jax.random.split(k, 3)
        y = jax.random.randint(ky, (n,), 0, n_classes, jnp.int32)
        sh = jax.random.randint(ks, (2, n), -2, 3)
        rows = (jnp.arange(h)[None, :] - sh[0][:, None]) % h
        cols = (jnp.arange(w)[None, :] - sh[1][:, None]) % w
        x = t[y[:, None, None, None], jnp.arange(c)[None, :, None, None],
              rows[:, None, :, None], cols[:, None, None, :]]
        x = x + 0.25 * jax.random.normal(kn, x.shape)
        return jnp.clip(x, 0.0, 1.0).astype(jnp.float32).reshape(n, -1), y

    k1, k2 = jax.random.split(kd)
    return split(k1, n_train), split(k2, n_test)


def _nhwc(x, shape) -> np.ndarray:
    h, w, c = shape
    x = np.asarray(x).reshape(-1, c, h, w)
    return np.ascontiguousarray(x.transpose(0, 2, 3, 1))


def make(key, config: dict) -> tuple[Split, Split]:
    shape = tuple(config["image_shape"])
    (xt, yt), (xe, ye) = _make(key, config["n_train"], config["n_test"],
                               shape, config["n_classes"])
    return (Split(_nhwc(xt, shape), np.asarray(yt)),
            Split(_nhwc(xe, shape), np.asarray(ye)))


def rows(split: Split) -> dict:
    return {"images": split.x, "labels": split.y}


def zero_batch(config: dict, steps: int, batch: int) -> dict:
    return {"images": np.zeros((steps, batch) + tuple(config["image_shape"]),
                               np.float32),
            "labels": np.zeros((steps, batch), np.int32)}


def bytes_per_sample(config: dict) -> int:
    """A float32 image and an int32 label."""
    return 4 * int(np.prod(config["image_shape"])) + 4
