"""Synthetic token sequences, made on the device from the seed.

Each sequence counts up from a random start in steps of 1 to 3, modulo
the vocabulary, and one token in ten is replaced by a random one.  The
configuration gives ``vocab_size``, ``seq_len``, ``n_train`` and
``n_test``.  A minibatch is ``{"tokens": (..., seq_len) int32}``.
"""
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# the program's FL loop has no token task yet; the tests never run this
# task through the program
PROGRAM_TASK = "make_token_task"


class Split(NamedTuple):
    tokens: np.ndarray     # (N, seq_len) int32


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, n: int, seq_len: int, vocab: int):
    ks, kd, kn, kr = jax.random.split(key, 4)
    start = jax.random.randint(ks, (n, 1), 0, vocab)
    stride = jax.random.randint(kd, (n, 1), 1, 4)
    tok = (start + stride * jnp.arange(seq_len)[None, :]) % vocab
    noise = jax.random.randint(kr, (n, seq_len), 0, vocab)
    return jnp.where(jax.random.uniform(kn, (n, seq_len)) < 0.1, noise,
                     tok).astype(jnp.int32)


def make(key, config: dict) -> tuple[Split, Split]:
    k1, k2 = jax.random.split(key)
    return tuple(Split(np.asarray(_make(k, config[n], config["seq_len"],
                                        config["vocab_size"])))
                 for k, n in ((k1, "n_train"), (k2, "n_test")))


def rows(split: Split) -> dict:
    return {"tokens": split.tokens}


def zero_batch(config: dict, steps: int, batch: int) -> dict:
    return {"tokens": np.zeros((steps, batch, config["seq_len"]), np.int32)}


def bytes_per_sample(config: dict) -> int:
    return 4 * config["seq_len"]
