"""Traffic generators: every input a cell feeds the program, made on the
device from a seed.

One general generator per kind of data, driven by the parameters of a
traffic file (``bench/traffic/<name>.json``, key ``generator``). They are
copies, not imports, of the program's own seeded generators
(``repro.data.synthetic.classification_dataset``), so that a change to
``repro.data`` cannot move the yardstick:

* ``timit_frames`` — TIMIT-shaped classification frames: Gaussian class
  prototypes plus isotropic noise, with a held-out split drawn around the
  same prototypes. The prototypes come from the traffic file's
  ``corpus_seed`` (a corpus is fixed; users train on the same one), the
  frames and their labels from ``--seed``.

Batches are drawn on the device inside one jitted call per step. The
frames are independent draws, so consecutive rows are already in a random
order: step ``t`` takes the ``batch`` rows from ``t * batch`` on, modulo
the corpus, as one contiguous slice. (A gather by row index made the chip
copy the whole corpus into another layout at every step.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63: both 32-bit halves
    count, so seeds that differ only above bit 31 give different keys."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


# ------------------------------------------------------------ TIMIT frames --
def timit_corpus(key, traffic, d: int, n_classes: int):
    """{"train": {"x","y"}, "heldout": {"x","y"}}, on the device. ``key`` is
    the seed's key. The training split holds ``train_frames`` rows and, after
    them, its first ``batch`` rows once more, so that a step's rows are one
    slice also where they wrap round the end of the corpus."""
    n_train, n_held = traffic["train_frames"], traffic["heldout_frames"]
    batch = traffic["batch"]
    if batch > n_train:
        raise ValueError(f"batch {batch} exceeds the corpus's {n_train} rows")
    kp = jax.random.PRNGKey(traffic["corpus_seed"])
    protos = jax.random.normal(kp, (n_classes, d)) * traffic["proto_scale"]
    ktr, khe = jax.random.split(key)

    def frames(k, n):
        ky, kx = jax.random.split(k)
        y = jax.random.randint(ky, (n,), 0, n_classes)
        x = protos[y] + jax.random.normal(kx, (n, d)) * traffic["noise"]
        return {"x": x.astype(jnp.float32), "y": y.astype(jnp.int32)}

    train = jax.tree_util.tree_map(
        lambda a: jnp.concatenate([a, a[:batch]]), frames(ktr, n_train))
    return {"train": train, "heldout": frames(khe, n_held)}


def timit_start(step, batch: int, n_rows: int):
    """The first row of outer step ``step``: ``step * batch`` modulo the
    corpus's ``n_rows`` (unsigned, exact while ``step * batch < 2**32``)."""
    s = jnp.asarray(step).astype(jnp.uint32)
    return ((s * jnp.uint32(batch)) % jnp.uint32(n_rows)).astype(jnp.int32)


def timit_batch(train, step, batch: int, n_rows: int):
    """The step's batch: ``batch`` consecutive rows of the on-device
    training split (``timit_corpus``) from ``timit_start`` on."""
    start = timit_start(step, batch, n_rows)
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, start, batch, 0), train)
