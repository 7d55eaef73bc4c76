"""Seeded synthetic imagery as a traceable ``(sat, idx) -> batch``.

A copy of the program's ``DeviceImageryShards`` generator: per-satellite
non-IID class priors (a Dirichlet tilt) and Gaussian-blob images, every
batch a pure function of ``fold_in(seed, sat, idx)``.  The benchmark
feeds it to the fleet as its ``batch_fn`` and the plain reference reads
the same batches, so both train on identical samples.

The generator's own seed is a constant of every program that traces it,
so a run's ``--seed`` does not go there (each seed would compile its own
programs): every run reads the same stream from batch index 0, and its
``--seed`` makes the weights that train on it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp


STREAM_SEED = 0


@dataclasses.dataclass(frozen=True)
class Imagery:
    img: int
    n_classes: int
    batch: int
    seed: int = STREAM_SEED
    channels: int = 3

    def __call__(self, sat, idx) -> Dict[str, jnp.ndarray]:
        sat = jnp.asarray(sat, jnp.uint32)
        idx = jnp.asarray(idx, jnp.uint32)
        kshard = jax.random.fold_in(jax.random.key(self.seed), sat)
        prior = jax.random.dirichlet(kshard,
                                     jnp.full((self.n_classes,), 0.5))
        klab, kimg = jax.random.split(jax.random.fold_in(kshard, idx))
        labels = jax.random.categorical(
            klab, jnp.log(prior + 1e-9), shape=(self.batch,)
        ).astype(jnp.int32)

        xs = jnp.linspace(-1.0, 1.0, self.img, dtype=jnp.float32)
        xx, yy = jnp.meshgrid(xs, xs)

        def one(key, lab):
            kc, kn = jax.random.split(key)
            cxy = jax.random.uniform(kc, (2,), minval=-0.5, maxval=0.5)
            sx = 0.15 + 0.04 * (lab % 5).astype(jnp.float32)
            blob = jnp.exp(-(((xx - cxy[0]) ** 2 + (yy - cxy[1]) ** 2)
                             / (2.0 * sx * sx)))
            phase = 2.0 * jnp.pi * lab.astype(jnp.float32) / self.n_classes
            chans = jnp.stack(
                [blob * jnp.cos(phase + c) for c in range(self.channels)],
                axis=-1)
            noise = jax.random.normal(
                kn, (self.img, self.img, self.channels))
            return (chans + 0.05 * noise).astype(jnp.float32)

        imgs = jax.vmap(one)(jax.random.split(kimg, self.batch), labels)
        return {"images": imgs, "labels": labels}
