"""Deterministic synthetic datasets + a sharded batcher.

The paper trains on fashion-mnist / cifar-10 (Table 6).  Offline, we
generate class-conditional Gaussian-mixture images with the same tensor
shapes (784- or 1024-dim inputs, 10 classes) — learnable structure so the
end-to-end examples show loss decreasing, deterministic so tests are
stable.  LM token streams are Zipf-distributed with injected bigram
structure for the same reason.

The Batcher shards each host batch over the mesh's data axes via
jax.device_put with a NamedSharding (the production input path: per-host
feed then device layout).
"""

from __future__ import annotations

import dataclasses
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs


def fcnn_classification_dataset(
    n_samples: int, input_dim: int = 784, n_classes: int = 10, seed: int = 0,
    class_sep: float = 2.0,
) -> tuple[np.ndarray, np.ndarray]:
    """Gaussian-mixture stand-in for fashion-mnist/cifar (shapes match)."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_classes, input_dim)).astype(np.float32)
    centers *= class_sep / np.linalg.norm(centers, axis=1, keepdims=True)
    y = rng.integers(0, n_classes, size=n_samples)
    x = centers[y] + rng.normal(size=(n_samples, input_dim)).astype(np.float32)
    return x.astype(np.float32), y.astype(np.int32)


def token_stream(
    n_tokens: int, vocab: int, seed: int = 0, zipf_a: float = 1.2,
) -> np.ndarray:
    """Zipf unigrams + deterministic bigram structure (v -> (v*7+3) % vocab
    with prob .5) so an LM can reduce loss."""
    rng = np.random.default_rng(seed)
    base = rng.zipf(zipf_a, size=n_tokens).astype(np.int64) % vocab
    out = base.copy()
    follow = rng.random(n_tokens) < 0.5
    out[1:][follow[1:]] = (out[:-1][follow[1:]] * 7 + 3) % vocab
    return out.astype(np.int32)


@dataclasses.dataclass
class Batcher:
    """Iterates device-laid-out batches; resumable via ``state`` (step)."""

    data: dict[str, np.ndarray]
    batch_size: int
    mesh: Mesh | None = None
    batch_axes: tuple[str, ...] | None = ("data",)
    step: int = 0

    def __iter__(self) -> Iterator[dict[str, jax.Array]]:
        return self

    def _spec(self, arr: np.ndarray) -> P:
        axes = tuple(a for a in (self.batch_axes or ())
                     if self.mesh and a in self.mesh.axis_names)
        return P(axes if axes else None,
                 *([None] * (arr.ndim - 1)))

    def __next__(self) -> dict[str, jax.Array]:
        n = len(next(iter(self.data.values())))
        start = (self.step * self.batch_size) % n
        idx = (np.arange(self.batch_size) + start) % n
        self.step += 1
        with obs.span(obs.BATCH_INDEX):
            rows = {k: v[idx] for k, v in self.data.items()}
        with obs.span(obs.BATCH_PUT):
            if self.mesh is None:
                return {k: jnp.asarray(b) for k, b in rows.items()}
            return {k: jax.device_put(
                        b, NamedSharding(self.mesh, self._spec(b)))
                    for k, b in rows.items()}

    # --- checkpointable state ---
    def state(self) -> dict:
        return {"step": self.step}

    def restore(self, state: dict) -> None:
        self.step = int(state["step"])
