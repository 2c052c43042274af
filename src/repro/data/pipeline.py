"""Deterministic synthetic data pipeline.

Token streams are generated from a counter-based hash (splittable, seekable:
batch i is reproducible without generating batches 0..i-1), sharded by
data-parallel rank, with host-side prefetch. Stands in for a tokenized
corpus reader; the interface (``__iter__`` of global batches + ``state()``
for checkpoint resume) is what the trainer depends on.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import zlib
from typing import Iterator, Optional

import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.registry import train_batch_shapes


_MASK64 = (1 << 64) - 1


def _hash_tokens(seed: int, stream: int, offset: int, n: int,
                 vocab: int) -> np.ndarray:
    """SplitMix64-style counter hash -> tokens in [0, vocab)."""
    # scalar mixing constants are combined in Python-int space masked to 64
    # bits: np.uint64 scalar products raise RuntimeWarning on wraparound
    # (array ops wrap silently), and the wrapped value is exactly what
    # SplitMix64 wants
    stream_mix = np.uint64((int(stream) * 0x9E3779B97F4A7C15) & _MASK64)
    seed_mix = np.uint64((int(seed) * 0xBF58476D1CE4E5B9) & _MASK64)
    idx = np.arange(offset, offset + n, dtype=np.uint64) + stream_mix
    z = idx + seed_mix
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    z = z ^ (z >> np.uint64(31))
    return (z % np.uint64(vocab)).astype(np.int32)


@dataclasses.dataclass
class PipelineState:
    step: int = 0


class SyntheticPipeline:
    """Yields global batches (dict of numpy arrays) for any architecture."""

    def __init__(self, cfg: ModelConfig, shape: ShapeConfig, *,
                 seed: int = 0, start_step: int = 0, prefetch: int = 2):
        self.cfg = cfg
        self.shape = shape
        self.seed = seed
        self.state = PipelineState(step=start_step)
        self.prefetch = prefetch
        self._shapes = train_batch_shapes(cfg, shape)

    # ------------------------------------------------------------------
    def batch_at(self, step: int) -> dict:
        cfg, shape = self.cfg, self.shape
        out = {}
        for name, (shp, dt) in self._shapes.items():
            n = int(np.prod(shp))
            # crc32, not hash(): str hashes change from process to process
            stream = zlib.crc32(name.encode()) & 0x7FFFFFFF
            if str(dt) in ("int32",) or "int" in str(dt):
                arr = _hash_tokens(self.seed, stream, step * n, n,
                                   cfg.vocab_size).reshape(shp)
                if name == "labels":
                    # next-token labels = tokens shifted (approximated by an
                    # independent stream for synthetic data) with VLM image
                    # positions masked
                    if cfg.family == "vlm":
                        arr = arr.copy()
                        arr[:, :cfg.num_patches] = -1
            else:
                bits = _hash_tokens(self.seed, stream ^ 0x5555, step * n, n,
                                    1 << 16).astype(np.float32)
                arr = ((bits / (1 << 15)) - 1.0).reshape(shp)
            out[name] = arr
        return out

    def __iter__(self) -> Iterator[dict]:
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()

        def worker():
            s = self.state.step
            while not stop.is_set():
                q.put(self.batch_at(s))
                s += 1

        th = threading.Thread(target=worker, daemon=True)
        th.start()
        try:
            while True:
                item = q.get()
                self.state.step += 1
                yield item
        finally:
            stop.set()
