"""Seeded synthetic token streams: the shared corpus and request prompts.

``zipf_segments`` is a copy of ``synthesize_corpus`` in
``src/repro/data/pipeline.py``: Zipfian tokens with a per-1K-segment
additive offset, so that segments (and hence corpus chunks) differ in their
key statistics and routing is not degenerate. It is kept here so that a
change to the program cannot change the benchmark's inputs.
"""
from __future__ import annotations

import numpy as np


def zipf_segments(num_tokens: int, vocab_size: int, rng: np.random.Generator,
                  zipf_a: float = 1.2, segment: int = 1024) -> np.ndarray:
    """Zipfian tokens with drifting local flavour per ``segment`` tokens."""
    n = num_tokens
    base = rng.zipf(zipf_a, size=n).astype(np.int64) % vocab_size
    offs = rng.integers(0, vocab_size, size=(n + segment - 1) // segment)
    idx = np.arange(n) // segment
    return ((base + offs[idx]) % vocab_size).astype(np.int32)


def corpus_tokens(num_tokens: int, vocab_size: int, seed: int) -> np.ndarray:
    """The shared corpus of one run: the same seed gives the same tokens."""
    rng = np.random.default_rng([seed, 0xC0])
    return zipf_segments(num_tokens, vocab_size, rng)
