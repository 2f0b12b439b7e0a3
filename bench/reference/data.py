"""Plain reference of the training corpus and of one training row.

The corpus is the one ``launch/train.py`` builds from its seed: document
``i`` has a length drawn geometric (mean ``mean_len``) plus ``min_len``,
capped at ``4 * mean_len``, and tokens from an order-1 Markov chain over
16 buckets of the vocabulary, each document drawn from its own generator
``(seed, 13, i)``. A training row of sequence length ``S`` holds a
document's first ``S`` tokens as inputs, the next-token targets, and a
loss mask over the targets inside the document; the rest is padding.
Nothing here imports the program under test.
"""

from __future__ import annotations

import numpy as np

BUCKETS = 16
MIN_LEN = 32


class Corpus:
    def __init__(self, num_docs: int, vocab: int, mean_len: int, seed: int):
        self.vocab, self.seed = int(vocab), int(seed)
        rng = np.random.default_rng((self.seed, 11))
        lens = rng.geometric(1.0 / mean_len, size=num_docs) + MIN_LEN
        self.lengths = np.minimum(lens, 4 * mean_len).astype(np.int64)

    def tokens(self, doc: int) -> np.ndarray:
        n = int(self.lengths[doc])
        rng = np.random.default_rng((self.seed, 13, int(doc)))
        first = int(rng.integers(self.vocab))
        width = max(self.vocab // BUCKETS, 1)
        offsets = rng.integers(width, size=n - 1).tolist()
        out = [first]
        prev = first
        for off in offsets:
            center = ((prev // width) % BUCKETS * 37 + 11) % self.vocab
            prev = (center + off) % self.vocab
            out.append(prev)
        return np.asarray(out, dtype=np.int32)


def expected_row(doc_tokens: np.ndarray, seq_len: int, pad_id: int = 0):
    """(tokens, targets, loss_mask) of one training row of length seq_len."""
    n = min(len(doc_tokens), seq_len + 1)
    full = np.full(seq_len + 1, pad_id, dtype=np.int32)
    full[:n] = doc_tokens[:n]
    mask = np.zeros(seq_len, dtype=np.float32)
    mask[: n - 1] = 1.0
    return full[:seq_len], full[1:], mask
