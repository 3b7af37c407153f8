"""Training batches from a file of parameters: ``rows`` x ``seq`` token ids
a step, uniform over the vocabulary, every row and every step different,
labels the next token.  ``batch(i)`` is a pure function of the seed and
``i``, so the reference can ask for the same steps again."""
import numpy as np


class Batches:
    def __init__(self, traffic, seed, vocab_size):
        self.rows, self.seq = int(traffic["rows"]), int(traffic["seq"])
        self.vocab, self.seed = int(vocab_size), int(seed)

    def batch(self, i):
        """(data, label) int32 arrays of shape (rows, seq) for step ``i``
        (0-based)."""
        rng = np.random.default_rng([self.seed, int(i)])
        ids = rng.integers(0, self.vocab, (self.rows, self.seq + 1),
                           dtype=np.int32)
        return ids[:, :-1], ids[:, 1:]


def make(traffic, seed, vocab_size):
    return Batches(traffic, seed, vocab_size)
