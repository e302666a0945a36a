"""One general generator for every traffic mix: a mix is a data file.

Every number a mix needs is in its file; inputs are drawn from the seed, and
the same seed gives the same inputs. The work of a window does not depend on
the seed: every seed gets the same sizes.

Kinds:
  train_closed   one seeded batch of structures (`nodes`, `batch`), steps
                 back to back, the loss fetched after each
"""
import numpy as np

from .state import seed_words


def rng_for(seed, stream):
    return np.random.default_rng([int(w) for w in seed_words(seed, 4)]
                                 + [stream])


def train_structure(traffic, seed, dim):
    """The training cell's one structure: features [1, n, dim], a centred
    random walk [1, n, 3] and an all-true mask, as
    scripts/_flagship_common.py builds them, from the seed."""
    rng = rng_for(seed, 3)
    n, b = traffic['nodes'], traffic['batch']
    seqs = rng.normal(size=(b, n, dim)).astype(np.float32)
    coords = np.cumsum(rng.normal(size=(b, n, 3)), axis=1)
    coords = (coords - coords.mean(axis=1, keepdims=True)).astype(np.float32)
    return seqs, coords, np.ones((b, n), bool)
