"""Token batches for the decoder's training cells, from a traffic file and
the seed: ids by a Zipf law over the vocabulary rows held, in documents whose
lengths are log-normal, packed back to back into sequences (the last one cut
at the sequence's end). Id 0 opens every document; attention runs across the
whole sequence.

Kind:
  lm_train_closed   `n_batches` batches of `batch` sequences of `seq` tokens,
                    placed on the device in set-up and taken in turn, steps
                    back to back
"""
import numpy as np

from .traffic import rng_for


def token_batches(traffic, seed, vocab_rows):
    """int32 [n_batches, batch, seq]."""
    assert traffic['kind'] == 'lm_train_closed', traffic['kind']
    rng = rng_for(seed, 5)
    shape = (traffic['n_batches'], traffic['batch'], traffic['seq'])
    total = int(np.prod(shape))
    # rank r = 1 .. vocab_rows - 1 with p ~ r^-exponent; which id has which
    # rank is the seed's
    p = np.arange(1, vocab_rows, dtype=np.float64) ** -traffic['zipf_exponent']
    ids = rng.permutation(np.arange(1, vocab_rows))
    tokens = ids[rng.choice(vocab_rows - 1, size=total, p=p / p.sum())]
    doc = traffic['document_tokens']
    starts, at = [], 0
    while at < total:
        starts.append(at)
        at += max(2, int(round(rng.lognormal(np.log(doc['median']),
                                             doc['sigma']))))
    tokens[np.asarray(starts)] = 0
    tokens = tokens.reshape(shape)
    tokens[..., 0] = 0          # a sequence opens a document too
    return tokens.astype(np.int32)
