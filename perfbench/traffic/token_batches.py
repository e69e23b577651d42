"""The one generator of training traffic: batches of token ids drawn from a
Zipf law over the vocabulary, as word frequencies are, from the seed.

The parameters are a cell's `traffic` group in workloads/<cell>.json:
`batch`, `seq_len`, `zipf_a`, and `objective`:
  "mlm"        — `mask_rate` of the positions are replaced by `mask_id`; the
                 labels are the original ids at every position;
  "next_token" — the labels are the ids shifted by one position.

Every generator in this directory has the same two entry points:
`generate(traffic, seed, config)`, an endless iterator of items that is a
function of the seed alone, and `self_check(traffic, config, items)`,
which raises AssertionError when items are not what the parameters say.
"""
import numpy as np


def generate(traffic, seed, config):
    """Endless iterator of (tokens, labels), int32 arrays (batch, seq_len).
    The same seed gives the same sequence of batches."""
    rng = np.random.Generator(np.random.PCG64(seed))
    b, s, vocab_size = traffic["batch"], traffic["seq_len"], \
        config["vocab_size"]
    weights = 1.0 / np.arange(1, vocab_size + 1) ** traffic["zipf_a"]
    cdf = np.cumsum(weights / weights.sum())
    objective = traffic["objective"]
    if objective not in ("mlm", "next_token"):
        raise ValueError("unknown objective %r" % (objective,))
    n = s + 1 if objective == "next_token" else s
    while True:
        ids = np.minimum(np.searchsorted(cdf, rng.random((b, n))),
                         vocab_size - 1).astype(np.int32)
        if objective == "next_token":
            yield ids[:, :-1].copy(), ids[:, 1:].copy()
        else:
            masked = rng.random((b, s)) < traffic["mask_rate"]
            yield np.where(masked, np.int32(traffic["mask_id"]), ids), ids


def self_check(traffic, config, items):
    vocab_size = config["vocab_size"]
    for tokens, labels in items:
        assert tokens.shape == labels.shape == (traffic["batch"],
                                                traffic["seq_len"])
        assert tokens.dtype == labels.dtype == np.int32
        assert 0 <= tokens.min() and tokens.max() < vocab_size
        assert 0 <= labels.min() and labels.max() < vocab_size
        if traffic["objective"] == "next_token":
            assert np.array_equal(tokens[:, 1:], labels[:, :-1])
        else:
            changed = tokens != labels
            assert (tokens[changed] == traffic["mask_id"]).all()
            assert 0.5 * traffic["mask_rate"] < changed.mean() \
                < 1.5 * traffic["mask_rate"]
        # Zipf: the most frequent id is drawn far more often than a uniform
        # draw over the vocabulary would give
        assert (labels == 0).mean() > 5.0 / vocab_size
