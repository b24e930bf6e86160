"""The traffic generators: the same seed gives the same inputs."""
import jax
import numpy as np

from bench import generate

TRAFFIC = {"corpus_seed": 7, "train_frames": 1003, "heldout_frames": 97,
           "proto_scale": 0.1, "noise": 1.0, "batch": 100}


def corpus(seed):
    return generate.timit_corpus(generate.seed_key(seed), TRAFFIC, 6, 5)


def same(a, b):
    return all(np.array_equal(x, y) for x, y in
               zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


def test_timit_same_seed_same_frames():
    assert same(corpus(3), corpus(3))
    assert not same(corpus(3)["train"], corpus(4)["train"])


def test_seeds_beyond_32_bits():
    lo, hi = corpus(5), corpus(5 + (1 << 32))
    assert not same(lo["train"], hi["train"])
    big = 4_000_000_000_123
    assert same(corpus(big), corpus(big))


def test_heldout_shares_the_training_prototypes():
    traffic = dict(TRAFFIC, proto_scale=2.0, heldout_frames=2000)

    def means(corpus_seed, seed, split):
        c = generate.timit_corpus(generate.seed_key(seed),
                                  dict(traffic, corpus_seed=corpus_seed), 6, 5)
        x, y = np.asarray(c[split]["x"]), np.asarray(c[split]["y"])
        return np.stack([x[y == k].mean(0) for k in range(5)])

    # the prototypes belong to the corpus, not to the seed
    same_corpus = np.abs(means(7, 3, "train") - means(7, 9, "heldout")).max()
    other_corpus = np.abs(means(7, 3, "train") - means(8, 9, "heldout")).max()
    assert same_corpus < 0.5 < other_corpus


def test_timit_rows_of_an_epoch_all_differ():
    c = corpus(3)
    n = TRAFFIC["train_frames"]
    x = np.asarray(c["train"]["x"])
    assert x.shape == (n + 100, 6)
    # the rows after the corpus repeat its first batch
    assert np.array_equal(x[n:], x[:100])
    starts = [int(generate.timit_start(s, 100, n)) for s in range(12)]
    assert starts[:11] == [(100 * s) % n for s in range(11)]
    # ten steps of 100 rows from a corpus of 1003 rows all differ
    rows = np.concatenate([x[s:s + 100] for s in starts[:10]])
    assert len({r.tobytes() for r in rows}) == 1000
    # step 10 wraps round the end: rows 1000-1002, then rows 0-96
    b10 = generate.timit_batch(c["train"], 10, 100, n)
    assert np.array_equal(np.asarray(b10["x"]),
                          np.concatenate([x[1000:1003], x[:97]]))
    b0 = generate.timit_batch(c["train"], 0, 100, n)
    assert same(b0, generate.timit_batch(c["train"], 0, 100, n))
    assert b0["x"].shape == (100, 6)
    big = generate.timit_start(40_000, 100_000, n)
    assert int(big) == (40_000 * 100_000) % n
