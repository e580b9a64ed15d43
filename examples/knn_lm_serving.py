"""Retrieval-augmented decoding (kNN-LM) with a per-request Lp metric.

    PYTHONPATH=src python examples/knn_lm_serving.py

1. Draws a token stream from a sparse Markov chain and, for each position,
   a context "hidden state": the embedding of the current token plus
   heavy-tailed (Laplace) noise, as an LM's last layer would carry it.
2. Builds a U-HNSW datastore of (hidden state -> next token) pairs.
3. Scores held-out contexts with a plain unigram LM (a parametric model
   that has not learned the transitions) and with kNN-LM mixing, sweeping
   the retrieval metric p — the knob the paper makes free.

Expected: kNN-LM lowers NLL vs the plain LM, and the best p depends on
the datastore geometry (the paper's motivation for universal-p serving).
One temperature serves every p here, so at p = 0.5, whose distances run
largest, the neighbour weights are too sharp and the mix can lose.
"""

import numpy as np

from repro.retrieval.knn_lm import KnnLM


def main():
    rng = np.random.default_rng(0)
    vocab, d, n_store, n_eval = 64, 32, 5000, 256
    # each token has 3 likely successors: the structure kNN-LM recovers
    succ = rng.integers(0, vocab, size=(vocab, 3))
    embed = rng.standard_normal((vocab, d)).astype(np.float32)

    def stream(n):
        tok = np.empty(n + 1, np.int64)
        tok[0] = rng.integers(vocab)
        for i in range(n):
            tok[i + 1] = succ[tok[i], rng.integers(3)]
        hidden = embed[tok[:-1]] + rng.laplace(
            scale=0.5, size=(n, d)).astype(np.float32)
        return hidden.astype(np.float32), tok[1:]

    print("building the (hidden -> next token) datastore ...")
    hidden, next_tok = stream(n_store)
    knn = KnnLM.build_from_hidden(hidden, next_tok, vocab, m=8, k=8,
                                  lam=0.3, temperature=1.0)

    print("evaluating held-out contexts: plain LM vs kNN-LM across p ...")
    q, gold = stream(n_eval)
    unigram = np.bincount(next_tok, minlength=vocab) + 1.0
    lm_lp = np.log(np.broadcast_to(unigram / unigram.sum(), (n_eval, vocab)))
    nll_lm = -lm_lp[np.arange(n_eval), gold].mean()
    print(f"  plain LM NLL: {nll_lm:.3f}")
    for p in [0.5, 0.8, 1.0, 1.4, 2.0]:
        mixed = knn.mix(lm_lp, q, p)
        nll = -mixed[np.arange(n_eval), gold].mean()
        print(f"  kNN-LM (p={p}): NLL {nll:.3f} "
              f"({'better' if nll < nll_lm else 'worse'})")


if __name__ == "__main__":
    main()
