"""Closed backlog: a queue of work that is all there at the start.

The whole backlog is submitted before the engine runs, so every slot
that frees takes the next request at once, like a batch job. The
measured window opens when every slot is busy. The backlog is sized to
outlast the window. Its sizes are fixed by the mix's ``shape_seed``;
``--seed`` draws the token ids and permutes the order within each block
of ``order_block`` consecutive prompts or documents (``lengths.py``).

Two shapes of work (the mix file holds one of them):

  requests   n_requests unshared prompts: prompt_len, output_len
  sessions   n_sessions documents, each asked several questions that
             are all queued together: doc_len, questions (how many per
             document), question_len, answer_len. Documents are unique
             to their session; the questions of one session share it
             as a prefix.
"""
from __future__ import annotations

import numpy as np

import lengths as L


def _requests(params, shape, rng, vocab):
    n = params["n_requests"]
    plens = L.lognormal(shape, params["prompt_len"], n)
    outs = L.lognormal(shape, params["output_len"], n)
    out = []
    for i in L.block_order(rng, n, params["order_block"]):
        out.append({"due": 0.0, "prompt": L.tokens(rng, vocab, plens[i]),
                    "max_new": int(outs[i])})
    return out, L.length_range(params["prompt_len"])


def _sessions(params, shape, rng, vocab):
    n = params["n_sessions"]
    docs = L.lognormal(shape, params["doc_len"], n)
    n_q = L.uniform(shape, params["questions"], n)
    q_lens = [L.uniform(shape, params["question_len"], k) for k in n_q]
    a_lens = [L.uniform(shape, params["answer_len"], k) for k in n_q]
    out = []
    for i in L.block_order(rng, n, params["order_block"]):
        doc = L.tokens(rng, vocab, docs[i])
        for q, a in zip(q_lens[i], a_lens[i]):
            out.append({"due": 0.0,
                        "prompt": np.concatenate(
                            [doc, L.tokens(rng, vocab, q)]),
                        "max_new": int(a)})
    lo = L.length_range(params["doc_len"])[0] \
        + L.length_range(params["question_len"])[0]
    hi = L.length_range(params["doc_len"])[1] \
        + L.length_range(params["question_len"])[1]
    return out, (lo, hi)


def generate(params: dict, seed: int, vocab: int, seconds: float) -> dict:
    shape = np.random.default_rng(params["shape_seed"])
    rng = np.random.default_rng([seed, 2])
    make = _sessions if "n_sessions" in params else _requests
    requests, prange = make(params, shape, rng, vocab)
    return {"requests": requests, "prompt_range": prange}
