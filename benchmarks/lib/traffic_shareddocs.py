"""The generator of the ``shareddocs`` mixes: a few long documents, each asked
again and again by SEVERAL closed-loop clients at once. As ``lib/traffic.py``
and ``lib/traffic_longshort.py`` do, the mix file fixes every size, who sends
which and in what order; the seed draws the token ids only, so every run
replays one schedule.

Client ``i`` asks document ``i % documents``: with 16 documents and 32 clients,
clients ``i`` and ``i + 16`` hold one document's pages at once. Every turn is
the document (committed to the prefix cache in set-up: a prefix hit) + a fresh
question + the new tokens."""

import random

from . import traffic as T
from .traffic_longshort import _sizes


class Turns:
    """A client's turns as a sequence of (prompt token ids, max_new_tokens), the prompt joined when it is asked
    for: 1,024 prompts of 25k ids would be a fifth of a gigabyte of tuples on the server's heap."""

    def __init__(self, document, turns):
        self.document, self.turns = document, turns

    def __len__(self):
        return len(self.turns)

    def __getitem__(self, j):
        question, new = self.turns[j]
        return self.document + question, new


def serve_requests(mix: dict, seed: int):
    """(documents, clients): the documents (token tuples, in whole pages) and, per client, the sequence of
    (prompt token ids, max_new_tokens) it cycles through."""
    rng = random.Random(seed)
    lo, hi = mix["token_id_range"]
    draw = lambda n: tuple(rng.randrange(lo, hi) for _ in range(n))
    page, n_docs = mix["page_size"], mix["documents"]
    grid = [(i + 0.5) / n_docs for i in range(n_docs)]
    doc_lens = [T._quantile(mix["document_len"], u) // page * page for u in grid]
    random.Random(mix["pairing_seed"] + 2).shuffle(doc_lens)
    documents = [draw(n) for n in doc_lens]
    sizes = _sizes({"clients": mix["clients"], "turns": mix["turns"], "question_len": mix["question_len"],
                    "max_new_tokens": mix["max_new_tokens"]}, "question_len", mix["pairing_seed"])
    clients = [Turns(documents[i % n_docs], [(draw(q), o) for q, o in turns]) for i, turns in enumerate(sizes)]
    return documents, clients
