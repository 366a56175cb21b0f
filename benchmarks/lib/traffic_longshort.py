"""The generator of the ``longshort`` mixes: long document sessions and short
chat turns in one closed-loop queue. As ``lib/traffic.py`` does, the mix file
fixes every size, who sends which and in what order (quantile grids paired by
a shuffle the file's ``pairing_seed`` fixes); the seed draws the token ids
only, so every run replays one schedule.

A *long* client owns one document (committed to the prefix cache in set-up)
and asks ``turns`` fresh questions about it: every turn is the document + a
question + the new tokens. A *short* client sends ``turns`` fresh prompts,
nothing shared."""

import random

from . import traffic as T


def _sizes(group: dict, first: str, pairing_seed: int):
    """``clients * turns`` (first, max_new_tokens) pairs, a list per client."""
    n = group["clients"] * group["turns"]
    pool = T.size_pool({"pool": n, "prompt_len": group[first], "max_new_tokens": group["max_new_tokens"],
                        "pairing_seed": pairing_seed})
    random.Random(pairing_seed + 1).shuffle(pool)
    return [pool[i::group["clients"]] for i in range(group["clients"])]


def serve_requests(mix: dict, seed: int):
    """(documents, clients): the long clients' documents (token tuples, in
    whole pages) and, per client, the list of (prompt token ids,
    max_new_tokens) it cycles through; long clients first."""
    rng = random.Random(seed)
    lo, hi = mix["token_id_range"]
    # tuples, not lists: Python's collector stops tracking a tuple of ints at its first pass, where 400 lists of
    # 20k ids are walked again by every full collection; the generator shares the server's process, and its heap is
    # not the server's to pay for
    draw = lambda n: tuple(rng.randrange(lo, hi) for _ in range(n))
    long, short, page = mix["long"], mix["short"], mix["page_size"]
    grid = [(i + 0.5) / long["clients"] for i in range(long["clients"])]
    doc_lens = [T._quantile(long["document_len"], u) // page * page for u in grid]
    random.Random(mix["pairing_seed"] + 2).shuffle(doc_lens)
    documents = [draw(n) for n in doc_lens]
    clients = [[(doc + draw(q), o) for q, o in turns]
               for doc, turns in zip(documents, _sizes(long, "question_len", mix["pairing_seed"]))]
    clients += [[(draw(p), o) for p, o in turns] for turns in _sizes(short, "prompt_len", mix["pairing_seed"] + 3)]
    return documents, clients
