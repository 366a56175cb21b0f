"""One general generator for every traffic mix. A mix is a data file under
``benchmarks/traffic``. The file fixes the sizes, who sends which and in
what order; the seed draws the token ids (and, with them, nothing the
schedule depends on). A closed loop on an engine that moves in whole steps
is scheduled by the sizes alone: with the order drawn from the seed, runs
read tokens/s 5% and the TTFT tail 10% apart from seed to seed on the chip
(PERF.md, PR 24), which is the seed changing the work."""

import math
import random
from statistics import NormalDist


def _quantile(dist: dict, u: float) -> int:
    if dist["dist"] == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    elif dist["dist"] == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif dist["dist"] == "fixed":
        x = dist["value"]
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return int(min(max(round(x), dist.get("min", 1)), dist.get("max", 10**9)))


def size_pool(mix: dict):
    """The mix's fixed multiset of (prompt_len, max_new_tokens): a quantile
    grid of both distributions, paired by a shuffle that the file fixes."""
    n = mix["pool"]
    grid = [(i + 0.5) / n for i in range(n)]
    prompts = [_quantile(mix["prompt_len"], u) for u in grid]
    outs = [_quantile(mix["max_new_tokens"], u) for u in grid]
    random.Random(mix.get("pairing_seed", 0)).shuffle(outs)
    cap = mix.get("sum_max")
    return [(p, min(o, cap - p) if cap else o) for p, o in zip(prompts, outs)]


def serve_requests(mix: dict, seed: int):
    """Per client, the list of (prompt token ids, max_new_tokens) it cycles through."""
    rng = random.Random(seed)
    pool = size_pool(mix)
    random.Random(mix.get("pairing_seed", 0) + 1).shuffle(pool)  # the file's order, the same for every seed
    lo, hi = mix["token_id_range"]
    shared = mix.get("shared_prefix") or {}
    prefixes = [[rng.randrange(lo, hi) for _ in range(shared.get("len", 0))]
                for _ in range(shared.get("groups", 0))]
    clients = [[] for _ in range(mix["clients"])]
    for i, (p, o) in enumerate(pool):
        head = prefixes[rng.randrange(len(prefixes))][:p] if prefixes else []
        toks = head + [rng.randrange(lo, hi) for _ in range(p - len(head))]
        clients[i % len(clients)].append((toks, o))
    return clients


def ppo_prompts(mix: dict, seed: int):
    rng = random.Random(seed)
    return ["".join(chr(rng.randrange(97, 123)) for _ in range(mix["prompt_chars"]))
            for _ in range(mix["n_prompts"])]


def decode_bytes(ids) -> str:
    """The byte tokenizer's published rule: ids 0..255 are bytes, the rest are dropped."""
    return bytes(int(i) for i in ids if 0 <= int(i) < 256).decode("utf-8", errors="replace")


def reward(text: str) -> float:
    """The PPO mix's host reward: cheap, deterministic, in [-1, 1], different from row to row."""
    return (sum(text.encode("utf-8")) % 97) / 48.0 - 1.0
