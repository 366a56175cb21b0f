"""Share of the prompt tokens admitted since the window opened that were a
prefix hit (their pages found in the radix cache, their forward skipped):
the scheduler's own two totals."""


def read(ctx):
    m = ctx["measured"]
    if not m.get("prompt_tokens"):
        return None
    return 100.0 * m["prefix_tokens_saved"] / m["prompt_tokens"]
