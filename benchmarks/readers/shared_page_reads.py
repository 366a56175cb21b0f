"""Of the pages the decode steps of the window read (every live slot's context, in pages), the share that another
live slot read too: the flight record's ``shared_pages_read`` over its ``pages_read``. Nothing where a record lacks
them: a program without the counters."""


def read(ctx):
    steps = [s for s in ctx["measured"].get("flight") or [] if s.get("active")]
    if not steps or any("pages_read" not in s or "shared_pages_read" not in s for s in steps):
        return None
    read_ = sum(s["pages_read"] for s in steps)
    ctx["notes"]["page_reads"] = {"pages_read_per_step": read_ / len(steps),
                                  "shared_per_step": sum(s["shared_pages_read"] for s in steps) / len(steps)}
    return 100.0 * sum(s["shared_pages_read"] for s in steps) / read_ if read_ else None
