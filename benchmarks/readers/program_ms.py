"""Mean device time of one run of the programs named ``module*``, from the
trace. which = "most_run": the program among them that ran most often (a
decode step); "others": all the rest (where every serve program carries one
name, the prefill programs)."""


def read(ctx, module, which="most_run"):
    reduced = ctx.get("reduced")
    if not reduced:
        return None
    mods = [v for k, v in reduced["modules"].items() if k.startswith(module)]
    if not mods:
        return None
    most = max(mods, key=lambda v: v["count"])
    chosen = [most] if which == "most_run" else [v for v in mods if v is not most]
    runs = sum(v["count"] for v in chosen)
    return 1e3 * sum(v["seconds"] for v in chosen) / runs if runs else None
