"""Mean candidate rows gathered and scored per query served in the
closed loop: the methods' `cand_rows` counter (pad slots of their lists
included) over the `queries` counter, totalled over every `search`."""


def read(ctx):
    if ctx.kind != "closed" or not ctx.spans or "search" not in ctx.spans:
        return None
    c = ctx.spans["search"].get("counters", {})
    if "cand_rows" not in c or not c.get("queries"):
        return None
    return c["cand_rows"] / c["queries"]
