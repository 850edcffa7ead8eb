"""Share of the rows that ivf_gamma's probe-masked scan scored which lay
in the queries' probed IVF lists, in %: 100 · `probe_rows` /
(`scan_queries` · N), the program's counters totalled over every
`search` of the closed loop. Nothing where no query took the scan."""


def read(ctx):
    if ctx.kind != "closed" or not ctx.spans or "search" not in ctx.spans:
        return None
    c = ctx.spans["search"].get("counters", {})
    if not c.get("scan_queries"):
        return None
    return 100.0 * c.get("probe_rows", 0) / (c["scan_queries"] * ctx.dims["n"])
