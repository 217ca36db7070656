"""Seconds per family inside the program's ``map1.chain`` span (map(1)'s
k-mer phase: the center index, the chaining of every pair and the host
read of its per-pair ``ok`` flags), over the families completed in the
traced window."""


def read(ctx):
    fams = ctx["work"].get("families", 0)
    if not fams or "map1.chain" not in ctx["spans"]:
        return None
    return ctx["spans"]["map1.chain"] / fams
