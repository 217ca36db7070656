"""Seconds per family inside the program's ``map1.dp`` span (map(1)'s
full-DP phase: the SW forward kernel and traceback of every pair whose
k-mer chain failed, or of every pair on the plain method), over the
families completed in the traced window."""


def read(ctx):
    fams = ctx["work"].get("families", 0)
    if not fams or "map1.dp" not in ctx["spans"]:
        return None
    return ctx["spans"]["map1.dp"] / fams
