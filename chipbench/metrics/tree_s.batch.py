"""Seconds per family inside the program's ``tree`` span (distances and
the tree: exact tiled NJ or the HPTree pipeline), over the families
completed in the traced window."""


def read(ctx):
    fams = ctx["work"].get("families", 0)
    if not fams or "tree" not in ctx["spans"]:
        return None
    return ctx["spans"]["tree"] / fams
