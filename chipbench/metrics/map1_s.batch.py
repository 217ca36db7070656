"""Seconds per family inside the program's ``map1`` span (the map(1)
stage: k-mer chaining and the SW kernel, on the host path or the mesh
pipeline), over the families completed in the traced window."""


def read(ctx):
    fams = ctx["work"].get("families", 0)
    if not fams or "map1" not in ctx["spans"]:
        return None
    return ctx["spans"]["map1"] / fams
