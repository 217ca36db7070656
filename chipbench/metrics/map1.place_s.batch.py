"""Seconds per family inside the program's ``map1.place`` span (the mesh
path's map(1) set-up: the center's k-mer index, padding the queries to
the shard count, and placing every operand on the mesh, ended on the
placed operands being ready), over the families completed in the traced
window. A program without the span reads as no value."""


def read(ctx):
    fams = ctx["work"].get("families", 0)
    if not fams or "map1.place" not in ctx["spans"]:
        return None
    return ctx["spans"]["map1.place"] / fams
