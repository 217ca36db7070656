"""Seconds per family inside the program's ``assemble`` span (reduce(1)
and map(2): the merged gap profile, every row rebuilt in the MSA's frame
and copied to the host), over the families completed in the traced
window."""


def read(ctx):
    fams = ctx["work"].get("families", 0)
    if not fams or "assemble" not in ctx["spans"]:
        return None
    return ctx["spans"]["assemble"] / fams
