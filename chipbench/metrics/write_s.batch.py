"""Seconds per family inside the launcher's ``write`` spans (the aligned
FASTA and the Newick tree written to disk: both spans of ``msa_run``),
over the families completed in the traced window."""


def read(ctx):
    fams = ctx["work"].get("families", 0)
    if not fams or "write" not in ctx["spans"]:
        return None
    return ctx["spans"]["write"] / fams
