"""Share of map(1)'s k-mer chained pairs whose chain failed (and so went
to the full DP), in %: failed / (kept + failed), read from the program's
counter ``repro_kmer_chain_pairs_total{outcome}``.

The counter holds the whole run: set-up's one run of each family and the
window's runs of the same families, which chain alike. No chained pair
reads as no value.
"""
COUNTER = "repro_kmer_chain_pairs_total"


def read(ctx, registry=None):
    if registry is None:
        from repro.obs.metrics import REGISTRY as registry
    samples = registry.snapshot().get(COUNTER, {}).get("samples", ())
    by = {}
    for s in samples:
        outcome = s["labels"].get("outcome")
        by[outcome] = by.get(outcome, 0.0) + s["value"]
    total = by.get("kept", 0.0) + by.get("failed", 0.0)
    if total <= 0:
        return None
    return 100.0 * by.get("failed", 0.0) / total
