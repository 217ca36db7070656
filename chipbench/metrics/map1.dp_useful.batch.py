"""Share of map(1)'s dispatched DP cells that real pairs need, in %:
useful / (useful + pad) cells of the engine's ``to_center`` calls, read
from the program's counters ``repro_align_cells_total`` and
``repro_align_pad_cells_total`` (label ``api="to_center"``). Useful is
each real pair's query length x center length; pad is everything else
the calls computed: width padding, the duplicate rows that fill the last
direction-budget chunk, and full-DP fallback rows.

The counters hold the whole run: set-up's one run of each family and the
window's runs of the same families. The share depends only on their
lengths and the chunk plan, so the run's share is the window's. Nothing
dispatched reads as no value.
"""
COUNTERS = ("repro_align_cells_total", "repro_align_pad_cells_total")


def _total(snapshot, name, api):
    return sum(s["value"] for s in snapshot.get(name, {}).get("samples", ())
               if s["labels"].get("api") == api)


def read(ctx, registry=None):
    if registry is None:
        from repro.obs.metrics import REGISTRY as registry
    snap = registry.snapshot()
    useful, pad = (_total(snap, name, "to_center") for name in COUNTERS)
    if useful + pad <= 0:
        return None
    return 100.0 * useful / (useful + pad)
