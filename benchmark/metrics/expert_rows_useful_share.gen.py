"""Routed rows over the rows the expert matmuls computed (tile padding included), over the prefills the
engine harvested in the traced window: the ``moe_held`` and ``moe_rows`` attributes of its
``engine.harvest_prefills`` spans (a chunked prompt's windows summed at its harvest). A 256-row window routes
1,024 pairs over 64 experts at a 16-row tile, so this reads what the prefills' tiles cost beside the decode
route's (``expert_rows_useful_share_decode.gen``); every expert holds every token's pairs here, so it cannot
read the share of experts held, as ``docs``' does."""

from benchmark import spans


def read(ctx):
    found = spans.of(ctx)
    if not found:
        return None
    held = rows = 0
    for span in found["read"]["phases"]:
        attrs = span["attrs"]
        if span["name"] == "engine.harvest_prefills" and "moe_rows" in attrs:
            held += int(attrs["moe_held"])
            rows += int(attrs["moe_rows"])
    return 100.0 * held / rows if rows else None
