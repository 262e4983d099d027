"""Routed rows over the rows the expert matmuls computed (tile padding included), over the decode chunks the
engine harvested in the traced window: the ``moe_held`` and ``moe_rows`` attributes of its ``engine.emit``
spans. A step of 64 slots at a 16-row tile computes a tile for every held expert a token meets, so this
reads what the decode route's padding costs (the prefills' share is the prefill spans' counters, which no metric of this cell reads)."""

from benchmark import spans


def read(ctx):
    found = spans.of(ctx)
    if not found:
        return None
    held = rows = 0
    for span in found["read"]["phases"]:
        attrs = span["attrs"]
        if span["name"] == "engine.emit" and "moe_rows" in attrs:
            held += int(attrs["moe_held"])
            rows += int(attrs["moe_rows"])
    return 100.0 * held / rows if rows else None
