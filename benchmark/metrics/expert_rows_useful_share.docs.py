"""Routed rows over the rows the expert matmuls computed (tile padding included), over the prefills the
engine harvested in the traced window: the ``moe_held`` and ``moe_rows`` attributes of its
``engine.harvest_prefills`` spans. Every held expert on every token would read 100 x 1.5 / 40 = 3.75."""

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
