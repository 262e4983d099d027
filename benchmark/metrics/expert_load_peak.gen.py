"""The busiest held expert's tokens over the held experts' mean, prefills and decode chunks of the traced
window together: the ``moe_by_expert`` attribute of the ``engine.harvest_prefills`` and ``engine.emit``
spans. 1 is an even load."""

from benchmark import spans


def read(ctx):
    found = spans.of(ctx)
    if not found:
        return None
    total = None
    for span in found["read"]["phases"]:
        listed = span["attrs"].get("moe_by_expert")
        if span["name"] in ("engine.harvest_prefills", "engine.emit") and listed:
            counts = [int(n) for n in str(listed).split(":")]
            total = counts if total is None else [a + b for a, b in zip(total, counts)]
    if not total or not sum(total):
        return None
    return max(total) * len(total) / sum(total)
