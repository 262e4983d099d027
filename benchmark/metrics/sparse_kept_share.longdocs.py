"""Key blocks the sparse layers attended over the key blocks their queries had in context, over the decode chunks
the engine harvested in the traced window: the ``sparse_kept`` and ``sparse_visible`` attributes of its
``engine.emit`` spans (summed over sparse layers, kv heads and live slots). Under 100 it proves the selection ran;
a program without the counters leaves the attributes out and this reads nothing."""

from benchmark import spans


def read(ctx):
    found = spans.of(ctx)
    if not found:
        return None
    kept = visible = 0
    for span in found["read"]["phases"]:
        attrs = span["attrs"]
        if span["name"] == "engine.emit" and "sparse_visible" in attrs:
            kept += int(attrs["sparse_kept"])
            visible += int(attrs["sparse_visible"])
    return 100.0 * kept / visible if visible else None
