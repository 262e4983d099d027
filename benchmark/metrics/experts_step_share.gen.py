"""Device seconds of the kernel named ``moe_grouped_matmul`` inside the decode programs over those programs'
device seconds, from the reduced trace's ``programs[].kernels``: the share of a decode step the routed
experts' matmuls take. None where the trace holds no decode program or none of them holds the kernel (a
program without routed experts)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    decode = [p for p in trace["programs"] if p["kind"].startswith("decode")]
    whole = sum(p["seconds"] for p in decode)
    experts = sum(
        p["kernels"]["moe_grouped_matmul"]["seconds"]
        for p in decode if "moe_grouped_matmul" in p["kernels"]
    )
    if whole <= 0 or experts <= 0:
        return None
    return 100.0 * experts / whole
