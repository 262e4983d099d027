"""Prompt tokens over prompt tokens plus bucket padding, over the traced
window: the prompts whose first token the engine handed out between the
trace's two counter readings, against the padding the engine counted
between them. Not ``measure.prefill_useful_share``, which the ``chat``
reader calls: that one takes the counters at the run window's close, and a
traced run reads them only once its trace is written, a minute late. An
open loop has stopped sending by then; this cell's closed loop has not, so
the padding of that minute would be counted against the window's prompts.
Both trace readings are taken just after a decode harvest, when no prefill
is in flight, so prompts and padding are of the same dispatches."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    begin, end = trace["begin"], trace["end"]
    prompt = sum(
        len(r["prompt_ids"]) for r in ctx["requests"]
        if r.get("prompt_ids") and begin["at"] <= r.get("engine_first", -1.0) < end["at"]
    )
    padding = (
        end["tokens_wasted"].get("prefill_padding", 0)
        - begin["tokens_wasted"].get("prefill_padding", 0)
    )
    if prompt + padding <= 0:
        return None
    return 100.0 * prompt / (prompt + padding)
