"""The comparison that decides ``correct`` for a served model, whatever
its family: the family's file (``reference/<model_type>.py``) is handed in.

Once the window has closed, a sample of the requests it finished (drawn
from the seed, the longest and the shortest prompt always in it) is run
through the plain reference: one forward pass over each prompt with its
served tokens. At every served token the gap is how far that token's
reference logit lies below the reference's best at its position: nought
where the program served the reference's own best. Two numbers are
compared, each against a limit of its own in the configuration's file:

- ``max_logit_gap``, the widest gap of the sample. Greedy serving in the
  stated precision puts a near-best token at every position; a wrong
  token, or a much lower precision, does not.
- ``mean_logit_gap``, the sample's mean gap. Rounding noise of size s
  flips a token about as often as s and by about s when it does, so the
  mean grows as s squared where the widest gap and the share of flipped
  tokens grow as s: it tells one precision from the next below where the
  widest gap, a single draw from the tail, does not.
"""

from __future__ import annotations

import random
from typing import Any, Dict, Optional, Sequence

import numpy as np


def draw_sample(records: Sequence[Dict[str, Any]], seed: int, count: int):
    """``count`` finished requests: the longest prompt and the shortest
    (so the largest and the smallest prefill bucket are both in it), then a
    draw from the seed. A record needs ``prompt_ids`` and ``output_ids``."""
    finished = [
        r for r in records
        if r.get("prompt_ids") is not None and r.get("output_ids")
    ]
    if not finished:
        return []
    finished.sort(key=lambda r: r["index"])
    ends = [max(finished, key=lambda r: len(r["prompt_ids"]))]
    shortest = min(finished, key=lambda r: len(r["prompt_ids"]))
    if shortest is not ends[0] and count > 1:
        ends.append(shortest)
    rest = [r for r in finished if not any(r is e for e in ends)]
    random.Random(seed).shuffle(rest)
    return ends + rest[: max(0, count - len(ends))]


def gaps(
    family,
    sizes,
    weights: Dict[str, Any],
    sample: Sequence[Dict[str, Any]],
    pad_to: int,
    lower: Optional[str] = None,
) -> Dict[str, Any]:
    """Widest gap of the served tokens (``lower`` None), or of the tokens
    that the lower precision puts first at the same positions (the
    control). Also the share of positions where the token compared is not
    the reference's own best."""
    rows = [list(r["prompt_ids"]) + list(r["output_ids"]) for r in sample]
    spans = [
        (len(r["prompt_ids"]) - 1, len(r["prompt_ids"]) - 1 + len(r["output_ids"]))
        for r in sample
    ]
    reference = family.logits_at(sizes, weights, rows, spans, pad_to)
    if lower is None:
        chosen = [np.asarray(r["output_ids"]) for r in sample]
    else:
        lowered = family.logits_at(sizes, weights, rows, spans, pad_to, lower)
        chosen = [np.argmax(l, axis=-1) for l in lowered]
    each, worst = [], None
    for record, logits, tokens in zip(sample, reference, chosen):
        best = logits.max(axis=-1)
        gap = best - logits[np.arange(len(tokens)), tokens]
        each.append({
            "tokens": len(gap), "not_best": int((gap > 0).sum()),
            "max": float(gap.max()) if len(gap) else 0.0,
            "sum": float(gap.sum(dtype=np.float64)),
        })
        if len(gap) and each[-1]["max"] >= max(e["max"] for e in each):
            worst = (record["index"], int(gap.argmax()))
    return dict(
        summed_up(each),
        worst_at=worst,
        each_request=each,
        requests=[r["index"] for r in sample],
        prompt_lengths=[len(r["prompt_ids"]) for r in sample],
    )


def summed_up(each: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """The comparison's numbers over the requests of ``each``."""
    total = sum(e["tokens"] for e in each)
    return {
        "max_logit_gap": max((e["max"] for e in each), default=0.0),
        "mean_logit_gap": sum(e["sum"] for e in each) / max(1, total),
        "tokens_compared": total,
        "tokens_not_best": sum(e["not_best"] for e in each),
    }
