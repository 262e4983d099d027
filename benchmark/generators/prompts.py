"""The fixed multiset of questions every traffic mix draws from.

The lengths are the evenly spaced quantiles of a clipped lognormal over
the question's length in characters; the seed permutes them and fills the
text, and never changes the histogram. The permutation is stratified too:
the sorted lengths are dealt round-robin into blocks, so that every block
of consecutive requests spans the whole distribution, and the seed
shuffles inside a block and the order of the blocks.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import Any, Dict, List, Sequence

_WORDS = (
    "stream topic agent record broker gateway prompt token cache batch "
    "vector query index chunk model answer window offset commit schema"
).split()


def lengths(spec: Dict[str, Any], count: int) -> List[int]:
    """``count`` quantiles at (i + 0.5) / count of the clipped lognormal."""
    normal = NormalDist()
    median, sigma = float(spec["median_chars"]), float(spec["sigma"])
    low, high = int(spec["min_chars"]), int(spec["max_chars"])
    out = []
    for i in range(count):
        z = normal.inv_cdf((i + 0.5) / count)
        out.append(min(high, max(low, round(median * math.exp(sigma * z)))))
    return out


def permute(sorted_lengths: List[int], block: int, rng: random.Random) -> List[int]:
    count = len(sorted_lengths)
    blocks = max(1, count // max(1, block))
    dealt = [sorted_lengths[b::blocks] for b in range(blocks)]
    for hand in dealt:
        rng.shuffle(hand)
    rng.shuffle(dealt)
    return [length for hand in dealt for length in hand]


def question(index: int, length: int, rng: random.Random) -> str:
    """A question of exactly ``length`` characters that starts with its own
    number, lowest digit first: two questions differ within their first
    six characters, so where the mix's ``frame`` puts the question first
    no two prompts share 16 tokens (the chat template's head is 10), the
    least the engine's prefix reuse takes."""
    head = f"{index:06d}"[::-1] + " "
    words = []
    size = len(head)
    while size < length:
        word = rng.choice(_WORDS)
        words.append(word)
        size += len(word) + 1
    return (head + " ".join(words))[:max(length, len(head))].ljust(length, "?")


def shapes(spec: Dict[str, Any], count: int, turns: int) -> List[List[int]]:
    """The fixed multiset of ``count`` conversations, each the lengths of
    its ``turns`` questions, sorted by the first. Every turn's lengths are
    the quantiles; a follow-up's meet the first questions' at a fixed
    stride, so that long does not always follow long, and no seed changes
    which lengths share a conversation."""
    base = lengths(spec, count)
    stride = next(
        (s for s in range(max(1, round(0.618 * count)), 2 * count + 2) if math.gcd(s, count) == 1),
        1,
    )
    return [
        [base[k]] + [base[(k * stride + turn) % count] for turn in range(1, turns)]
        for k in range(count)
    ]


def conversations(spec: Dict[str, Any], indexes: Sequence[Sequence[int]], seed: int,
                  salt: str) -> List[List[str]]:
    """One conversation for every row of ``indexes`` (the numbers of its
    questions): the multiset of shapes, permuted and filled from the seed."""
    rng = random.Random(f"{seed}:{salt}")
    turns = len(indexes[0]) if indexes else 0
    order = permute(shapes(spec, len(indexes), turns), int(spec["block"]), rng)
    return [
        [question(index, n, rng) for index, n in zip(row, shape)]
        for row, shape in zip(indexes, order)
    ]


def questions(spec: Dict[str, Any], indexes: Sequence[int], seed: int, salt: str) -> List[str]:
    """Conversations of one question each."""
    return [c[0] for c in conversations(spec, [[i] for i in indexes], seed, salt)]


def message(spec: Dict[str, Any], asked: Sequence[str]) -> str:
    """What the client sends for a turn: the conversation's questions so
    far (a chat client sends its history again) inside the mix's ``frame``,
    the text around ``{question}``. The app's template adds nothing of its
    own, so whether the instruction stands before the question (every
    prompt shares it: the engine's warm prefill) or after it (no two
    prompts share a prefix: cold prefills) is the mix's to say."""
    return str(spec["frame"]).replace("{question}", " ".join(asked))
