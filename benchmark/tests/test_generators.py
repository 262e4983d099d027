import collections
import json
import os

import pytest

from benchmark import harness
from benchmark.generators import prompts, users
from benchmark.tokenizer import VisibleTokenizer

TRAFFIC = os.path.join(harness.HERE, "traffic")
FILES = sorted(f for f in os.listdir(TRAFFIC) if f.endswith(".json"))


def load(name):
    with open(os.path.join(TRAFFIC, name)) as handle:
        return json.load(handle)


def planned(name, seed, seconds=10.0, slots=8):
    traffic = load(name)
    generator = harness.load_module("generators", traffic["kind"])
    return generator.plan(traffic, seed, seconds, slots)


@pytest.mark.parametrize("name", FILES)
def test_every_traffic_file_names_a_generator_that_plans(name):
    plan = planned(name, 3_000_000_007)
    assert plan.requests and plan.users and plan.window_seconds > 0
    assert load(name)["counted_by"] in ("due", "ended")
    numbers = [r["index"] for r in plan.requests]
    assert numbers == list(range(len(numbers)))


@pytest.mark.parametrize("name", FILES)
def test_same_seed_same_plan_other_seed_other_order(name):
    a, b, c = planned(name, 11), planned(name, 11), planned(name, 2 ** 31 + 5)
    assert a.requests == b.requests and a.users == b.users
    assert [r["question"] for r in a.requests] != [r["question"] for r in c.requests]


@pytest.mark.parametrize("name", FILES)
def test_length_histogram_is_the_same_for_every_seed(name):
    def histogram(seed):
        return collections.Counter(
            (r["phase"], r["turn"], len(r["question"])) for r in planned(name, seed).requests
        )

    assert histogram(1) == histogram(2) == histogram(3_000_000_000)


def test_exactly_one_arrival_in_every_interval():
    traffic = load("chat.json")
    rate = traffic["users"]["rate_per_s"]
    for seed in (1, 2, 99):
        plan = users.plan(traffic, seed, 20.0, 128)
        starts = [user["start_s"] for user in plan.users]
        assert [int(s * rate) for s in starts] == list(range(len(starts)))
        assert [r["due_s"] for r in plan.requests] == starts  # one turn a user
        window = [r for r in plan.requests if r["phase"] == "window"]
        assert len(window) == round(rate * 20.0)
        assert min(r["due_s"] for r in window) >= plan.opens_after_s
        assert plan.window_seconds == len(window) / rate


def test_lengths_are_the_quantiles_and_questions_are_distinct():
    spec = load("chat.json")["prompts"]
    lengths = prompts.lengths(spec, 400)
    assert lengths == sorted(lengths)
    assert lengths[0] == spec["min_chars"] and lengths[-1] == spec["max_chars"]
    assert abs(lengths[200] - spec["median_chars"]) <= 1
    asked = prompts.questions(spec, range(400), 5, "x")
    assert sorted(len(q) for q in asked) == lengths
    heads = {q[:6] for q in asked}
    assert len(heads) == 400  # no two share 16 tokens with the template's 10
    assert asked[12].startswith("210000 ")


def test_every_block_spans_the_distribution():
    spec = dict(load("chat.json")["prompts"], block=20)
    sizes = [len(q) for q in prompts.questions(spec, range(400), 9, "x")]
    median = sorted(sizes)[200]
    for start in range(0, 400, 20):
        block = sizes[start:start + 20]
        above = sum(1 for n in block if n > median)
        assert 8 <= above <= 12


@pytest.mark.parametrize("name", ["chat.json", "sat.json"])
def test_the_frame_gives_the_prompt_the_example_app_would_with_the_question_first(name):
    """The instruction moved from the app's template into the mix's frame:
    the prompt's tokens are those of the example's message with the
    question before the instruction, 154 to 1,796 of them."""
    tokenizer = VisibleTokenizer()
    sizes = set()
    for record in planned(name, 7, 45.0, 32).requests:
        question = record["question"].split("\n\n")[0]
        example = (
            question + "\n\nYou are a helpful assistant. Above you can find a question "
            "from the user. Please try to help them the best way you can."
        )
        assert record["question"] == example
        sizes.add(len(tokenizer.apply_chat_template([{"role": "user", "content": example}])))
    assert min(sizes) == 154 and max(sizes) == 1796


def test_a_fixed_population_lays_out_two_users_a_slot():
    plan = users.plan(load("sat.json"), 4, 45.0, 32)
    assert len(plan.users) == 64 and plan.opens_after_finished == 32
    # 32 users over a request's 8 s
    assert [u["start_s"] for u in plan.users[:3]] == [0.0, 0.25, 0.5]
    assert all(u["again"] for u in plan.users)
    assert len(plan.requests) == 4096 and plan.window_seconds == 45.0


def test_a_follow_up_carries_its_conversation_and_shares_its_session():
    plan = planned("selftest-sessions.json", 5)
    spec = load("selftest-sessions.json")["prompts"]
    before, _, _ = spec["frame"].partition("{question}")
    by_session = collections.defaultdict(list)
    for record in plan.requests:
        by_session[record["session"]].append(record)
    assert len(by_session) == len(plan.users)
    for turns in by_session.values():
        assert [r["turn"] for r in turns] == [0, 1, 2]
        asked = [r["question"][len(before):] for r in turns]
        assert asked[1].startswith(asked[0] + " ") and asked[2].startswith(asked[1] + " ")
        assert "due_s" in turns[0] and "due_s" not in turns[1]
