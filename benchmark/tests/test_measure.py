import types

import pytest

from benchmark import harness, measure


def family_of(config):
    """A configuration's family and its sizes, found as a cell's are: by
    the file's ``model_type``."""
    config_file = harness.load_json("configs", config + ".json")
    family = harness.load_module("reference", config_file["model_type"])
    return family, family.Sizes(config_file), config_file


def request(due, first, done, tokens=128, frames=16, error=None):
    record = {"due": due, "sent": due + 0.001, "output_ids": list(range(tokens))}
    if first is not None:
        step = (done - first) / max(1, frames - 1)
        per = tokens // frames
        record["frames"] = [(first + i * step, per) for i in range(frames)]
        record["done"] = done
    else:
        record["frames"] = []
    if error:
        record["error"] = error
        record.pop("done", None)
    return record


def ctx_of(requests, opens=0.0, closes=10.0, counted_by="due"):
    return {"counted_by": counted_by, "requests": requests, "limit_s": 60.0,
            "window": {"opens": opens, "closes": closes}}


def test_percentile_interpolates():
    assert measure.percentile([], 50) is None
    assert measure.percentile([1, 2, 3, 4], 50) == 2.5
    assert measure.percentile(list(range(101)), 95) == 95
    assert measure.percentile([5], 95) == 5


def test_ttft_counts_from_due_and_over_all_requests_due_in_the_window():
    requests = [request(i, i + 0.5, i + 2.0) for i in range(10)]
    requests.append(request(11.0, 11.1, 12.0))  # due after the close: not counted
    ctx = ctx_of(requests)
    assert len(measure.ttft_ms(ctx)) == 10
    assert measure.percentile(measure.ttft_ms(ctx), 50) == pytest.approx(500.0)


def test_a_failed_request_counts_as_the_worst():
    requests = [request(i * 0.1, i * 0.1 + 0.5, i * 0.1 + 2.0) for i in range(19)]
    requests.append(request(1.9, None, None, error="no complete answer"))
    ctx = ctx_of(requests)
    assert max(measure.ttft_ms(ctx)) == 60000.0
    assert measure.percentile(measure.ttft_ms(ctx), 95) > 500.0


def test_a_stalled_window_moves_ttft_p95_and_out_tok_s():
    steady = [request(i * 0.1, i * 0.1 + 0.2, i * 0.1 + 1.2) for i in range(100)]
    stalled = [
        request(i * 0.1, max(i * 0.1, 8.0 if 40 <= i < 80 else 0) + 0.2,
                max(i * 0.1, 8.0 if 40 <= i < 80 else 0) + 1.2)
        for i in range(100)
    ]
    p95 = lambda rs: measure.percentile(measure.ttft_ms(ctx_of(rs)), 95)  # noqa: E731
    assert p95(steady) == pytest.approx(200.0)
    assert p95(stalled) > 3000.0
    rate = lambda rs: measure.out_tok_s(ctx_of(rs, 0.0, 8.0))  # noqa: E731
    assert rate(stalled) < 0.6 * rate(steady)


def test_out_tok_s_is_not_quantised_by_whole_harvests():
    # 32 slots, each request 1 token at once and then 32 every 2 s, four
    # times; every harvest hands the clients 1,024 tokens in one instant.
    # Whatever the window's phase against the harvests: (1 + 128) tokens a
    # request, a request a slot every 8 s
    requests = [
        {"frames": [(start, 1)] + [(start + 2.0 * k, 32) for k in (1, 2, 3, 4)]}
        for start in range(0, 80, 8) for _ in range(32)
    ]
    for opens in (20.0, 20.7, 21.9, 23.0):
        ctx = ctx_of(requests, opens, opens + 32.0, "ended")
        assert measure.out_tok_s(ctx) == pytest.approx(32 * 129 / 8.0, rel=0.01)
    # counted at their instants the same frames read 480 to 544
    assert measure.out_tok_s(ctx_of([], 0.0, 10.0)) is None


def test_out_tok_s_counts_only_what_lies_inside_the_window():
    record = {"frames": [(10.0, 1), (12.0, 50), (20.0, 50)]}  # 100 over 10..20
    assert measure.out_tok_s(ctx_of([record], 0.0, 40.0)) == pytest.approx(101 / 40)
    assert measure.out_tok_s(ctx_of([record], 15.0, 25.0)) == pytest.approx(50 / 10)
    assert measure.out_tok_s(ctx_of([record], 10.0, 15.0)) == pytest.approx(51 / 5)
    assert measure.out_tok_s(ctx_of([record], 30.0, 40.0)) is None


def test_tpot_is_per_token_after_the_first():
    ctx = ctx_of([request(1.0, 2.0, 2.0 + 1.27, tokens=128)])
    assert measure.tpot_ms(ctx) == [pytest.approx(10.0)]


def test_slot_occupancy_and_padding_share():
    ctx = {
        "slots": 4, "window": {"opens": 0.0, "closes": 10.0},
        "chunk_log": [(8, 4, 0.1), (8, 2, 0.1), (8, 4, 0.1), (8, 1, 0.1)],
        "counters": {
            "open": {"chunk_log_len": 1, "tokens_wasted": {"prefill_padding": 100}},
            "close": {"chunk_log_len": 3, "tokens_wasted": {"prefill_padding": 400}},
        },
        "requests": [{"prompt_ids": [0] * 100, "engine_first": 5.0}],
    }
    assert measure.slot_occupancy(ctx) == pytest.approx(75.0)
    assert measure.prefill_useful_share(ctx) == pytest.approx(25.0)


# by hand, a layer of each tiny preset: q 64x64, k and v 64x32 each, o
# 64x64; then three 64x128 MLP matrices (qwen2), or a 64x4 router and the
# three matrices of the 2 experts of 4 that a token is routed to (mixtral)
ATTENTION = 64 * 64 + 2 * 64 * 32 + 64 * 64
LAYER = {
    "tiny-selftest": ATTENTION + 3 * 64 * 128,
    "tiny-moe-selftest": ATTENTION + 64 * 4 + 2 * 3 * 64 * 128,
}


@pytest.mark.parametrize("config", sorted(LAYER))
def test_flops_against_a_hand_count_for_the_tiny_presets(config):
    family, sizes, _ = family_of(config)
    layer = LAYER[config]
    if config == "tiny-selftest":
        # the count of a GQA decoder, reached through its family's file
        from benchmark import flops

        assert flops.layer_matmul_params(sizes) == layer == 36864
        assert family.prompt_flops is flops.prompt_flops
    else:
        assert family.layer_matmul_params(sizes) == layer == 61696
    head = 64 * 512
    # ten prompt tokens: matmuls, causal attention over 1+2+...+10 keys
    # (4 heads x 16 x 2 matmuls x 2 flops, 2 layers), the head once
    attention = 4 * 4 * 16 * 2 * 55
    assert family.prompt_flops(sizes, 10) == 2 * layer * 2 * 10 + attention + 2 * head
    assert family.output_token_flops(sizes, 11) == 2 * (layer * 2 + head) + 4 * 4 * 16 * 2 * 11


@pytest.mark.parametrize("config", sorted(LAYER))
def test_mfu_is_work_over_wall_time_times_peak(config):
    family, sizes, _ = family_of(config)
    record = {
        "prompt_ids": [0] * 10, "engine_first": 1.0,
        "frames": [(1.0, 1), (2.0, 1), (3.0, 1)],
    }
    ctx = {
        "family": family, "sizes": sizes, "chips": 1, "requests": [record],
        "peaks": {"bf16_flops_per_s": 1e6},
        "trace": {"begin": {"at": 0.0}, "end": {"at": 4.0}},
    }
    work = family.prompt_flops(sizes, 10) + sum(
        family.output_token_flops(sizes, 10 + j) for j in (1, 2)
    )
    assert measure.mfu(ctx) == pytest.approx(100.0 * work / 4e6)
    ctx["trace"] = None
    assert measure.mfu(ctx) is None


@pytest.mark.parametrize("config", sorted(LAYER))
def test_a_kernels_roofline_is_found_by_the_kernels_name(config):
    """The seconds are those of the kernel NAMED, inside the programs whose
    kind is named, and the work is the family's count for that name: a
    second kernel in the same program, the same kernel in a prefill, and a
    kernel the family does not count change nothing or read nothing."""
    family, sizes, _ = family_of(config)
    record = {
        "prompt_ids": [0] * 10, "engine_first": 1.0,
        "frames": [(1.0, 1), (2.0, 1), (3.0, 1)],
    }
    programs = [
        {"kind": "decode_chunk_dense", "kernels": {
            "flash_decode": {"calls": 8, "seconds": 2e-3},
            "expert_matmul": {"calls": 8, "seconds": 5e-3}}},
        {"kind": "decode_chunk_paged", "kernels": {
            "flash_decode": {"calls": 8, "seconds": 2e-3}}},
        {"kind": "prefill_dense", "kernels": {
            "flash_decode": {"calls": 2, "seconds": 9e-3},
            "flash_prefill": {"calls": 2, "seconds": 1e-3}}},
    ]
    ctx = {
        "family": family, "sizes": sizes, "chips": 1, "requests": [record],
        "peaks": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6},
        "trace": {"begin": {"at": 0.0}, "end": {"at": 4.0}, "programs": programs},
    }
    served = measure.served_between(ctx, 0.0, 4.0)
    # two decoded tokens, which saw 11 and 12 keys
    assert served == {"prompts": [10], "decode_queries": 2, "decode_keys": 23}
    work, moved = family.kernel_work(sizes, "flash_decode", served)
    # by hand, 2 layers: QK^T and PV (4 heads x 16) over 23 keys; K and V
    # rows of 2 kv heads x 16 in bf16 once each, two queries in and out
    assert work == 4 * 4 * 16 * 23 * 2
    assert moved == (2 * 2 * 16 * 2 * 23 + 2 * 2 * 4 * 16 * 2) * 2
    least = max(work / 1e9, moved / 1e6)
    assert measure.kernel_roofline(ctx, "flash_decode", within="decode_chunk") == (
        pytest.approx(100.0 * least / 4e-3)
    )
    assert measure.kernel_roofline(ctx, "flash_decode", within="prefill") == (
        pytest.approx(100.0 * least / 9e-3)
    )
    # a kernel the trace holds and the family does not count: left out
    assert family.kernel_work(sizes, "expert_matmul", served) is None
    assert measure.kernel_roofline(ctx, "expert_matmul", within="decode_chunk") is None
    # a kernel the family counts and the trace does not hold: left out
    assert measure.kernel_roofline(ctx, "flash_decode", within="verify") is None
    ctx["requests"] = []  # nothing decoded in the window: nothing to count
    assert measure.kernel_roofline(ctx, "flash_decode", within="decode_chunk") is None


def engine_config_of(preset):
    from langstream_tpu.providers.jax_local.model import LlamaConfig

    return LlamaConfig.from_dict({"preset": preset, "vocab-size": 512, "head-dim": 16})


@pytest.mark.parametrize("config,preset", [
    ("tiny-selftest", "tiny-qwen2"), ("tiny-moe-selftest", "tiny-moe"),
])
def test_the_size_check_is_the_familys_and_the_exit_is_the_harnesss(config, preset):
    family, _, config_file = family_of(config)
    program = engine_config_of(preset)
    harness.check_sizes(family, program, config_file)  # as the app builds it
    held = family.size_check(program)
    assert set(held) <= set(config_file)
    assert ("num_local_experts" in held) == (config == "tiny-moe-selftest")
    for key in held:
        # every key the family holds ends the run when the file differs ...
        wrong = dict(config_file, **{key: "another"})
        with pytest.raises(SystemExit, match=key):
            harness.check_sizes(family, program, wrong)
        # ... or lacks it
        with pytest.raises(SystemExit, match=key):
            harness.check_sizes(
                family, program, {k: v for k, v in config_file.items() if k != key}
            )


def test_a_family_checks_what_the_other_has_not():
    """The mixture's file against the dense program, and the other way
    round: the keys that only one family has are held too."""
    moe_family, _, moe_file = family_of("tiny-moe-selftest")
    with pytest.raises(SystemExit, match="num_local_experts"):
        harness.check_sizes(moe_family, engine_config_of("tiny"), moe_file)
    fewer = types.SimpleNamespace(**{
        **vars(engine_config_of("tiny-moe")), "dims_per_head": 16, "num_experts_per_tok": 1,
    })
    with pytest.raises(SystemExit, match="num_experts_per_tok"):
        harness.check_sizes(moe_family, fewer, moe_file)
