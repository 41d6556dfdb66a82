"""The benchmark's arithmetic on the CPU: FLOPs and bytes from shapes,
the percentile and ITL arithmetic, and the peaks table."""
import json

import pytest

import bench_tiny  # noqa: F401  (puts the harness on sys.path)
import yardstick as ys
from drivers.serve import warm_plan
from gen.requests import ServeRequest, requests

QWEN2 = json.loads((bench_tiny.HERE / "configs" / "qwen2-0.5b.json")
                   .read_text())["model"]
INTERNLM2 = json.loads((bench_tiny.HERE / "configs" / "internlm2-1.8b.json")
                       .read_text())["model"]


def test_parameter_counts_match_the_published_models():
    assert ys.all_params(QWEN2) == 494_032_768          # Qwen2-0.5B
    assert ys.all_params(INTERNLM2) == 1_889_110_016    # InternLM2-1.8B
    # tied head: the output projection is the embedding table
    assert ys.matmul_params(QWEN2) == ys.all_params(QWEN2) - (
        24 * (2 * 896 + (14 + 4) * 64) + 896)


def test_train_flops_are_six_n_per_token_plus_causal_attention():
    tokens = 4 * 128
    n = ys.matmul_params(QWEN2)
    attn = 3 * 4 * 24 * 14 * 64 * (128 * 129 / 2) * 4
    assert ys.train_step_flops(QWEN2, 1, 4, 128) == pytest.approx(
        6 * n * tokens + attn, rel=1e-12)
    assert ys.train_step_flops(QWEN2, 4, 4, 128) == pytest.approx(
        4 * ys.train_step_flops(QWEN2, 1, 4, 128), rel=1e-12)


def test_serve_flops_count_prompt_and_decoded_positions():
    n = ys.matmul_params(INTERNLM2)
    # a 10-token prompt and 1 output token: the prompt's forward only
    assert ys.serve_request_flops(INTERNLM2, 10, 1) == pytest.approx(
        2 * n * 10 + 4 * 24 * 16 * 128 * 55, rel=1e-12)
    more = ys.serve_request_flops(INTERNLM2, 10, 3) \
        - ys.serve_request_flops(INTERNLM2, 10, 1)
    # two decode positions, at contexts 11 and 12
    assert more == pytest.approx(2 * (2 * n) + 4 * 24 * 16 * 128 * 23,
                                 rel=1e-12)


def test_prox_update_moves_twenty_bytes_a_parameter():
    assert ys.prox_update_bytes(QWEN2) == 20 * 494_032_768


def test_percentiles_by_nearest_rank():
    xs = list(range(1, 201))
    assert ys.percentile(xs, 95) == 190
    assert ys.beyond(200, 95) == 10
    assert ys.percentile([5.0], 95) == 5.0
    with pytest.raises(ValueError):
        ys.percentile([], 95)


def test_itl_is_a_tail_over_tokens_not_steps():
    # 19 steps of 10 ms with 1 row, one step of 50 ms with 16 rows: 16 of
    # 35 tokens waited 50 ms, so the p95 over tokens is 50 ms, while the
    # p95 over steps would be 10 ms
    log = [(10.0, 1)] * 19 + [(50.0, 16)]
    assert ys.weighted_percentile(log, 95) == 50.0
    assert ys.percentile([g for g, _ in log], 95) == 10.0
    assert ys.weighted_percentile([(3.0, 0), (7.0, 2)], 50) == 7.0


def test_peaks_lookup_refuses_an_unknown_device():
    assert ys.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert ys.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ys.UnknownDevice):
        ys.peaks("cpu")


def test_every_seed_sends_the_same_work_in_another_order():
    mix = json.loads((bench_tiny.HERE / "traffic" / "serve-chat.json")
                     .read_text())
    a = requests(mix, 1000, 1, 30.0)
    b = requests(mix, 1000, 2**33 + 5, 30.0)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 30.0)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert sorted(map(key, a)) == sorted(map(key, b))
        assert list(map(key, a)) != list(map(key, b))
    assert all(0 <= r.due_s < 30.0 for r in a)
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)
    p = mix["prompt_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in a)
    again = requests(mix, 1000, 1, 30.0)
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, again))


def test_warm_plan_reaches_every_width_the_requests_can():
    from repro.serve.bucketing import table_width

    def width(n):
        return table_width(n, 16, 1536)

    reqs = [ServeRequest(0.0, [0] * n, o)
            for n, o in ((16, 8), (100, 30), (1024, 512))]
    plan, extra = warm_plan(reqs, width)
    assert [w for w, _, _ in plan] == [2, 4, 8, 16, 32, 64, 128]
    for w, p, pre in plan:
        assert 16 <= p <= 1024
        # the long request's first decode after `pre` tokens is at width w
        assert width(p + pre + 1) == w
    assert extra == []  # the shortest prompt, 16, prefills at width 1
    # a prompt longer than any decode width's shortest still prefills
    plan, extra = warm_plan([ServeRequest(0.0, [0] * n, 2)
                             for n in (20, 200)], width)
    assert [w for w, _, _ in plan] == [2, 4, 8, 16]
    assert {width(p) for _, p, _ in plan} | {width(p) for p in extra} >= \
        {width(20), width(200)}
