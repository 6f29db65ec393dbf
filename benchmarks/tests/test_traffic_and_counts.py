"""Traffic generators and the operation / byte counts."""

import json

import numpy as np
import pytest

from benchmarks import cells, counts
from benchmarks.stats import percentile
from benchmarks.traffic import lm_batches, requests

CODE = json.load(open(cells.BENCH / "traffic" / "code-completion.json"))
GPT2 = json.load(open(cells.BENCH / "configs" / "gpt2-medium.json"))
STAR = json.load(open(cells.BENCH / "configs" / "starcoderbase-1b.json"))


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345])
def test_requests_same_seed_same_requests(seed):
    a = requests.make(CODE, STAR, seed, 30.0)
    b = requests.make(CODE, STAR, seed, 30.0)
    assert len(a) == len(b) == round(CODE["rate_per_s"] * 30)
    for x, y in zip(a, b):
        assert x.arrival_time == y.arrival_time and x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)


def test_requests_inside_clips_and_window():
    reqs = requests.make(CODE, STAR, 3, 30.0)
    plen, olen = CODE["prompt_len"], CODE["output_len"]
    assert all(plen["min"] <= len(r.prompt) <= plen["max"] for r in reqs)
    assert all(olen["min"] <= r.max_new_tokens <= olen["max"] for r in reqs)
    assert all(0.0 <= r.arrival_time < 30.0 for r in reqs)
    assert all(a.arrival_time <= b.arrival_time for a, b in zip(reqs, reqs[1:]))
    assert all(len(r.prompt) + r.max_new_tokens <= STAR["n_positions"] for r in reqs)
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < STAR["vocab_size"]
               for r in reqs)


def test_requests_every_seed_same_work_other_order():
    a = requests.make(CODE, STAR, 1, 30.0)
    b = requests.make(CODE, STAR, 2, 30.0)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == sorted(r.max_new_tokens for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    med = np.median([len(r.prompt) for r in a])
    assert abs(med - CODE["prompt_len"]["median"]) < 0.05 * CODE["prompt_len"]["median"]


def test_requests_gaps_are_an_exponentials_quantiles_at_the_rate():
    gaps = requests.arrival_gaps(192, CODE["rate_per_s"])
    assert gaps.mean() == pytest.approx(1.0 / CODE["rate_per_s"])
    assert np.std(gaps) == pytest.approx(gaps.mean(), rel=0.05)  # exponential: sd = mean


def test_lm_batches_rows_differ_and_repeat_by_seed():
    traffic = {"batch": 4, "seq_len": 32, "rows": 64}
    cfg = {"vocab_size": 256}
    a, b = lm_batches.make(traffic, cfg, 9), lm_batches.make(traffic, cfg, 9)
    assert np.array_equal(a["inputs"], b["inputs"])
    assert np.array_equal(a["inputs"][:, 1:], a["targets"][:, :-1])
    assert len({row.tobytes() for row in a["inputs"]}) == 64
    assert not np.array_equal(a["inputs"], lm_batches.make(traffic, cfg, 10)["inputs"])


def test_counts_by_hand():
    # GPT-2 medium: per layer 4 d^2 (q, k, v, o) + 8 d^2 (MLP) = 12 x 1024^2.
    d, v = 1024, 50257
    assert counts.matmul_params(GPT2) == 24 * 12 * d * d + d * v
    assert counts.param_count(GPT2) == GPT2["parameters"] == 406336593
    tokens = 8 * 1024
    by_hand = 6 * tokens * (24 * 12 * d * d + d * v) + 6 * 24 * 8 * 1024 * 1024 * d
    assert counts.train_step_flops(GPT2, 8, 1024) == by_hand
    assert 18.5e12 < by_hand < 18.7e12
    # StarCoderBase-1B, multi-query: k and v project to one head of 128.
    d, v = 2048, 49152
    per_layer = 2 * d * d + 2 * d * 128 + 2 * d * 8192
    assert counts.matmul_params(STAR) == 24 * per_layer + d * v
    assert counts.param_count(STAR) == STAR["parameters"] == 1237919744
    # A decode step: bf16 weights once + 64 slots x 8192 rows x (k + v) x 128 x bf16.
    cache = 2 * 24 * 64 * 8192 * 128 * 2
    assert cache == 6442450944
    assert counts.decode_step_bytes(STAR, 64, 8192) == 2 * (24 * per_layer + d * v) + cache


def test_percentile_is_numpys():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0, 8.0]
    for q in (50, 90, 95, 100):
        assert percentile(xs, q) == pytest.approx(float(np.percentile(xs, q)))


def test_requests_small_shuffle_block_keeps_the_canonical_load_profile():
    local = {**CODE, "shuffle_block": 4}
    a = requests.make(local, STAR, 1, 30.0)
    b = requests.make(local, STAR, 2, 30.0)
    la, lb = [len(r.prompt) for r in a], [len(r.prompt) for r in b]
    assert la != lb and sorted(la) == sorted(lb)
    assert all(sorted(la[i:i + 4]) == sorted(lb[i:i + 4]) for i in range(0, len(la), 4))
    assert max(abs(x.arrival_time - y.arrival_time) for x, y in zip(a, b)) < 2.0
