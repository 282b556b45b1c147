"""The generators: the same requests for the same seed, the clips honoured,
the same work at the same times for every seed."""
import itertools

import numpy as np
import pytest

from chipbench import traffic

MIXES = ["chat-closed16", "chat-poisson", "toy-closed", "toy-open"]
BIG_SEED = 2**31 + 12345


def _closed(mix, seed, n):
    return list(itertools.islice(traffic.closed_loop(mix, seed, 50272), n))


def _take(name, seed, seconds=45.0):
    mix = traffic.load_mix(name)
    if mix["kind"] == "closed_loop":
        return mix, _closed(mix, seed, mix["pool_size"])
    return mix, traffic.open_loop(mix, seed, 50272, seconds)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    _, a = _take(name, BIG_SEED)
    _, b = _take(name, BIG_SEED)
    assert a == b
    _, c = _take(name, BIG_SEED + 1)
    assert a != c


@pytest.mark.parametrize("name", MIXES)
def test_clips_honoured(name):
    mix, reqs = _take(name, 7)
    for r in reqs:
        assert mix["prompt_len"]["min"] <= len(r["prompt_ids"]) <= \
            mix["prompt_len"]["max"]
        assert mix["output_len"]["min"] <= r["max_new_tokens"] <= \
            mix["output_len"]["max"]
        assert min(r["prompt_ids"]) >= 4 and max(r["prompt_ids"]) < 50272


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work(name):
    def work(reqs):
        return [(r.get("due_s"), len(r["prompt_ids"]), r["max_new_tokens"])
                for r in reqs]
    _, a = _take(name, 1)
    _, b = _take(name, BIG_SEED)
    assert work(a) == work(b)
    assert [r["prompt_ids"] for r in a] != [r["prompt_ids"] for r in b]


def test_lognormal_median_is_near_the_stated_one():
    mix = traffic.load_mix("chat-closed16")
    pool = traffic.request_pool(mix, 4000)
    prompts = sorted(p for p, _ in pool)
    outputs = sorted(o for _, o in pool)
    assert 230 <= prompts[len(prompts) // 2] <= 285
    assert 58 <= outputs[len(outputs) // 2] <= 70
    assert prompts[0] == 16 and prompts[-1] == 1536     # both clips bite
    assert outputs[-1] == 256


def test_open_loop_schedule():
    mix = traffic.load_mix("chat-poisson")
    reqs = traffic.open_loop(mix, 3, 50272, 40.0)
    assert len(reqs) == round(mix["rate_per_s"] * 40.0)
    due = [r["due_s"] for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 40.0
    # another schedule is another sizes_seed, that is another mix
    other = traffic.open_loop(dict(mix, sizes_seed=1), 3, 50272, 40.0)
    assert [r["due_s"] for r in other] != due
    gaps = np.diff([0.0] + due)
    assert gaps.std() / gaps.mean() > 0.5       # Poisson-like, not a clock


def test_lm_batches():
    mix = traffic.load_mix("lm-b8")
    a = next(traffic.lm_batches(mix, BIG_SEED, 1024, 51200))
    b = next(traffic.lm_batches(mix, BIG_SEED, 1024, 51200))
    assert a["input_ids"].shape == (8, 1024) == a["labels"].shape
    assert (a["input_ids"] == b["input_ids"]).all()
    # the labels are the next tokens
    assert (a["input_ids"][:, 1:] == a["labels"][:, :-1]).all()
    assert a["input_ids"].max() < 51200 and a["input_ids"].min() >= 0
    gen = traffic.lm_batches(mix, 1, 1024, 51200)
    assert (next(gen)["input_ids"] != next(gen)["input_ids"]).any()


def test_unknown_kind_is_refused(tmp_path, monkeypatch):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "odd.json").write_text('{"kind": "swarm"}')
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    with pytest.raises(ValueError, match="unknown kind"):
        traffic.load_mix("odd")
