"""The traffic generator: seeded, and every seed does the same work."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.harness import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["decode-long-16", "decode-fewlong-4"])
def test_same_seed_same_requests(name):
    a = traffic.generate(mix(name), 2**31 + 5, 64000)
    b = traffic.generate(mix(name), 2**31 + 5, 64000)
    assert len(a) == len(b) == mix(name)["sessions"]
    for x, y in zip(a, b):
        assert x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name,total", [("decode-long-16", 163840), ("decode-fewlong-4", 131072)])
def test_every_seed_has_the_same_lengths_and_total(name, total):
    lens = None
    for seed in (0, 1, 2**31 + 11, 2**40 + 3):
        got = [len(s.prompt) for s in traffic.generate(mix(name), seed, 131072)]
        assert sum(got) == total
        assert lens is None or got == lens
        lens = got


def test_seeds_change_the_tokens_not_the_sizes():
    a = traffic.generate(mix("decode-long-16"), 1, 131072)
    b = traffic.generate(mix("decode-long-16"), 2, 131072)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(x.prompt.max() < 131072 and x.prompt.min() >= 0 for x in a)


def test_unknown_loop_and_distribution_are_refused():
    with pytest.raises(ValueError):
        traffic.generate({**mix("decode-long-16"), "loop": "open"}, 0, 10)
    with pytest.raises(ValueError):
        traffic.generate({**mix("decode-long-16"), "loop": "../harness/traffic"}, 0, 10)
    with pytest.raises(ValueError):
        traffic.draw_lengths({"dist": "zipf"}, 3, np.random.default_rng(0))


@pytest.mark.parametrize("name", ["decode-long-16", "decode-fewlong-4"])
def test_each_mix_names_a_loop_module_with_the_whole_interface(name):
    loop = traffic.loop(mix(name)["loop"])
    for attr in ("generate", "server_sizes", "Drive"):
        assert hasattr(loop, attr), attr
    for hook in ("setup", "before_tick", "contexts", "due", "served"):
        assert callable(getattr(loop.Drive, hook, None)), hook


def test_a_closed_batch_server_holds_every_session_whole():
    reqs = traffic.generate(mix("decode-fewlong-4"), 7, 64000)
    sizes = traffic.loop("closed_batch").server_sizes(mix("decode-fewlong-4"), reqs, 128)
    assert sizes["max_batch"] == 4
    longest = max(len(r.prompt) + r.max_new for r in reqs)
    assert longest + 2 <= sizes["cache_len"] < longest + 2 + 128
    assert (sizes["num_pages"] - 1) * 128 >= sum(len(r.prompt) + r.max_new + 2 for r in reqs)
