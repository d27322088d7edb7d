"""The traffic generator: the same seed gives the same requests, every seed the
same set of sizes, and the clips hold."""

import numpy as np


def chat(run):
    import os

    return run.load_json(os.path.join(os.path.dirname(run.__file__), "traffic", "chat_closed16.json"))


def test_deck_is_fixed_and_clipped(run):
    mod = run.load_module("drivers", "serve_closed")
    traffic = chat(run)
    deck = mod.deck(traffic)
    assert deck == mod.deck(traffic) and len(deck) == traffic["deck"]
    prompts, news = [p for p, _ in deck], [n for _, n in deck]
    assert min(prompts) >= 32 and max(prompts) <= 2048 and min(news) >= 16 and max(news) <= 384
    assert 240 <= float(np.median(prompts)) <= 272 and 90 <= float(np.median(news)) <= 102
    assert max(p + n for p, n in deck) <= 2048 + 384


def test_requests_follow_the_seed(run):
    mod = run.load_module("drivers", "serve_closed")
    traffic = chat(run)

    def take(seed, n=300):
        r = mod.Requests(traffic, 151936, seed)
        return [(ids.tolist(), new) for ids, new in (r.next() for _ in range(n))]

    n = traffic["deck"]
    a, b, c = take(2**31 + 9), take(2**31 + 9), take(4)
    assert a == b and a != c
    sizes = lambda reqs: [(len(ids), new) for ids, new in reqs]  # noqa: E731
    assert sizes(a) == sizes(c)  # the mix's own order (deck_order_seed): the seed draws the ids alone
    assert sizes(a)[:n] == sizes(a)[n : 2 * n] and sorted(sizes(a)[:n]) == sorted(mod.deck(traffic))
    assert max(max(ids) for ids, _ in a) < 151936
    traffic = {k: v for k, v in traffic.items() if k != "deck_order_seed"}  # without it the seed deals the order too
    a, c = take(2**31 + 9, n), take(4, n)
    assert sizes(a) != sizes(c) and sorted(sizes(a)) == sorted(sizes(c)) == sorted(mod.deck(traffic))


def test_warm_lengths_reach_every_width(run):
    mod = run.load_module("drivers", "serve_closed")
    lengths = mod.warm_prompt_lengths(chat(run), 16, 32)
    assert lengths == [32, 64, 128, 256, 512, 1024, 2048]


def test_train_batches_follow_the_seed(run, qwen2, tiny_cfg):
    import os

    mod = run.load_module("drivers", "train")
    traffic = run.load_json(os.path.join(os.path.dirname(__file__), "data", "traffic", "train_tiny.json"))

    def feed(seed):
        d = mod.Driver({"cfg": tiny_cfg, "traffic": traffic, "family": qwen2, "seed": seed})
        d.sharding = None
        return [np.asarray(d.next_batch()["input_ids"]) for _ in range(4)]

    a, b, c = feed(2**31 + 1), feed(2**31 + 1), feed(3)
    assert all(np.array_equal(x, y) for x, y in zip(a, b)) and not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[0], a[1]) and not np.array_equal(a[0][0], a[0][1])  # rows all differ


def test_percentile_interpolates_between_ranks(run):
    mod = run.load_module("drivers", "serve_closed")
    values = sorted(float(v) for v in np.random.default_rng(3).integers(0, 10_000, 60))
    for q in (50, 90, 95):
        assert mod.percentile(values, q) == float(np.percentile(values, q))
    assert mod.percentile([], 90) is None and mod.percentile([7.0], 90) == 7.0
    # one first token fewer at the window's edge moves it by a tenth of a gap of the tail, not a whole one
    assert abs(mod.percentile(values[1:], 90) - mod.percentile(values, 90)) <= 0.11 * (values[54] - values[53]) + 1e-9
