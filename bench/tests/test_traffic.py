"""The generator: the same seed gives the same traffic; other seeds
send the same prompts and gaps in another order."""
from bench import traffic


def test_same_seed_same_traffic():
    mix = traffic.load_mix("conv")
    a = traffic.generate(mix, 2 ** 33 + 5, 2.0)
    b = traffic.generate(mix, 2 ** 33 + 5, 2.0)
    assert a.window == b.window and a.head == b.head and a.warm == b.warm


def test_other_seeds_reorder_the_same_work():
    mix = traffic.load_mix("conv")
    a = traffic.generate(mix, 1, 3.0)
    b = traffic.generate(mix, 3_000_000_001, 3.0)
    assert a.window != b.window
    assert a.head == b.head and a.warm == b.warm
    assert sorted((p, c) for _, p, c in a.window) == \
        sorted((p, c) for _, p, c in b.window)
    gaps = lambda w: sorted(round(y[0] - x[0], 9) for x, y in zip(w, w[1:]))
    assert len(a.window) == round(mix["rate_per_s"] * 3.0)
    assert abs(sum(gaps(a.window)) - sum(gaps(b.window))) < 3.0


def test_lengths_and_judge_classes():
    mix = traffic.load_mix("conv")
    t = traffic.generate(mix, 9, 10.0)
    assert all(25 <= len(p) <= 2100 for _, p, _ in t.window)
    # head classes carry their curated row to the judge; the others an
    # id no row of the tier has, whether or not the tier is padded
    rows = {c: r for r, (c, _) in enumerate(t.head)}
    for _, _, c in t.window:
        for static_rows in (0, 1 << 20):
            j = t.judge_class(c, static_rows)
            if c in rows:
                assert j == rows[c]
            else:
                assert j >= max(static_rows, len(t.head))
