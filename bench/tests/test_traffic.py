"""The generator gives every seed the same work in another order."""

import itertools
from collections import Counter

import pytest

from conftest import ROOT


def _take(mix, seed, n, window_s=51.0):
    from bench import traffic
    return list(itertools.islice(
        traffic.schedule(mix, seed, 1000, window_s), n))


def _lengths(plans):
    return (Counter(len(p.prompt) for p in plans),
            Counter(p.max_new for p in plans))


def test_backlog_same_work_for_every_seed():
    from bench import traffic

    mix = traffic.load(ROOT / "bench" / "traffic" / "offline-decode.json")
    k = mix["block"]
    a, b = _take(mix, 1, 3 * k), _take(mix, 2**31 + 77, 3 * k)
    for blk in range(3):
        assert (_lengths(a[blk * k:(blk + 1) * k])
                == _lengths(b[blk * k:(blk + 1) * k]))
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    assert all(p.due_s == 0 for p in a)


@pytest.mark.parametrize("window_s", [20.0, 51.0])
def test_window_holds_the_same_requests_for_every_seed(window_s):
    """Open loop: the requests due inside the window are the same multiset
    of lengths and of gaps for every seed, the first due at its open."""
    from bench import traffic

    mix = traffic.load(ROOT / "bench" / "traffic" / "chat.json")
    rate, pre = mix["arrivals"]["rate_per_s"], mix["preroll_s"]
    n = round(rate * window_s)
    seen = []
    for seed in (1, 2**31 + 77, 2**33 + 5):
        plans = _take(mix, seed, 200, window_s)
        inside = [p for p in plans if pre <= p.due_s < pre + window_s]
        assert len(inside) == n
        assert inside[0].due_s == pre
        gaps = sorted(round(b.due_s - a.due_s, 9)
                      for a, b in zip(inside, inside[1:]))
        seen.append((_lengths(inside), gaps, [len(p.prompt) for p in inside]))
        assert len([p for p in plans if p.due_s < pre]) == round(rate * pre)
    assert seen[0][0] == seen[1][0] == seen[2][0]
    # the gaps differ only in which one closes the window
    assert len(set(seen[0][1]) ^ set(seen[1][1])) <= 2
    assert seen[0][2] != seen[1][2]


def test_same_seed_same_requests():
    from bench import traffic

    mix = traffic.load(ROOT / "bench" / "traffic" / "chat.json")
    a, b = _take(mix, 5, 40), _take(mix, 5, 40)
    assert [(p.due_s, p.prompt, p.max_new) for p in a] == \
        [(p.due_s, p.prompt, p.max_new) for p in b]


def test_mix_that_overflows_the_cache_is_refused(tmp_path):
    import json
    from bench import traffic

    mix = json.loads((ROOT / "bench" / "traffic" / "chat.json").read_text())
    mix["engine"]["max_len"] = 1024
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(mix))
    with pytest.raises(ValueError, match="does not fit"):
        traffic.load(path)
