"""Traffic is a function of the seed alone, and every seed asks for the
same work in another order."""
import json
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))

import traffic  # noqa: E402

MIXES = sorted((BENCH_DIR / "traffic").glob("*.json"))
BIG_SEED = 2**31 + 12345  # seeds may pass 32 signed bits


def _key(specs):
    return [(s.prompt.tolist(), s.max_new) for s in specs]


def test_same_seed_same_requests():
    for path in MIXES:
        mix = json.loads(path.read_text())
        a = traffic.generate(mix, BIG_SEED, 1000, 100)
        b = traffic.generate(mix, BIG_SEED, 1000, 100)
        assert _key(a) == _key(b), path.name
        c = traffic.generate(mix, BIG_SEED + 1, 1000, 100)
        assert _key(a) != _key(c), path.name


def test_every_block_holds_the_same_lengths():
    for path in MIXES:
        mix = json.loads(path.read_text())
        k = mix["block"]
        sets = []
        for seed in (1, 2, BIG_SEED):
            specs = traffic.generate(mix, seed, 1000, 2 * k)
            for blk in (specs[:k], specs[k:]):
                sets.append((sorted(len(s.prompt) for s in blk), sorted(s.max_new for s in blk)))
        assert all(s == sets[0] for s in sets), path.name
        lo, hi = mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]
        assert lo <= min(sets[0][0]) and max(sets[0][0]) <= hi

