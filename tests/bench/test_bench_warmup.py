"""The warm-up's neighbour programs: the same stage at the planner's
adjacent k_cold buckets and the decode-only stage of a mixed stage's
decode rows run once, with the served call's arguments."""
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

from benchlib.compiles import StageCalls  # noqa: E402


class Engine:
    """The engine's stage-program lookup, with programs that log calls."""

    def __init__(self):
        self.planner = SimpleNamespace(buckets=(0, 8, 16, 32, 48, 64))
        self._mixed_fns, self._paged_decode_fns = {}, {}
        self.ran = []
        self._invoke = lambda fn, *args: fn(*args)

    def _moe_caps(self, T, k):
        return T, k, 8

    def _program(self, kind, key):
        def fn(*args):
            self.ran.append((kind, key[0], len(args)))
            return np.zeros(1)
        return fn

    def _mixed_fn(self, k, ch, cc, cb, nc, sc, nb, mp, mpc, spec=False):
        key = (k, ch, cc, nc, sc, nb, mp, mpc, spec)
        return self._mixed_fns.setdefault(key, self._program("mixed", key))

    def _paged_decode_fn(self, k, ch, cc, cb, nb, mp):
        key = (k, ch, cc, nb, mp)
        return self._paged_decode_fns.setdefault(key,
                                                 self._program("decode", key))


def _args(n):
    return [np.zeros((2, 3), np.int32) for _ in range(n)]


def test_mixed_stage_warms_its_neighbours_once():
    eng = Engine()
    calls = StageCalls(eng)
    fn = eng._mixed_fn(48, *eng._moe_caps(32 + 256, 48), 1, 256, 32, 32, 4)
    eng._invoke(fn, *_args(10))
    assert eng.ran == [("mixed", 48, 10)]
    assert calls.shapes[fn][0].shape == (2, 3)
    calls.neighbours = True
    eng.ran.clear()
    eng._invoke(fn, *_args(10))
    assert eng.ran == [("mixed", 32, 10), ("mixed", 64, 10),
                       ("decode", 32, 6), ("decode", 48, 6),
                       ("decode", 64, 6), ("mixed", 48, 10)]
    # the decode-only twin takes the mixed call's own caps for its batch
    assert (48, 32, 48, 32, 32) in eng._paged_decode_fns
    eng.ran.clear()
    eng._invoke(fn, *_args(10))
    assert eng.ran == [("mixed", 48, 10)]


def test_decode_stage_warms_adjacent_buckets_only():
    eng = Engine()
    calls = StageCalls(eng)
    calls.neighbours = True
    fn = eng._paged_decode_fn(0, *eng._moe_caps(16, 0), 16, 8)
    eng._invoke(fn, *_args(6))
    assert eng.ran == [("decode", 8, 6), ("decode", 0, 6)]
