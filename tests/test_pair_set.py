"""``results.PairSet``, the public pair set, against the frozenset of the
same (lo, hi) tuples:

  * ``len``, ``==`` both ways and ``hash`` agree (CPython's frozenset hash,
    computed over the packed array), the two are interchangeable as dict
    keys, and pickling round-trips
  * ``in`` gives the frozenset's answer for any hashable probe: reversed
    pairs, out-of-range and negative ints, numpy ints, floats, non-tuples
    and tuples of the wrong length
  * iteration yields Python-int tuples in sorted order
  * ``& | - ^`` between two ``PairSet``s stay ``PairSet``s; with a plain
    set on either side they give the frozenset's answer and type
  * the array is held read-only, without a copy, and the tracer's
    ``pairs.materialized`` counter counts only what is turned into tuples
  * CPython's two -1 remaps (tuple hash, frozenset hash) are reproduced
"""
import pickle

import numpy as np
import pytest

from repro import obs
from repro.api import results as RES
from repro.api.results import PairSet, pack_pairs, unpack_pairs

TOP = 2 ** 32 - 1
M64 = (1 << 64) - 1


def _packed(pairs) -> np.ndarray:
    a = np.asarray(list(pairs), np.int64).reshape(-1, 2)
    return np.unique(pack_pairs(a[:, 0], a[:, 1]))


def _random(n: int, top: int, seed: int) -> np.ndarray:
    a = np.random.default_rng(seed).integers(0, top, (n, 2), np.int64)
    a = a[a[:, 0] != a[:, 1]]
    return np.unique(pack_pairs(a[:, 0], a[:, 1]))


CASES = {
    "empty": np.empty((0,), np.uint64),
    "single": _packed([(3, 7)]),
    "random": _random(3000, 500, 0),
    "near_top": _packed([(0, TOP), (TOP - 1, TOP), (TOP - 2, TOP - 1),
                         (5, TOP - 3), (2 ** 31, 2 ** 31 + 1)]),
}


def _frozen(packed: np.ndarray) -> frozenset:
    lo, hi = unpack_pairs(packed)
    return frozenset(zip(lo.tolist(), hi.tolist()))


@pytest.fixture(params=sorted(CASES))
def case(request):
    packed = CASES[request.param].copy()
    return PairSet(packed), _frozen(packed), packed


def test_len_equality_and_hash(case):
    ps, fs, packed = case
    assert len(ps) == len(fs) == packed.size
    assert ps == fs and fs == ps and not ps != fs
    assert ps == set(fs) and set(fs) == ps
    assert ps == PairSet(packed.copy())
    assert hash(ps) == hash(fs)
    assert hash(RES.packed_to_frozenset(packed)) == \
        hash(frozenset(zip(*unpack_pairs(packed))))
    if len(fs):
        assert ps != fs - {min(fs)} and fs - {min(fs)} != ps
        assert ps != PairSet(packed[1:])


def test_membership_matches_frozenset(case):
    ps, fs, _ = case
    probes = [(3, 7), (7, 3), (0, TOP), (TOP, 0), (TOP - 1, TOP),
              (0, 2 ** 32), (2 ** 32, 2 ** 32 + 1), (-1, 3), (-2, -1),
              (3.0, 7), (3.5, 7), (np.int64(3), np.uint32(7)),
              (True, 7), "ab", 3, None, (), (3,), (3, 7, 9),
              frozenset({3, 7}), ((3, 7),), ("3", "7")]
    for p in sorted(fs)[:50]:
        probes += [p, p[::-1], (p[0], p[1] + 1), (np.uint32(p[0]), p[1]),
                   (p[0] + 2 ** 61 - 1, p[1])]
    for probe in probes:
        assert (probe in ps) == (probe in fs), probe
    for unhashable in ([3, 7], (3, [7])):
        with pytest.raises(TypeError):
            unhashable in fs
        with pytest.raises(TypeError):
            unhashable in ps


def test_iteration_is_sorted_python_ints(case):
    ps, fs, _ = case
    got = list(ps)
    assert got == sorted(fs)
    assert all(type(x) is tuple and type(x[0]) is int and type(x[1]) is int
               for x in got)
    assert list(ps) == got      # iterable again


def _other(fs: frozenset) -> frozenset:
    """Half of ``fs`` plus pairs it lacks."""
    return frozenset(sorted(fs)[::2]) | {(1, 2), (40, 41), (TOP - 5, TOP)}


OPS = {"and": lambda a, b: a & b, "or": lambda a, b: a | b,
       "sub": lambda a, b: a - b, "xor": lambda a, b: a ^ b}


@pytest.mark.parametrize("op", sorted(OPS))
def test_set_algebra(case, op):
    ps, fs, _ = case
    f = OPS[op]
    other = _other(fs)
    want = f(fs, other)
    both = f(ps, PairSet(_packed(other)))
    assert type(both) is PairSet and both == want
    assert hash(both) == hash(want)
    assert type(f(ps, other)) is frozenset and f(ps, other) == want
    assert type(f(ps, set(other))) is frozenset and f(ps, set(other)) == want
    assert type(f(set(other), ps)) is set and \
        f(set(other), ps) == f(set(other), fs)
    assert type(f(other, ps)) is frozenset and f(other, ps) == f(other, fs)
    assert f(ps, list(other)) == want


@pytest.mark.parametrize("name", ["single", "random", "near_top"])
def test_the_benchmark_fault_edit_plants_its_pair(name):
    ps, fs = PairSet(CASES[name].copy()), _frozen(CASES[name])
    lo, hi = min(ps)
    altered = (ps - {(lo, hi)}) | {(lo, hi + 1)}
    assert altered == (fs - {(lo, hi)}) | {(lo, hi + 1)}
    assert altered != ps and hash(altered) != hash(ps)


def test_pickle_dict_keys_and_read_only_array(case):
    ps, fs, packed = case
    back = pickle.loads(pickle.dumps(ps))
    assert type(back) is PairSet and back == ps and hash(back) == hash(ps)
    assert {fs: "f"}[ps] == "f" and {ps: "p"}[fs] == "p"
    assert len({fs, ps}) == 1
    assert ps.packed.flags.writeable is False
    assert np.shares_memory(ps.packed, packed) or packed.size == 0
    with pytest.raises(ValueError):
        ps.packed[:1] = 0
    assert RES.pack_pair_set(ps) is ps.packed


def test_materialized_counter_counts_only_tuples_made(case):
    ps, fs, _ = case
    tracer = obs.Tracer()
    with obs.activate(tracer):
        tracer.metrics.counter("pairs.materialized")
        len(ps), hash(ps), (3, 7) in ps, ps == PairSet(ps.packed), ps & ps
        count = lambda: \
            tracer.metrics.to_dict()["pairs.materialized"]["value"]
        assert count() == 0
        list(ps)
        assert count() == len(fs)
        ps | {(1, 2)}
        assert count() == 2 * len(fs)
    list(ps)                    # no tracer: nothing counted
    assert count() == 2 * len(fs)


def _rotr31(x: int) -> int:
    return ((x >> 31) | (x << 33)) & M64


def _tuple_hash_minus_one_pair() -> tuple:
    """(lo, hi) with eid hashes = themselves whose CPython tuple hash is
    the error code -1, remapped to 1546275796."""
    p1, p2, p5 = (int(RES._XXPRIME_1), int(RES._XXPRIME_2),
                  int(RES._XXPRIME_5))
    tail = int(RES._TUPLE2_LEN)
    r = ((M64 - tail) * pow(p1, -1, 1 << 64)) & M64
    for lo in range(1000):
        acc = (p5 + lo * p2) & M64
        acc = (((acc << 31) | (acc >> 33)) & M64) * p1 & M64
        hi = ((_rotr31(r) - acc) * pow(p2, -1, 1 << 64)) & M64
        if lo < hi < 2 ** 61 - 1:
            return lo, hi
    raise AssertionError("no pair found")


def test_tuple_hash_minus_one_case():
    lo, hi = _tuple_hash_minus_one_pair()
    assert hash((lo, hi)) == 1546275796
    h = RES._tuple_hashes(np.array([lo], np.uint64),
                          np.array([hi], np.uint64))
    assert int(h[0]) == 1546275796
    assert RES._frozenset_hash(RES._shuffled_xor(h), 1) == \
        hash(frozenset({(lo, hi)}))


def _unshuffle(y: int) -> int:
    """Inverse of CPython's ``_shuffle_bits``."""
    w = ((y * pow(3644798167, -1, 1 << 64)) & M64) ^ 89869747
    h = w
    for _ in range(5):
        h = w ^ ((h << 16) & M64)
    return h


def _shuffle(h: int) -> int:
    return (((h ^ 89869747) ^ (h << 16)) * 3644798167) & M64


def test_frozenset_hash_minus_one_case():
    """Two ints whose frozenset hash would be -1 (remapped to 590923713):
    the size term and dispersion are inverted to find them."""
    x = ((M64 - 907133923) * pow(69069, -1, 1 << 64)) & M64
    h = x
    for _ in range(7):
        h = x ^ (h >> 11) ^ (h >> 25)
    target = h ^ ((3 * 1927868237) & M64)
    for a in range(1, 1000):
        e = _unshuffle(target ^ _shuffle(a))
        signed = e - (1 << 64) if e >> 63 else e
        if abs(signed) < 2 ** 61 - 1 and signed not in (-1, a):
            break
    assert hash(frozenset({a, signed})) == 590923713
    hashes = np.array([a, e], np.uint64)
    assert RES._frozenset_hash(RES._shuffled_xor(hashes), 2) == 590923713
