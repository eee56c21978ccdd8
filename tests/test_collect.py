"""Host collection: the slot-map path against the reference extraction.

``variants.collect`` packs every band slot once from the eid vector, takes
each pair set by one boolean compress and sorts it in place.  Its arrays
must equal what ``results.pairs_from_band`` (the Python-set reference)
gives, as strictly increasing uint64, for every variant and runner, for
padded and sparse bands, and across parts; the ``collect.slots`` and
``collect.duplicates`` counters account for the map and the dedup.
"""
import numpy as np
import pytest

from repro import api, obs
from repro.api import results as RES
from repro.core import entities as E
from repro.core import partition as P

N, R, WIN, NK = 260, 4, 6, 64


@pytest.fixture(scope="module")
def ents():
    return E.synth_entities(np.random.default_rng(11), N, n_keys=NK,
                            dup_frac=0.25)


def _packed(pairs) -> np.ndarray:
    return RES.pack_pair_set(set(pairs))


def _assert_strictly_increasing(a: np.ndarray):
    assert a.dtype == np.uint64
    assert np.all(a[1:] > a[:-1])


def _part(eid, mask, match=None):
    """A band part as the shard program leaves it (eids under ``ents``)."""
    mask = np.asarray(mask, bool)
    return {"ents": {"eid": np.asarray(eid, np.int32)}, "mask": mask,
            "match": mask if match is None else np.asarray(match, bool)}


def _run(ents, variant, runner_name):
    if runner_name == "vmap":
        runner = api.VmapRunner(R)
        cfg = api.ERConfig(variant=variant, runner="vmap", window=WIN,
                           num_shards=R, hops=R - 1)
        b = P.balanced_partition(np.asarray(ents["key"]), R)
    else:
        runner = api.ShardMapRunner()
        r = runner.shards
        cfg = api.ERConfig(variant=variant, runner="shard_map", window=WIN,
                           num_shards=r, hops=max(r - 1, 1))
        b = api.default_bounds(ents, cfg, r)
    return runner.run_raw(ents, b, cfg)


@pytest.mark.parametrize("variant", ["srp", "repsn", "jobsn"])
@pytest.mark.parametrize("runner_name", ["vmap", "shard_map"])
@pytest.mark.parametrize("field", ["mask", "match"])
def test_collect_equals_reference(ents, variant, runner_name, field):
    """Per part and unioned over parts, the slot-map arrays are the
    reference's pair sets, strictly increasing."""
    out = _run(ents, variant, runner_name)
    v = api.get_variant(variant)
    reference = set()
    for p in v.parts:
        ref = api.pairs_from_band(out[p], field)
        packed = api.packed_pairs_from_band(out[p], field)
        _assert_strictly_increasing(packed)
        np.testing.assert_array_equal(packed, _packed(ref))
        reference |= ref
    col = v.collect(out)
    got = col.blocked if field == "mask" else col.matched
    _assert_strictly_increasing(got)
    np.testing.assert_array_equal(got, _packed(reference))
    if field == "mask":
        assert got.size > 0


def test_all_false_band_gives_empty_sets():
    part = _part(np.arange(12).reshape(2, 6), np.zeros((2, 3, 6), bool))
    tracer = obs.Tracer()
    with obs.activate(tracer):
        blocked, matched = RES.packed_pair_sets_from_band(part)
    for a in (blocked, matched):
        assert a.dtype == np.uint64 and a.size == 0
    assert tracer.metrics.to_dict()["collect.slots"]["value"] == 0


def test_padding_beside_the_largest_eid():
    """-1 padding next to eid 2**31 - 1: the real pairs are packed exactly
    and no padding slot leaks into either set."""
    top = 2**31 - 1
    eid = np.array([[5, top, 7, -1, -1, -1],
                    [top, 0, -1, -1, -1, -1]])
    mask = np.zeros((2, 2, 6), bool)
    mask[0, 0, :2] = True        # (5, top), (top, 7)
    mask[0, 1, 0] = True         # (5, 7)
    mask[1, 0, 0] = True         # (0, top)
    match = np.zeros_like(mask)
    match[0, 0, 1] = True
    part = _part(eid, mask, match)
    blocked, matched = RES.packed_pair_sets_from_band(part)
    np.testing.assert_array_equal(
        blocked, _packed({(5, top), (7, top), (5, 7), (0, top)}))
    np.testing.assert_array_equal(matched, _packed({(7, top)}))
    assert api.packed_to_pair_set(blocked) == \
        api.pairs_from_band(part, "mask")


def test_trailing_padding_is_trimmed():
    """The map stops w-1 columns past the last selected one: trailing
    padding columns are never packed, and the sets equal the reference."""
    r, k, m, live = 3, 4, 40, 10
    rng = np.random.default_rng(5)
    eid = np.full((r, m), -1)
    eid[:, :live] = rng.permutation(r * live).reshape(r, live)
    mask = np.zeros((r, k, m), bool)
    for d in range(1, k + 1):
        mask[:, d - 1, :live - d] = rng.random((r, live - d)) < 0.7
    mask[0, 0, live - 2] = True           # the last selected column
    match = mask & (rng.random(mask.shape) < 0.3)
    part = _part(eid, mask, match)
    tracer = obs.Tracer()
    with obs.activate(tracer):
        blocked, matched = RES.packed_pair_sets_from_band(part)
    assert tracer.metrics.to_dict()["collect.slots"]["value"] == \
        r * k * (live - 1)
    np.testing.assert_array_equal(
        blocked, _packed(api.pairs_from_band(part, "mask")))
    np.testing.assert_array_equal(
        matched, _packed(api.pairs_from_band(part, "match")))


@pytest.mark.parametrize("m,k,d,i", [(4, 2, 2, 3),   # slot 5 of 4
                                     (2, 3, 3, 0)])  # offset wider than M
def test_slot_past_the_shard_end_raises(m, k, d, i):
    mask = np.zeros((1, k, m), bool)
    mask[0, d - 1, i] = True              # pairs slot i with slot i + d
    with pytest.raises(IndexError):
        RES.packed_pair_sets_from_band(_part(np.arange(m)[None], mask))


def _two_parts():
    """Two parts that both hold the pair (1, 2), blocked but unmatched."""
    main_mask = np.zeros((1, 2, 4), bool)
    main_mask[0, 0, :3] = True            # (1,2) (2,3) (3,4)
    bnd_mask = np.zeros((1, 2, 3), bool)
    bnd_mask[0, 1, 0] = True              # (1, 2) again
    return {"main": _part([[1, 2, 3, 4]], main_mask, 0 * main_mask),
            "boundary": _part([[2, 9, 1]], bnd_mask, 0 * bnd_mask)}


def test_pair_in_both_parts_comes_out_once():
    col = api.get_variant("jobsn").collect(_two_parts())
    np.testing.assert_array_equal(col.blocked,
                                  _packed({(1, 2), (2, 3), (3, 4)}))
    assert col.matched.size == 0


def test_single_part_is_returned_as_is():
    out = {"main": _two_parts()["main"]}
    main = RES.packed_pair_sets_from_part(out["main"])
    col = api.get_variant("repsn").collect(out)
    np.testing.assert_array_equal(col.blocked, main[0])
    assert RES.union_packed([main[0]]) is main[0]


def test_collect_counters_two_parts():
    tracer = obs.Tracer()
    with obs.activate(tracer):
        api.get_variant("jobsn").collect(_two_parts())
    counters = tracer.metrics.to_dict()
    assert counters["collect.duplicates"]["value"] == 1
    assert counters["collect.slots"]["value"] == 1 * 2 * 3 + 1 * 2 * 1


def test_collect_counters_in_a_traced_resolve(ents, monkeypatch):
    """On a small repsn resolve, ``collect.slots`` is the summed size of
    the slot maps built and ``collect.duplicates`` is 0."""
    built = []
    slot_map = RES._slot_map

    def spy(eid, k, c):
        s = slot_map(eid, k, c)
        built.append(s.size)
        return s

    monkeypatch.setattr(RES, "_slot_map", spy)
    tracer = obs.Tracer()
    with obs.activate(tracer):
        res = api.resolve(ents, api.ERConfig(variant="repsn", window=WIN,
                                             num_shards=R, hops=R - 1))
    counters = tracer.metrics.to_dict()
    assert len(res.blocking.pairs) > 0 and built
    assert counters["collect.slots"]["value"] == sum(built) > 0
    assert counters["collect.duplicates"]["value"] == 0
