"""The host merge of reduced candidate sets (``merge_reduced``,
``fold_segments``) against the composition it replaced.

The production merge sorts the pooled candidates once and scans them.
Here it is held, bit for bit on all eight fields and their dtypes, to
the plain composition: pool the parts, drop repeated flat indices per
segment in a Python loop (first occurrence in part order stays), reduce
with ``reduce_oracle`` and carry the parts' ``clipped``.  A second group
checks that no production path calls ``reduce_oracle`` and that the
``pareto.candidates_merged`` counter counts the pooled candidates.
"""
import numpy as np
import pytest

from repro import obs
from repro.analysis import pareto
from repro.analysis.pareto import (CANDIDATE_FIELDS, REDUCED_FIELDS,
                                   RESULT_FIELDS, ParetoFront,
                                   ReducedResult, TopK, fold_segments,
                                   merge_reduced, reduce_oracle)
from repro.apps import mibench
from repro.core import dse
from repro.core.hwconfig import TOPOLOGIES
from repro.core.program import bucket_programs
from repro.service import ResumableSweepRunner

MAX_STEPS = 256

SPEC_KINDS = {
    "topk-latency_cc": lambda k: TopK("latency_cc", k),
    "topk-energy_pj": lambda k: TopK("energy_pj", k),
    "topk-power_mw": lambda k: TopK("power_mw", k),
    "topk-edp": lambda k: TopK("edp", k),
    "pareto-latency_cc-energy_pj":
        lambda k: ParetoFront(("latency_cc", "energy_pj"), k),
    "pareto-energy_pj-power_mw":
        lambda k: ParetoFront(("energy_pj", "power_mw"), k),
    "pareto-edp-latency_cc":
        lambda k: ParetoFront(("edp", "latency_cc"), k),
}


# ---------------------------------------------------------------------------
# The composition the production merge replaced
# ---------------------------------------------------------------------------

def _merge_by_oracle(spec, parts):
    parts = [p for p in parts if p is not None]
    if len(parts) == 1:
        return ReducedResult(*(np.asarray(x) for x in parts[0]))
    G = int(np.asarray(parts[0].count).shape[0])
    cat = {f: np.concatenate(
        [np.asarray(getattr(p, f)) for p in parts], axis=1)
        for f in CANDIDATE_FIELDS}
    n = cat["indices"].shape[1]
    lane = cat["indices"].astype(np.int64)
    for g in range(G):
        seen = set()
        for j in range(n):
            ix = lane[g, j]
            if ix < 0:
                continue
            if ix in seen:
                lane[g, j] = -1
            else:
                seen.add(ix)
    prog = np.repeat(np.arange(G), n)
    fields = tuple(cat[f].reshape(-1) for f in RESULT_FIELDS)
    red = reduce_oracle(spec, fields, prog, lane.reshape(-1), G)
    carried = np.sum([np.asarray(p.clipped) for p in parts], axis=0)
    return red._replace(clipped=(red.clipped + carried).astype(np.int32))


def _fold_by_oracle(spec, part, seg_of, n_out):
    part = ReducedResult(*(np.asarray(x) for x in part))
    seg = np.asarray(seg_of, dtype=np.int64)
    K = part.indices.shape[1]
    fields = tuple(getattr(part, f).reshape(-1) for f in RESULT_FIELDS)
    red = reduce_oracle(spec, fields, np.repeat(seg, K),
                        part.indices.reshape(-1), n_out)
    carried = np.zeros((n_out,), np.int64)
    np.add.at(carried, seg, part.clipped.astype(np.int64))
    return red._replace(clipped=(red.clipped + carried).astype(np.int32))


def _assert_bit_identical(want, got):
    for f in REDUCED_FIELDS:
        a, b = np.asarray(getattr(want, f)), np.asarray(getattr(got, f))
        assert a.dtype == b.dtype and a.shape == b.shape, f
        assert a.tobytes() == b.tobytes(), f"{f}: {a} != {b}"


# ---------------------------------------------------------------------------
# Candidate sets
# ---------------------------------------------------------------------------

def _lanes(rng, G, n_lanes, special=False):
    """Each segment's candidate lanes and their values, from small pools:
    ties on every axis and identical points under different indices."""
    B = G * n_lanes
    energy = rng.integers(0, 8, B) * 0.5
    if special:
        pick = rng.random(B)
        energy = np.where(pick < 0.1, np.nan, energy)
        energy = np.where((pick >= 0.1) & (pick < 0.2), np.inf, energy)
        energy = np.where((pick >= 0.2) & (pick < 0.3), -0.0, energy)
    return (rng.integers(1, 10, B).astype(np.int32),
            energy.astype(np.float32),
            (rng.integers(1, 5, B) * 0.25).astype(np.float32),
            rng.integers(-5, 5, B).astype(np.int32),
            rng.integers(1, 99, B).astype(np.int32))


def _parts(rng, G, n_parts, K, *, n_lanes=24, special=False,
           empty_row=True):
    """``n_parts`` candidate sets of ``(G, K)`` over one grid: each row
    a random subset of its segment's lanes at random slots, ``-1``
    between them; the second part (if any) repeats lanes of the first
    (a re-delivered partial); some rows carry ``clipped``; with
    ``empty_row`` segment 0 is empty in every part."""
    values = _lanes(rng, G, n_lanes, special)
    parts = []
    for p in range(n_parts):
        out = {f: np.zeros((G, K), pareto._OUT_DTYPES[f])
               for f in CANDIDATE_FIELDS}
        out["indices"][:] = -1
        count = np.zeros((G,), np.int32)
        for g in range(G):
            if empty_row and g == 0 and G > 1:
                continue
            m = int(rng.integers(0, min(K, n_lanes) + 1))
            if p == 1:       # a re-delivered partial: lanes of part 0
                lanes = parts[0].indices[g]
                lanes = lanes[lanes >= 0][:m]
            else:
                lanes = g * n_lanes + rng.choice(n_lanes, m, replace=False)
            slots = np.sort(rng.choice(K, lanes.size, replace=False))
            out["indices"][g, slots] = lanes
            for i, f in enumerate(RESULT_FIELDS):
                out[f][g, slots] = values[i][lanes]
            count[g] = lanes.size
        clipped = np.where(rng.random(G) < 0.3,
                           rng.integers(1, 4, G), 0).astype(np.int32)
        parts.append(ReducedResult(count=count, clipped=clipped, **out))
    return parts


# every pair of G in {1, 5, 64}, parts in {1, 2, 4, 8}, K in {1, 8, 512}
SHAPES = [(1, 1, 1), (5, 1, 8), (64, 1, 512),
          (1, 2, 8), (5, 2, 512), (64, 2, 1),
          (1, 4, 512), (5, 4, 1), (64, 4, 8),
          (1, 8, 1), (5, 8, 8), (64, 8, 512)]


@pytest.mark.parametrize("G,n_parts,K", SHAPES)
@pytest.mark.parametrize("kind", sorted(SPEC_KINDS))
def test_merge_matches_oracle_composition(kind, G, n_parts, K):
    spec = SPEC_KINDS[kind](K)
    rng = np.random.default_rng([G, n_parts, K, len(kind)])
    parts = _parts(rng, G, n_parts, K)
    got = merge_reduced(spec, parts)
    _assert_bit_identical(_merge_by_oracle(spec, parts), got)
    # the same pool folded into fewer segments
    seg_of = rng.integers(0, 3, G)
    _assert_bit_identical(_fold_by_oracle(spec, got, seg_of, 4),
                          fold_segments(spec, got, seg_of, 4))


@pytest.mark.parametrize("kind", sorted(SPEC_KINDS))
def test_merge_matches_oracle_on_nan_inf_and_signed_zero(kind):
    """NaN neither dominates nor is dominated; +inf ties +inf; -0.0
    ties 0.0 and falls to the index."""
    spec = SPEC_KINDS[kind](8)
    rng = np.random.default_rng(7)
    for _ in range(20):
        parts = _parts(rng, 5, 3, 8, special=True)
        got = merge_reduced(spec, parts)
        _assert_bit_identical(_merge_by_oracle(spec, parts), got)
        seg_of = rng.integers(0, 2, 5)
        _assert_bit_identical(_fold_by_oracle(spec, got, seg_of, 2),
                              fold_segments(spec, got, seg_of, 2))


@pytest.mark.parametrize("kind", sorted(SPEC_KINDS))
def test_merge_of_empty_parts(kind):
    spec = SPEC_KINDS[kind](8)
    parts = [ReducedResult(**pareto.reduced_zeros(3, spec))
             for _ in range(3)]
    parts[1] = parts[1]._replace(clipped=np.array([0, 2, 0], np.int32))
    got = merge_reduced(spec, parts)
    _assert_bit_identical(_merge_by_oracle(spec, parts), got)
    assert got.count.tolist() == [0, 0, 0]
    assert got.clipped.tolist() == [0, 2, 0]
    _assert_bit_identical(_fold_by_oracle(spec, got, [1, 1, 0], 2),
                          fold_segments(spec, got, [1, 1, 0], 2))


def test_fold_keeps_duplicate_indices_of_its_rows():
    """``fold_segments`` pools rows without deduplicating: two rows that
    carry the same index both stay candidates, in row order."""
    spec = ParetoFront(("latency_cc", "energy_pj"), 8)
    rng = np.random.default_rng(11)
    part, = _parts(rng, 4, 1, 8, empty_row=False)
    dup = part._replace(**{f: np.concatenate([getattr(part, f)[:2]] * 2)
                           for f in REDUCED_FIELDS})
    got = fold_segments(spec, dup, [0, 1, 1, 0], 2)
    _assert_bit_identical(_fold_by_oracle(spec, dup, [0, 1, 1, 0], 2), got)


def _front(rng, g, m, block):
    """A Pareto front of ``m`` points of program ``g``: latency up,
    energy down."""
    lat = np.cumsum(rng.integers(1, 4, m)).astype(np.int32)
    en = (1e4 - np.cumsum(rng.random(m) + 0.1)).astype(np.float32)
    idx = (g * block + rng.choice(block, m, replace=False)).astype(np.int32)
    return idx, lat, en


def test_merge_of_mibench_shaped_buckets():
    """The MiBench campaign's merge: G = 5, K = 512, four length buckets
    ({4}, {2}, {1, 3}, {0}) placed as disjoint parts, with per-program
    fronts of 10, 400, 50, 50 and 50 points."""
    G, K, block = 5, 512, 1200
    spec = ParetoFront(("latency_cc", "energy_pj"), K)
    rng = np.random.default_rng(3141592653)
    sizes = [10, 400, 50, 50, 50]
    parts = []
    for bucket in ([4], [2], [1, 3], [0]):
        out = pareto.reduced_zeros(G, spec)
        for g in bucket:
            idx, lat, en = _front(rng, g, sizes[g], block)
            m = sizes[g]
            out["indices"][g, :m] = idx
            out["latency_cc"][g, :m] = lat
            out["energy_pj"][g, :m] = en
            out["power_mw"][g, :m] = en / lat
            out["checksum"][g, :m] = g
            out["steps_executed"][g, :m] = lat
            out["count"][g] = m
        parts.append(ReducedResult(**out))
    got = merge_reduced(spec, parts)
    _assert_bit_identical(_merge_by_oracle(spec, parts), got)
    assert got.count.tolist() == sizes
    assert got.clipped.tolist() == [0] * G


# ---------------------------------------------------------------------------
# No production path calls the reference; the counter counts the pool
# ---------------------------------------------------------------------------

@pytest.fixture
def no_oracle(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("a production path called reduce_oracle")
    monkeypatch.setattr(pareto, "reduce_oracle", refuse)


@pytest.fixture(scope="module")
def grid(profile):
    ks = [mibench.bitcnt(n_words=16), mibench.crc32(n_words=3)]
    hws = [TOPOLOGIES["baseline"](), TOPOLOGIES["c_interleaved"]()]
    return dict(programs=[k.program for k in ks], profile=profile,
                hw_configs=hws,
                mem_images=np.stack([k.mem_init for k in ks]),
                max_steps=MAX_STEPS)


def _merged():
    return obs.COUNTS["pareto.candidates_merged"]


def test_merge_and_fold_count_without_the_oracle(no_oracle):
    spec = ParetoFront(("latency_cc", "energy_pj"), 8)
    parts = _parts(np.random.default_rng(5), 5, 4, 8)
    pooled = sum(int((p.indices >= 0).sum()) for p in parts)
    n0 = _merged()
    got = merge_reduced(spec, parts)
    assert _merged() - n0 == pooled
    merge_reduced(spec, parts[:1])           # one part: nothing pooled
    assert _merged() - n0 == pooled
    fold_segments(spec, got, [0, 1, 0, 1, 1], 2)
    assert _merged() - n0 == pooled + int((got.indices >= 0).sum())


def test_sweep_and_stitch_run_without_the_oracle(grid, no_oracle):
    """A length-bucketed reduced sweep and a runner's stitch of several
    units, with ``reduce_oracle`` made to raise.  With ``k`` above the
    lanes of a program nothing is cut, so every lane is a candidate of
    exactly one part: the pooled count is the grid."""
    G = len(grid["programs"])
    H, D = len(grid["hw_configs"]), grid["mem_images"].shape[0]
    spec = TopK("energy_pj", k=H * D)
    assert bucket_programs(grid["programs"], 4).n_buckets >= 2
    n0 = _merged()
    res = dse.sweep(**grid, backend="xla", max_buckets=4, reduce=spec)
    assert res.count.tolist() == [H * D] * G
    assert _merged() - n0 == G * H * D

    runner = ResumableSweepRunner(
        programs=grid["programs"], profile=grid["profile"],
        hw_configs=grid["hw_configs"], mem_images=grid["mem_images"],
        unit_size=3, max_steps=MAX_STEPS, reduce=spec)
    red, _ = runner.run()
    n1 = _merged()
    again = runner.stitch()
    assert _merged() - n1 == G * H * D
    _assert_bit_identical(red, again)
