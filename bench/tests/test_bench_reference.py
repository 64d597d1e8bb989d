"""The plain reference against the program, and the comparison's rules."""
import numpy as np
import pytest

from benchlib import compare, reference, workload
from benchlib.cell import load_cell
from benchlib.drive import reference_answer, reference_lanes

SEED = 2 ** 33 + 17


@pytest.mark.parametrize("kernels", [
    ("repro.apps.mibench:bitcnt", "repro.apps.mibench:crc32",
     "repro.apps.mibench:dijkstra_relax"),
    ("repro.apps.conv:conv_op",)])
def test_reference_agrees_with_the_xla_engine(kernels, tmp_path,
                                               monkeypatch):
    """A second witness: the program's XLA engine on the CPU and the
    reference agree on every lane, discrete fields exactly."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    from repro.core import dse
    cfg = load_cell("mibench_t2.sweep").config
    if "conv" in kernels[0]:
        cfg = load_cell("conv_t2.sweep").config
    cfg["kernels"] = [{"builder": k} for k in kernels]
    cfg["n_banks"], cfg["smul_lat"] = [2, 256], [1, 3]
    hws = workload.hw_grid(cfg)
    job = workload.campaign(cfg, SEED, 0, hws)
    res = dse.sweep(programs=job.programs,
                    profile=workload.make_profile(cfg),
                    hw_configs=workload.make_hw(hws), mem_images=job.images,
                    max_steps=cfg["max_steps"], mem_size=cfg["mem_size"],
                    backend="xla")
    lanes = reference_lanes(cfg, job)
    n = len(hws) * job.images.shape[0]
    for g, f in enumerate(lanes):
        sl = slice(g * n, (g + 1) * n)
        for k in ("latency_cc", "checksum", "steps_executed"):
            np.testing.assert_array_equal(np.asarray(getattr(res, k))[sl],
                                          f[k].astype(np.int64))
        for k in ("energy_pj", "power_mw"):
            np.testing.assert_allclose(np.asarray(getattr(res, k))[sl], f[k],
                                       rtol=cfg["limits"]["energy_rel_gap"])


def _lanes():
    lat = np.array([10, 10, 12, 12, 15, 20, 20])
    en = np.array([9.0, 9.0, 7.0, 8.0, 7.5, 3.0, 3.0 + 1e-9])
    return {"latency_cc": lat, "energy_pj": en, "power_mw": en / lat,
            "checksum": np.arange(7), "steps_executed": np.full(7, 5)}


def _answer(ref, pos, offset=100):
    row = {k: np.asarray(ref[k])[pos] for k in ref}
    row["indices"] = np.asarray(pos) + offset
    row["clipped"] = 0
    return row


def test_pareto_front_keeps_duplicates_and_orders():
    ref = _lanes()
    assert list(reference.pareto_front(ref["latency_cc"],
                                       ref["energy_pj"])) == [0, 1, 2, 5]


def test_judge_accepts_the_reference_answer():
    ref = _lanes()
    r = compare.judge_front(_answer(ref, [0, 1, 2, 5]), ref, 100, 1e-6)
    assert r == {"wrong_lanes": 0, "front_errors": 0, "energy_rel_gap": 0.0}
    # lane 6 ties lane 5 to within rounding: undecided either way
    r = compare.judge_front(_answer(ref, [0, 1, 2, 5, 6]), ref, 100, 1e-6)
    assert r["front_errors"] == 0


@pytest.mark.parametrize("pos,wrong,front", [
    ([0, 2, 5], 0, 1),              # duplicate lane 1 missing
    ([0, 1, 2, 3, 5], 0, 1),        # dominated lane 3 listed
    ([0, 1, 5], 0, 1),              # front point 2 missing
    ([0, 1, 2, 5, 5], 1, 0),        # an index repeated
])
def test_judge_counts_wrong_fronts(pos, wrong, front):
    ref = _lanes()
    r = compare.judge_front(_answer(ref, pos), ref, 100, 1e-6)
    assert (r["wrong_lanes"], r["front_errors"]) == (wrong, front)


def test_judge_counts_wrong_lanes_and_gaps():
    ref = _lanes()
    ans = _answer(ref, [0, 1, 2, 5])
    ans["latency_cc"] = ans["latency_cc"] + np.array([0, 1, 0, 0])
    ans["energy_pj"] = ans["energy_pj"] * np.array([1, 1, 1 + 3e-6, 1])
    ans["clipped"] = 2
    r = compare.judge_front(ans, ref, 100, 1e-6)
    assert r["wrong_lanes"] == 2
    assert r["energy_rel_gap"] == pytest.approx(3e-6)
    out = compare.judge_front(_answer(ref, [0, 1, 2, 5], offset=0), ref,
                              100, 1e-6)
    assert out["wrong_lanes"] == 4


def test_reference_answer_layout():
    ref = _lanes()
    ans = reference_answer([ref, ref], max_points=3)
    assert list(ans["indices"][1]) == [7, 8, 9]
    assert list(ans["clipped"]) == [1, 1]
