"""A whole run on the CPU: sound, under the precision control, and with
the timed path broken underneath.  ``correct`` must say which is which.

The cells run their own harness, data and comparison at a small size on
the program's XLA engine (the Pallas kernel only runs interpreted on a
CPU).  Each fault is planted in the program below the harness:

* ``state_unchanged`` -- the simulator step returns its state as it got
  it;
* ``half_left_out`` -- the upper half of the lanes of every engine call
  is masked out of the on-device reduction, so half of the grid never
  reaches the answer;
* ``answer_altered`` -- every lane's executed-step count is off by one
  where the engine hands it to the reduction.
"""
import pytest

CELLS = ["mibench_t2.sweep", "conv_t2.sweep", "mibench_t2.served"]


def _state_unchanged(monkeypatch):
    from repro.core import dse
    make = dse.make_exec_fn

    def broken(*a, **k):
        step = make(*a, **k)

        def same(instr, n_instrs, state, hw, live=None):
            return state, step(instr, n_instrs, state, hw, live=live)[1]
        return same
    monkeypatch.setattr(dse, "make_exec_fn", broken)


def _reducer_fault(monkeypatch, alter):
    from repro.analysis import pareto
    make = pareto.make_device_reducer

    def broken(spec, n_programs):
        red = make(spec, n_programs)

        def fn(fields, prog_idx, lane_idx):
            return red(*alter(tuple(fields), prog_idx, lane_idx))
        return fn
    monkeypatch.setattr(pareto, "make_device_reducer", broken)


def _half_left_out(monkeypatch):
    import jax.numpy as jnp

    def alter(fields, prog_idx, lane_idx):
        n = lane_idx.shape[0]
        upper = jnp.arange(n) >= n // 2
        return fields, prog_idx, jnp.where(upper, -1, lane_idx)
    _reducer_fault(monkeypatch, alter)


def _answer_altered(monkeypatch):
    def alter(fields, prog_idx, lane_idx):
        lat, en, pw, ck, st = fields
        return (lat, en, pw, ck, st + 1), prog_idx, lane_idx
    _reducer_fault(monkeypatch, alter)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_left_out": _half_left_out,
          "answer_altered": _answer_altered}


@pytest.fixture
def fresh_cores():
    """Compiled sweep cores are cached per shape: drop them around a
    planted fault so neither the fault nor a clean core leaks."""
    from repro.core import dse
    dse._xla_sweep_core.cache_clear()
    yield
    dse._xla_sweep_core.cache_clear()


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(tiny, name):
    out = tiny.run(tiny(name))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_precision_control_is_not_correct(tiny, name):
    from benchlib.drive import control_engine
    cell = tiny(name)
    out = tiny.run(cell, engine=control_engine(cell))
    assert not out["correct"]
    gap = out["checks"]["energy_rel_gap"]
    assert gap["value"] > 3 * gap["limit"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_planted_fault_is_not_correct(tiny, name, fault, monkeypatch,
                                      fresh_cores):
    FAULTS[fault](monkeypatch)
    out = tiny.run(tiny(name))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_lost_answer_is_not_correct(tiny, name):
    """Warm-up is answered; the window's first call raises, so one
    campaign or request never gets its answer."""
    from benchlib.drive import program_engine
    cell = tiny(name)
    engine, calls = program_engine(cell), []
    warm = (len(cell.config["kernels"]) if cell.traffic["kind"] == "served"
            else 1)

    def flaky(job):
        calls.append(job.index)
        if len(calls) == warm + 1:
            raise RuntimeError("planted: no answer")
        return engine(job)
    out = tiny.run(cell, engine=flaky)
    assert not out["correct"]
    assert out["checks"]["answers_lost"]["value"] >= 1
    assert out["failed"] >= 1
