"""Characterization (Fig. 1 red box): profiling micro-kernels + the fit."""
import os

import numpy as np
import pytest

from repro.core import isa
from repro.core.characterization import Profile, characterize
from repro.core.hwconfig import baseline
from repro.core.physical import DEFAULT_PHYS


def test_profile_latencies_match_openedgecgra(profile):
    """All logic/arith ops take 1 cc except SMUL (3 cc); memory ops expose
    the uncontended t_mem -- exactly the paper's Section 2 description."""
    for name in ("SADD", "SSUB", "SLL", "SRL", "SRA", "LAND", "LOR",
                 "LXOR", "SLT", "MV"):
        assert int(profile.lat[isa.OP[name]]) == 1, name
    assert int(profile.lat[isa.OP["SMUL"]]) == 3
    assert profile.t_mem == int(np.asarray(baseline().t_mem))


def test_profile_powers_positive_and_ordered(profile):
    """Fitted powers are physical: decode >= 0, SMUL hungrier than NOP,
    idle below active NOP power."""
    assert profile.p_flat > 0
    assert profile.p_dec[isa.OP["SMUL"]] > profile.p_dec[isa.OP["NOP"]]
    assert 0 < profile.p_idle < profile.p_dec[isa.OP["SMUL"]]
    assert (profile.p_dec[np.array(isa.ALU_OPS)] > 0).all()


def test_profile_source_energies(profile):
    """Operand-fetch energy: immediate is the reference (0 by convention);
    neighbour fetch must cost more than register fetch (longer wires)."""
    assert profile.e_src[1] == 0.0
    assert profile.e_src[3] > profile.e_src[2] > 0
    assert 0 < profile.mulzero < 1.0   # multiply-by-zero is cheaper


def test_profile_estimator_blind_to_physical_model(profile):
    """The fit only sees waveforms: fitted values are close to -- but not
    copies of -- the PhysicalModel (data-toggle power is folded in)."""
    phys = DEFAULT_PHYS
    fitted = profile.p_dec[isa.OP["SADD"]]
    truth = phys.p_dec[isa.OP["SADD"]]
    assert fitted != truth                      # not a parameter copy
    assert abs(fitted - truth) / truth < 0.6    # but physically anchored


def test_profile_save_load_roundtrip(tmp_path, profile):
    path = os.path.join(tmp_path, "prof.npz")
    profile.save(path)
    back = Profile.load(path)
    np.testing.assert_array_equal(profile.lat, back.lat)
    np.testing.assert_allclose(profile.p_dec, back.p_dec)
    assert back.t_mem == profile.t_mem
    assert back.t_clk_ns == profile.t_clk_ns


def test_characterize_is_deterministic(profile):
    """Profiling kernels use a fixed data pattern: the fit is reproducible."""
    again = characterize()
    np.testing.assert_allclose(profile.p_dec, again.p_dec, rtol=1e-6)
    np.testing.assert_array_equal(profile.lat, again.lat)


def test_default_profile_serves_what_the_committed_code_builds(
        monkeypatch, tmp_path, profile):
    """No profile file outlives the code that built it: after a change to
    the characterization, ``default_profile`` serves the new fit, and it
    reads and writes no file on the way (here: none appears in a fresh
    working directory or TMPDIR)."""
    import dataclasses

    from repro.core import characterization as ch
    changed = dataclasses.replace(profile, p_idle=profile.p_idle * 2.0)
    monkeypatch.setattr(ch, "characterize", lambda: changed)
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    ch.default_profile.cache_clear()
    try:
        assert ch.default_profile() is changed
        assert ch.default_profile() is changed       # once per process
    finally:
        ch.default_profile.cache_clear()
    assert list(tmp_path.iterdir()) == []
