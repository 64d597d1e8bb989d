"""BENCHMARK.json resolves by name to files; a new cell is new files only;
a run without a TPU prints nothing and fails."""
import hashlib
import json
import os
import re
import subprocess
import sys

import pytest

from benchlib.cell import BENCH_DIR, ROOT, load_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _tree_digest(top):
    h = hashlib.sha256()
    for d, _, files in sorted(os.walk(top)):
        if "__pycache__" in d:
            continue
        for f in sorted(files):
            with open(os.path.join(d, f), "rb") as fh:
                h.update(f.encode() + fh.read())
    return h.hexdigest()


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = load_cell(cell)
    assert callable(c.kind().window)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(c.reader(m["name"]))


def test_names_and_files_follow_the_contract():
    b = _bench()
    assert b["paths"] == ["bench"]
    names = [c["name"] for c in b["configs"]] + \
        [w["name"] for w in b["workloads"]] + \
        [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in b["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["moves"] for m in b["per_layer"]} <= {
        m["name"] for m in b["end_to_end"]}


def _throwaway(tmp_path):
    """A new configuration, traffic mix, kind of traffic and metric, in
    files of their own, and a BENCHMARK.json that names them."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "kinds", "metrics"):
        (bench / sub).mkdir(parents=True)
    with open(os.path.join(BENCH_DIR, "configs", "mibench_t2.json")) as f:
        cfg = json.load(f)
    cfg.update(name="toy",
               kernels=[{"builder": "repro.apps.mibench:sha_mix"}],
               n_banks=[4], smul_lat=[3], t_mem=[2])
    (bench / "configs" / "toy.json").write_text(json.dumps(cfg))
    (bench / "kinds" / "toy_kind.py").write_text(
        "import importlib.util, os\n"
        "from benchlib.cell import BENCH_DIR\n"
        "spec = importlib.util.spec_from_file_location(\n"
        "    'toy_sweep', os.path.join(BENCH_DIR, 'kinds', 'sweep.py'))\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(mod)\n"
        "window = mod.window\n")
    (bench / "traffic" / "toy_loop.json").write_text(json.dumps({
        "kind": "toy_kind", "backend": "xla", "campaigns_premade": 2,
        "check_sample": 1, "reduce": {"kind": "pareto", "axes": [
            "latency_cc", "energy_pj"], "max_points": 8}}))
    (bench / "metrics" / "toy.jobs.py").write_text(
        "def read(ctx):\n    return float(ctx['jobs'])\n")
    b = _bench()
    b["configs"].append({"name": "toy", "source": "test",
                         "file": "bench/configs/toy.json", "reduced": [],
                         "why": "test"})
    b["workloads"].append({"name": "toy.loop", "config": "toy",
                           "traffic": "toy_loop", "chips": 1, "why": "t"})
    b["per_layer"].append({"name": "toy.jobs", "unit": "campaigns",
                           "better": "higher", "source": "program_counter",
                           "layer": "dse", "moves": "points_per_s",
                           "workloads": ["toy.loop"]})
    b["end_to_end"][0]["workloads"].append("toy.loop")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    return str(tmp_path / "BENCHMARK.json"), str(bench)


def test_a_new_cell_is_new_files_only(tmp_path, tiny):
    before = _tree_digest(BENCH_DIR)
    path, bench = _throwaway(tmp_path)
    cell = load_cell("toy.loop", benchmark=path, bench_dir=bench)
    assert [m["name"] for m in cell.per_layer] == ["toy.jobs"]
    assert cell.reader("toy.jobs")({"jobs": 3}) == 3.0
    assert callable(cell.kind().window)
    out = tiny.run(cell)
    assert out["correct"] and set(out["metrics"]) == {"points_per_s",
                                                      "setup_s"}
    assert _tree_digest(BENCH_DIR) == before


def test_no_tpu_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "mibench_t2.sweep", "--seed", str(2 ** 33 + 1),
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "TPU" in p.stderr
