"""The cell command and the manifest: what the contract of the benchmark
asks of them, checked without a chip; and a cell of a new architecture
that joins with new files alone."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from benchmarks.chip import harness

ROOT = Path(__file__).resolve().parents[2]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CMD = ["--workload", MAN["workloads"][0]["name"], "--seed", "4000000000",
       "--seconds", "1", "--trace", "0"]


def run_command(cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *MAN["command"][1:], *CMD],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_to_measure_off_a_tpu():
    r = run_command(ROOT)
    assert r.returncode == 2, r.stderr
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in MAN["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = run_command(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_manifest_keys_and_names():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"][0] == "python3"
    assert 1 <= MAN["run_seconds"] <= 51
    cells = len(MAN["workloads"])
    # a full check of 24 cells fits its allowance
    assert (2 + 14 * 24) * (MAN["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in MAN[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) <= cells // 2 or \
        sum(w["chips"] == 4 for w in MAN["workloads"]) <= 1
    assert len(json.dumps(MAN)) < 64 * 1024


def test_every_entry_has_its_files():
    for c in MAN["configs"]:
        path = ROOT / c["file"]
        assert any(c["file"].startswith(p + "/") for p in MAN["paths"])
        conf = json.loads(path.read_text())
        assert conf["name"] == c["name"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert conf["limits"], f"{c['name']} compares nothing"
        assert (ROOT / "benchmarks/chip/arch" / f"{conf['arch']}.py") \
            .exists()
    for w in MAN["workloads"]:
        assert (ROOT / "benchmarks/chip/traffic" / f"{w['traffic']}.json") \
            .exists()
        assert w["chips"] in (1, 4)
        assert w["config"] in {c["name"] for c in MAN["configs"]}
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert (ROOT / "benchmarks/chip/metrics" / f"{m['name']}.py").exists()
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_every_cell_reports_what_it_must():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e[
        "setup_s"]
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    for w in MAN["workloads"]:
        own = [m for m in e2e.values() if w["name"] in
               m.get("workloads", [w["name"]])]
        assert len(own) >= 2
        layer = [m for m in MAN["per_layer"] if w["name"] in m["workloads"]]
        assert layer, w["name"]
    for m in MAN["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", [cell])


@pytest.mark.parametrize("w", MAN["workloads"], ids=lambda w: w["name"])
def test_why_is_one_short_line(w):
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


# detr_stem under another layout of its file: the transformer's sizes in a
# group of their own
NESTED = '''"""detr_stem with the transformer's sizes under "transformer"."""
from pathlib import Path

from benchmarks.chip import model

base = model.arch_module(Path(__file__).with_name("detr_stem.py"))
engine, make_params, to_f32 = base.engine, base.make_params, base.to_f32
reference, canvas = base.reference, base.canvas
output_shapes = base.output_shapes
flops_per_image, msda_calls = base.flops_per_image, base.msda_calls


def parse(raw):
    rest = {k: v for k, v in raw.items() if k not in ("transformer", "arch")}
    return base.parse({**rest, **raw["transformer"], "arch": "detr_stem"})
'''
TRANSFORMER = ("hidden_dim", "nheads", "enc_n_points", "dec_n_points",
               "enc_layers", "dec_layers", "dim_feedforward", "num_queries")


def _digests(root: Path) -> dict:
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_a_new_architecture_joins_with_files_alone(tmp_path, monkeypatch):
    """In a copy of the benchmark, one module under ``arch/``, one
    configuration file naming it, one traffic file and manifest entries
    make a cell that runs end to end (on the CPU, at tiny sizes) and comes
    out correct; no file of the copy but the manifest changes."""
    import repro  # noqa: F401  (the program, from this checkout's src)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in MAN["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)
    (tmp_path / "src").symlink_to(ROOT / "src")
    bench = tmp_path / "benchmarks/chip"

    (bench / "arch/detr_stem_nested.py").write_text(NESTED)
    tiny = json.loads((ROOT / "tests/bench_chip/data/tiny.json").read_text())
    conf = {k: v for k, v in tiny.items() if k not in TRANSFORMER}
    conf.update(name="tiny-nested", arch="detr_stem_nested",
                transformer={k: tiny[k] for k in TRANSFORMER}, reduced=[])
    (bench / "configs/tiny-nested.json").write_text(json.dumps(conf))
    (bench / "traffic/tiny-open.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 4.0, "schedule_seed": 5,
         "max_batch": 1, "long_side": 64,
         "aspects": [[4, 3, 0.5], [3, 4, 0.25], [1, 1, 0.25]]}))
    man = json.loads((tmp_path / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tiny-nested", "source": "test",
                           "file": "benchmarks/chip/configs/tiny-nested.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "tiny-nested.open",
                             "config": "tiny-nested", "traffic": "tiny-open",
                             "chips": 1, "why": "test"})
    for m in man["end_to_end"]:
        if m["name"] == "latency_p50_ms":
            m["workloads"].append("tiny-nested.open")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))

    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")
    cell = harness.load_cell("tiny-nested.open", tmp_path)
    assert Path(cell.model.arch.__file__) == bench / "arch/detr_stem_nested.py"
    r = harness.run_cell("tiny-nested.open", 2 ** 33 + 9, 2.0, False,
                         t_start=time.perf_counter(), root=tmp_path,
                         require_chip=False)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] == 8
    assert set(r["metrics"]) == {"latency_p50_ms", "setup_s"}
    after = _digests(tmp_path)
    assert {p for p in before if after.get(p) != before[p]} \
        == {Path("BENCHMARK.json")}
