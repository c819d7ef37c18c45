"""``BENCHMARK.json`` and the files it names: the benchmark's contract
on names, units and sizes, every file present, and cells that a later
change adds as data files alone."""
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = harness.manifest(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_size():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    assert all(re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p) and ".." not in p
               for p in MANIFEST["paths"])
    rs = MANIFEST["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_lines():
    groups = ("configs", "workloads", "end_to_end", "per_layer")
    for entry in (e for g in groups for e in MANIFEST[g]):
        assert NAME.match(entry["name"]), entry["name"]
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["chips"] in (1, 4)
    for c in MANIFEST["configs"]:
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert _line(c["why"]) and _line(c["source"])
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MANIFEST["per_layer"]:
        assert _line(m["layer"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names)), group
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_each_metric_moves_what_its_cells_report():
    cells = [w["name"] for w in MANIFEST["workloads"]]
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in MANIFEST["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert harness._reported(moved, cell), (m["name"], cell)
    for cell in cells:
        c = harness.load_cell(cell, ROOT)
        assert len(c.end_to_end) >= 2 and c.per_layer, cell


def test_every_named_file_exists():
    for c in MANIFEST["configs"]:
        path = ROOT / c["file"]
        assert path.is_file() and c["file"].startswith(tuple(MANIFEST["paths"]))
        data = json.loads(path.read_text())
        assert data["reduced"] == c["reduced"] and data["source"] == c["source"]
        assert (ROOT / "bench" / "reference" / f"{data['reference']}.py").is_file()
    for w in MANIFEST["workloads"]:
        cell = harness.load_cell(w["name"], ROOT)
        assert (ROOT / "bench" / "drivers" / f"{cell.traffic['kind']}.py").is_file()
        assert cell.limits["numbers"], w["name"]
        assert all(v["limit"] is not None for v in cell.limits["numbers"].values())
    for m in MANIFEST["per_layer"]:
        assert callable(harness.reader(m["name"], ROOT))
    assert len({c["file"] for c in MANIFEST["configs"]}) == len(MANIFEST["configs"])


def _copy_checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def _snapshot(root):
    return {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}


def test_a_cell_of_new_data_files_loads(tmp_path):
    """A configuration, a traffic mix, a limits file and a per-layer
    metric, each a new file plus manifest entries; no file edited."""
    root = _copy_checkout(tmp_path)
    before = _snapshot(root)
    cfg = json.loads((root / "bench/configs/h2o-danube-1.8b.json").read_text())
    cfg.update(name="h2o-danube-1.8b.l4", reduced=["n_layers"])
    cfg["arch"]["n_layers"] = 4
    (root / "bench/configs/h2o-danube-1.8b.l4.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/serve-b4-p1024-g64.json").write_text(json.dumps(
        {"kind": "serve", "batch": 4, "prompt_len": 1024, "gen_len": 64, "trace_rounds": 1,
         "check": {"requests": 4, "ref_rows": 4}}))
    (root / "bench/limits/danube-l4-mid.json").write_text(json.dumps(
        {"numbers": {"served_logit_gap": {"limit": 1.0}}}))
    (root / "bench/metrics/rounds_traced.py").write_text(
        "def read(run):\n    return float(run.stats['rounds'])\n")
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": cfg["name"], "source": cfg["source"], "reduced": ["n_layers"],
                         "file": "bench/configs/h2o-danube-1.8b.l4.json", "why": "4 layers"})
    m["workloads"].append({"name": "danube-l4-mid", "config": cfg["name"], "chips": 1,
                           "traffic": "serve-b4-p1024-g64", "why": "mid-length prompts"})
    m["per_layer"].append({"name": "rounds_traced", "unit": "rounds", "better": "higher",
                           "source": "host_clock", "layer": "bench", "moves": "ttft_p50_ms",
                           "workloads": ["danube-l4-mid"]})
    for e in m["end_to_end"]:
        if "danube-serve-long" in e.get("workloads", ()):
            e["workloads"].append("danube-l4-mid")
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    cell = harness.load_cell("danube-l4-mid", root)
    assert cell.arch["n_layers"] == 4 and cell.traffic["prompt_len"] == 1024
    assert [p["name"] for p in cell.per_layer][-1] == "rounds_traced"
    assert harness.reader("rounds_traced", root)(type("R", (), {"stats": {"rounds": 3}})) == 3.0
    assert {e["name"] for e in cell.end_to_end} >= {"ttft_p50_ms", "setup_s"}
    after = _snapshot(root)
    assert all(after[p] == before[p] for p in before)     # nothing edited under bench/


def test_without_a_tpu_run_exits_nonzero_and_prints_no_result(tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(tmp_path),
           "TMPDIR": str(tmp_path)}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "danube-serve-chat",
                        "--seed", str(2 ** 33 + 5), "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{") for line in p.stdout.splitlines())
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_limits_name_the_drivers_numbers(cell):
    c = harness.load_cell(cell, ROOT)
    want = {"serve": {"served_logit_gap"},
            "train": {"first_loss_gap", "grad_norm_gap", "change_norm_gap"}}[c.traffic["kind"]]
    assert set(c.limits["numbers"]) == want
