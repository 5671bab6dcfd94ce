"""``BENCHMARK.json`` keeps to its format, and the harness finds new cells,
configurations, traffic, kinds and metrics from new files alone."""
import json
import re
import shutil
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

MANIFEST = harness.load_manifest(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
FIXTURE = Path(__file__).parent / "data" / "jacobi2d5p-tiny.xplane.pb.gz"


def line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and not re.search(r"[\n\t]", text)


def test_top_level():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for p in MANIFEST["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    cmd = MANIFEST["command"]
    assert 1 <= len(cmd) <= 32 and all(line(w) for w in cmd)
    for word in cmd[1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in MANIFEST["paths"])


def test_run_seconds_fits_a_full_check():
    rs = MANIFEST["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_lines():
    entries = (MANIFEST["configs"] + MANIFEST["workloads"]
               + MANIFEST["end_to_end"] + MANIFEST["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names)), group
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for e in MANIFEST["configs"] + MANIFEST["workloads"]:
        assert line(e["why"])
    for m in MANIFEST["per_layer"]:
        assert line(m["layer"])


def test_configs():
    files = set()
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line(c["source"]) and c["source"].startswith("https://")
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"]
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        # each key named reduced differs from the published value, and
        # every other published key is run as published
        for key, value in body["published"].items():
            if key in body:
                assert (body[key] != value) == (key in c["reduced"]), key
        assert set(c["reduced"]) == set(body["reduced"])
        assert any(w["config"] == c["name"] for w in MANIFEST["workloads"])


def test_workloads():
    configs = {c["name"] for c in MANIFEST["configs"]}
    pairs = set()
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(MANIFEST["workloads"]) // 2)


def test_metrics():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    cells = {w["name"] for w in MANIFEST["workloads"]}
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert callable(harness.load_module(ROOT / "bench" / "metrics" / f"{m['name']}.py").read)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    # every cell reports setup_s, another end-to-end metric and a per-layer one
    for cell in cells:
        spec = harness.cell_spec(MANIFEST, cell)
        names = {m["name"] for m in spec.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and spec.per_layer


def test_files_under_paths_are_named_from_name_characters():
    for p in MANIFEST["paths"]:
        for f in (ROOT / p).rglob("*"):
            rel = f.relative_to(ROOT / p)
            if "__pycache__" in rel.parts:
                continue
            assert all(NAME.match(part) for part in rel.parts), rel


def test_peaks_are_keyed_by_device_kind():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        harness.peaks("cpu")


# -- new files, no edits --------------------------------------------------------

TOY_KIND = '''
from pathlib import Path
from bench.harness import Window

def run(cell):
    if cell.trace:
        cell.windows.append(Window(xplane=Path(cell.traffic["xplane"]), units=2))
    return dict(end_to_end={"sweep_s": 1.5, "setup_s": 2.5}, attempted=3,
                failed=0, checks=[("toy_gap", 0.0, cell.config["limit"])],
                memory_peak_bytes=123,
                layer={"toy": cell.config["toy"] * cell.traffic["scale"]})
'''
TOY_METRIC = '''
def read(ctx):
    return ctx.layer["toy"] if ctx.trace.launches else None
'''


def test_new_files_are_picked_up_without_edits(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "configs" / "toy-config.json").write_text(
        json.dumps({"name": "toy-config", "kind": "toy", "toy": 7, "limit": 1.0}))
    (tmp_path / "bench" / "traffic" / "toy-traffic.json").write_text(
        json.dumps({"scale": 3, "xplane": str(FIXTURE)}))
    (tmp_path / "bench" / "kinds" / "toy.py").write_text(TOY_KIND)
    (tmp_path / "bench" / "metrics" / "toy_share.py").write_text(TOY_METRIC)
    manifest["configs"].append({"name": "toy-config", "source": "https://example.org",
                                "file": "bench/configs/toy-config.json",
                                "reduced": [], "why": "toy"})
    manifest["workloads"].append({"name": "toy-cell", "config": "toy-config",
                                  "traffic": "toy-traffic", "chips": 1, "why": "toy"})
    manifest["per_layer"].append({"name": "toy_share", "unit": "%", "better": "higher",
                                  "source": "program_counter", "layer": "toy",
                                  "moves": "sweep_s", "workloads": ["toy-cell"]})
    spec = harness.cell_spec(manifest, "toy-cell", bench=tmp_path / "bench")
    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite", id=0)
    kw = dict(seed=1, seconds=1.0, t_start=0.0, devices=[chip], root=tmp_path)
    plain = harness.run_cell(spec, trace=False, **kw)
    assert plain["correct"] and plain["attempted"] == 3
    assert plain["metrics"] == {"sweep_s": {"value": 1.5, "unit": "s"},
                                "setup_s": {"value": 2.5, "unit": "s"}}
    assert list(plain)[-1] == "checks"
    traced = harness.run_cell(spec, trace=True, **kw)
    assert traced["metrics"] == {"toy_share": {"value": 21, "unit": "%"}}
    assert 0 < traced["device"]["busy_s"] < traced["device"]["window_s"]
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_a_reading_that_is_not_finite_fails_as_valid_json():
    kind = types.ModuleType("nan_kind")
    kind.run = lambda cell: dict(end_to_end={"sweep_s": 1.0, "setup_s": 1.0},
                                 attempted=1, failed=1, memory_peak_bytes=None,
                                 checks=[("max_rel_err", float("nan"), 1e-5)], layer={})
    spec = harness.CellSpec(workload={"name": "x"}, config={}, traffic={}, kind=kind,
                            end_to_end=MANIFEST["end_to_end"], per_layer=[])
    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite", id=0)
    res = harness.run_cell(spec, seed=0, seconds=1.0, trace=False, t_start=0.0,
                           devices=[chip])
    assert not res["correct"]
    assert json.loads(json.dumps(res, allow_nan=False))["checks"]["max_rel_err"]["value"] > 1e300
