"""``BENCHMARK.json`` keeps to the benchmark's contract, and everything a
cell needs is found by name, so that a configuration, a traffic mix or a
per-layer metric is added with new files and entries alone."""
import json
import math
import pathlib
import re
import shutil
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.roofline import bytes_per_iter, words_per_iter  # noqa: E402

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def test_top_level_keys_and_size():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(MAN["command"]) <= 32
    for p in MAN["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    assert any((ROOT / p / "run.py").is_file() for p in MAN["paths"])


def test_names_units_and_texts():
    named = MAN["configs"] + MAN["workloads"] + METRICS
    for e in named:
        assert NAME.match(e["name"]), e["name"]
    for group in (MAN["configs"], MAN["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    for m in METRICS:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for w in MAN["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert TEXT.match(w["why"])
    for c in MAN["configs"]:
        assert TEXT.match(c["source"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for m in MAN["per_layer"]:
        assert TEXT.match(m["layer"])
    for word in MAN["command"]:
        assert TEXT.match(word)


def test_entries_have_exactly_their_keys():
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_named_file_exists():
    configs = {c["name"]: c for c in MAN["configs"]}
    for c in MAN["configs"]:
        path = ROOT / c["file"]
        assert any(path.is_relative_to(ROOT / p) for p in MAN["paths"])
        cfg = json.loads(path.read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert (ROOT / "bench" / "references"
                / f"{cfg['operator']}.py").is_file()
        assert (ROOT / "bench" / "inputs" / f"{cfg['rhs']}.py").is_file()
    assert len({c["file"] for c in MAN["configs"]}) == len(configs)
    for w in MAN["workloads"]:
        assert w["config"] in configs
        mix = ROOT / "bench" / "traffic" / f"{w['traffic']}.json"
        loop = json.loads(mix.read_text())["loop"]
        assert (ROOT / "bench" / "loops" / f"{loop}.py").is_file()
    for m in METRICS:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    used = {w["config"] for w in MAN["workloads"]}
    assert used == set(configs)


def test_cells_report_what_their_metrics_need():
    cells = {w["name"] for w in MAN["workloads"]}
    pairs = {(w["config"], w["traffic"]) for w in MAN["workloads"]}
    assert len(pairs) == len(cells)
    for m in METRICS:
        assert set(m.get("workloads", cells)) <= cells
    for name in cells:
        spec = harness.resolve(name)
        e2e = {m["name"] for m in spec["e2e"]}
        assert "setup_s" in e2e and len(e2e) >= 2, name
        assert spec["per_layer"], name
    for m in MAN["per_layer"]:
        assert m["moves"] in {e["name"] for e in MAN["end_to_end"]}
        for name in m.get("workloads", cells):
            assert m["moves"] in {e["name"] for e in
                                  harness.resolve(name)["e2e"]}, (m, name)
    layers = {}
    for m in MAN["per_layer"]:
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_chips_bounds_and_run_length():
    chips = [w["chips"] for w in MAN["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 2)
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"]: m["bound"] for m in MAN["end_to_end"]}["setup_s"] \
        <= 0.25
    rs = MAN["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits the driver's 43,200 seconds
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("l", [1, 3, 5])
def test_roofline_bytes_are_6l_plus_7_words(l):
    n = 1000 * 1000
    assert words_per_iter(l, n) == (6 * l + 7) * n
    assert bytes_per_iter(l, n, 4) == (6 * l + 7) * n * 4
    assert bytes_per_iter(l, n, 2, lanes=8) == (6 * l + 7) * n * 2 * 8


def test_new_cell_needs_only_new_files_and_entries(tmp_path):
    """A temporary extra configuration (with a solver knob no committed
    configuration sets), traffic mix with a loop of its own, and
    per-layer metric, added as files plus entries, are found and run by
    the unchanged harness."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/poisson2d-1000.json").read_text())
    cfg.update(name="poisson2d-extra", grid=[16, 16],
               solver=dict(cfg["solver"], backend="ref", maxiter=100))
    (root / "bench/configs/poisson2d-extra.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/extra.json").write_text(json.dumps(
        {"loop": "twice", "batch": 2, "ring": 4}))
    (root / "bench/loops/twice.py").write_text(
        "SPANS = ('solve',)\n"
        "def setup(cell):\n    return 'ready'\n"
        "def step(cell, ring, k):\n"
        "    idx = [(2 * k + j) % len(ring) for j in range(2)]\n"
        "    return [(i, cell.solver.solve(ring[i])) for i in idx]\n"
        "def window(cell, ring, seconds):\n"
        "    out = step(cell, ring, 0)\n"
        "    return {'results': out, 'expected': 2, 'steps': 1,\n"
        "            'window_s': seconds}\n")
    (root / "bench/metrics/lanes_seen.extra.py").write_text(
        "def read(ctx):\n    return float(ctx.lanes)\n")
    man["configs"].append({"name": "poisson2d-extra", "source": "x",
                           "file": "bench/configs/poisson2d-extra.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "extra.twice", "config":
                             "poisson2d-extra", "traffic": "extra",
                             "chips": 1, "why": "test"})
    man["end_to_end"][0]["workloads"].append("extra.twice")
    man["per_layer"].append({"name": "lanes_seen.extra", "unit": "lanes",
                             "better": "higher", "source": "host_clock",
                             "layer": "test", "moves": "solve_ms",
                             "workloads": ["extra.twice"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    spec = harness.resolve("extra.twice", root)
    assert spec["cfg"]["grid"] == [16, 16]
    assert [m["name"] for m in spec["e2e"]] == ["solve_ms", "setup_s"]
    assert [m["name"] for m in spec["per_layer"]] == ["lanes_seen.extra"]
    cell = harness.Cell(spec["cfg"], spec["traffic"], root=root)
    assert cell.solver.backend == "ref" and cell.state == "ready"
    out = cell.step(cell.ring(2 ** 32 + 1), 1)
    assert [i for i, _ in out] == [2, 3]
    assert all(r.converged for _, r in out)
    win = harness.run_window(cell, cell.ring(3), 0.5)     # the loop's own
    assert [i for i, _ in win["results"]] == [0, 1] and win["steps"] == 1
    ctx = types.SimpleNamespace(lanes=cell.batch)
    assert harness.read_metrics(spec["per_layer"], ctx, root) == {
        "lanes_seen.extra": {"value": 2.0, "unit": "lanes"}}
    assert math.prod(spec["cfg"]["grid"]) == 16 * 16
