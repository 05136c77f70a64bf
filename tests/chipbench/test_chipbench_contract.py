"""BENCHMARK.json, the files it names, and the command's refusals."""
import json
import os
import re
import subprocess
import sys
import types

import pytest

from harness import HERE, ROOT, NoChip, device_check, metrics_of, read_json

BENCH = read_json(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files_and_metrics(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert w["chips"] == 1
    assert os.path.isfile(os.path.join(HERE, "configs",
                                       f"{w['config']}.json"))
    traffic = read_json(os.path.join(HERE, "traffic",
                                     f"{w['traffic']}.json"))
    assert os.path.isfile(os.path.join(HERE, "entries",
                                       f"{traffic['entry']}.py"))
    e2e = [m["name"] for m in metrics_of(BENCH, cell, False)]
    layer = metrics_of(BENCH, cell, True)
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for m in layer:
        assert m["moves"] in e2e
    for name in e2e + [m["name"] for m in layer]:
        if name != "setup_s":
            assert os.path.isfile(os.path.join(HERE, "metrics",
                                               f"{name}.py")), name


def test_free_text_fits_one_line():
    texts = [c[k] for c in BENCH["configs"] for k in ("why", "source")]
    texts += [w["why"] for w in BENCH["workloads"]]
    texts += [m["layer"] for m in BENCH["per_layer"]]
    texts += BENCH["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_bounds_and_config_files():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for c in BENCH["configs"]:
        assert c["file"].startswith("chipbench/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))


def test_a_full_check_fits_its_time_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def _fake_jax(platform, kind, count=1):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    return types.SimpleNamespace(devices=lambda: [dev] * count)


def test_device_check_refuses_what_it_cannot_measure():
    peaks = read_json(os.path.join(HERE, "peaks.json"))
    assert device_check(_fake_jax("tpu", "TPU v5 lite"), 1, peaks) is \
        peaks["TPU v5 lite"]
    with pytest.raises(NoChip):
        device_check(_fake_jax("cpu", "cpu"), 1, peaks)
    with pytest.raises(NoChip):
        device_check(_fake_jax("tpu", "TPU v0 unknown"), 1, peaks)
    with pytest.raises(NoChip):
        device_check(_fake_jax("tpu", "TPU v5 lite"), 4, peaks)


def test_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "q2.slides",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
