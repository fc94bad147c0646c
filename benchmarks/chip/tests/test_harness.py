"""The harness on the CPU: every cell rehearsed at a tiny size, the
result line's contract, the clock check, the look for a chip, and a new
cell, mix and metric picked up from files alone."""
import json
import re
import subprocess
import sys

import pytest

from conftest import ROOT, Checkout

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _assert_contract(res, trace):
    assert list(res)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in res
    dev = res["device"]
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        assert key in dev
    if trace:
        assert dev["busy_s"] > 0 and dev["window_s"] > 0
        for key in ("device_ops", "idle_gaps"):
            assert len(res["breakdown"][key]) <= 10
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cpu_rehearsal_of_every_cell(checkout, cell, trace):
    res, out = checkout.run(cell, seed=2**40 + 3, trace=trace)
    _assert_contract(res, trace)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    # a CPU number is never written under a device metric's name
    assert res["device"]["platform"] == "cpu"
    assert res["metrics"] and all(k.startswith("cpu.")
                                  for k in res["metrics"])
    assert "setup:" in out


def test_clock_count_is_checked(checkout, monkeypatch):
    import repro.serve as serve

    orig = serve.serve_loop

    def extra_call(*args, clock=None, **kw):
        out = orig(*args, clock=clock, **kw)
        if clock is not None:
            clock()
        return out

    monkeypatch.setattr(serve, "serve_loop", extra_call)
    with pytest.raises(RuntimeError, match="2T\\+2"):
        checkout.run("serve-paper-steady")


def test_exits_nonzero_off_a_tpu():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/chip/run.py"), "--workload",
         "serve-paper-steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_unknown_device_kind_is_an_error():
    import run

    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    import jax

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "devices", lambda: [Dev()])
        with pytest.raises(SystemExit, match="not in peaks.json"):
            run.check_device(1, {"TPU v5 lite": {}})


def test_exits_nonzero_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    import shutil

    shutil.copytree(ROOT / "benchmarks/chip", tmp_path / "benchmarks/chip")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "serve-paper-steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_new_cell_mix_and_metric_from_files_alone(tmp_path):
    """A configuration, a traffic mix, a cell's parameters and a metric
    reader added as new files with new BENCHMARK.json entries run with
    no file edited."""
    co = Checkout(tmp_path)
    before = {p: p.read_bytes() for p in co.bench_dir.rglob("*")
              if p.is_file()}
    (co.bench_dir / "configs" / "tiny-12x3.json").write_text(json.dumps({
        "M": 12, "N": 3, "pc": [74.0, 16.0], "pe": 3.45, "Pe": 9600.0,
        "Pc": 120000.0, "V": 0.05, "assumed": {}, "reduced": []}))
    (co.bench_dir / "traffic" / "halfload.json").write_text(json.dumps({
        "path": "serve", "amax": 200, "warmup_slots": 2,
        "carbon_slots": 48, "trace_slots": 2}))
    (co.bench_dir / "cells" / "serve-tiny-half.json").write_text(
        json.dumps({"window_slots_per_s": 100}))
    (co.bench_dir / "metrics" / "backlog_share.serve.py").write_text(
        "def read(rec):\n"
        "    return float(rec['window_slots'])\n")
    co.bench["configs"].append({
        "name": "tiny-12x3", "source": "https://arxiv.org/abs/2209.02198",
        "file": "benchmarks/chip/configs/tiny-12x3.json", "reduced": [],
        "why": "test"})
    co.bench["workloads"].append({
        "name": "serve-tiny-half", "config": "tiny-12x3",
        "traffic": "halfload", "chips": 1, "why": "test"})
    co.bench["per_layer"].append({
        "name": "backlog_share.serve", "unit": "slots", "better": "lower",
        "source": "host_clock", "layer": "served loop",
        "moves": "decisions_per_s", "workloads": ["serve-tiny-half"]})
    # a split of a quantity that has a reader needs no file of its own
    co.bench["per_layer"].append({
        "name": "device_idle_share.tiny", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device",
        "moves": "decisions_per_s", "workloads": ["serve-tiny-half"]})
    for m in co.bench["end_to_end"]:
        if "workloads" in m and m["name"].startswith("decision"):
            m["workloads"].append("serve-tiny-half")
    co.write_bench()
    res, _ = co.run("serve-tiny-half")
    assert res["correct"] and "cpu.decision_p50_us" in res["metrics"]
    res, _ = co.run("serve-tiny-half", trace=1)
    assert "cpu.backlog_share.serve" in res["metrics"]
    assert "cpu.device_idle_share.tiny" in res["metrics"]
    for p, data in before.items():
        assert p.read_bytes() == data, p


def test_benchmark_json_keeps_the_contract():
    import run

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    layers = set()
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        layers.add(m["layer"])
        assert run.reader_path(m["name"]).exists(), m["name"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME_RE.match(entry["name"]), entry["name"]
            for text in (entry.get("why", "x"), entry.get("layer", "x")):
                assert 1 <= len(text) <= 200 and "\n" not in text
    cells = {w["name"]: w for w in bench["workloads"]}
    for w in cells.values():
        assert w["chips"] == 1
        assert (ROOT / "benchmarks/chip/traffic"
                / f"{w['traffic']}.json").exists()
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        for c in m.get("workloads", cells):
            assert c in cells
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024
