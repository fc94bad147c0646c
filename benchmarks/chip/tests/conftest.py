"""Shared set-up of the benchmark's own tests, which run on the CPU:

    PYTHONPATH=src python -m pytest benchmarks/chip/tests

They are outside the repository's tier-1 `testpaths`.
"""
import copy
import io
import json
import os
import shutil
import sys
from contextlib import redirect_stdout
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src"), str(Path(__file__).parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

# Tiny sizes of each mix for runs on the CPU (the paper's own 5 x 5
# configuration is tiny already).
TINY_MIX = {"whatif": {"kinds": {"diurnal": 2, "bursty": 2,
                                 "heterogeneous-fleet": 2,
                                 "multi-region-uk": 2},
                       "T": 24, "Tc": 24},
            "steady": {"carbon_slots": 96, "trace_slots": 3,
                       "warmup_slots": 4}}
# Table I tiled to more task types and clouds at the paper's load
# factors: a size at which the greedy fill walks several chunks.
WIDE = {"M": 48, "N": 8, "Pe": 4000.0 * 48 / 5, "Pc": 30000.0 * 48 / 8}


def load_cfg(name):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in bench["configs"]}[name]
    return json.loads((ROOT / entry["file"]).read_text())


def cpu_device(chips, peaks):
    """Stands in for the harness's look for a chip."""
    import jax

    return jax.devices()[0]


class Checkout:
    """A copy of the benchmark (and a link to the program) in a
    temporary directory, laid out as the driver's checkout, with each
    configuration and mix cut to a tiny size."""

    def __init__(self, root: Path, tiny=True):
        self.root = root
        self.bench_dir = root / "benchmarks" / "chip"
        shutil.copytree(BENCH, self.bench_dir,
                        ignore=shutil.ignore_patterns("__pycache__",
                                                      "tests"))
        (root / "src").symlink_to(ROOT / "src")
        self.bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        self.write_bench()
        if tiny:
            for name, over in TINY_MIX.items():
                self.patch(self.bench_dir / "traffic" / f"{name}.json", over)

    @staticmethod
    def patch(path, over):
        data = json.loads(path.read_text())
        data.update(copy.deepcopy(over))
        path.write_text(json.dumps(data))

    def write_bench(self):
        (self.root / "BENCHMARK.json").write_text(json.dumps(self.bench))

    def module(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            f"bench_run_{abs(hash(self.root))}", self.bench_dir / "run.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def run(self, workload, seed=11, seconds=0.3, trace=0,
            device_check=cpu_device):
        """Runs one cell in this process; returns (result, stdout)."""
        buf = io.StringIO()
        with redirect_stdout(buf):
            self.module().main(
                ["--workload", workload, "--seed", str(seed),
                 "--seconds", str(seconds), "--trace", str(trace)],
                device_check=device_check)
        out = buf.getvalue()
        return json.loads(out.strip().splitlines()[-1]), out


@pytest.fixture
def checkout(tmp_path):
    return Checkout(tmp_path)
