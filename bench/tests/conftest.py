"""The benchmark's own tests run on the CPU: the rehearsal runs the kernels
in interpret mode, and nothing here needs a chip."""
import json
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"tiny-pelec": ROOT / "bench/tests/configs/tiny-pelec.json",
        "tiny-amg": ROOT / "bench/tests/configs/tiny-amg.json"}


def tiny_benchmark(tmp_path: Path) -> Path:
    """``BENCHMARK.json`` with the rehearsal fleets added as new
    configuration files and new cells, every other entry as it is."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name, src in TINY.items():
        conf = tmp_path / f"{name}.json"
        conf.write_text(src.read_text())
        spec["configs"].append({"name": name, "source": "rehearsal",
                                "file": str(conf), "reduced": [],
                                "why": "rehearsal"})
        spec["workloads"].append({"name": f"{name}.analyze", "config": name,
                                  "traffic": "analyze", "chips": 1,
                                  "why": "rehearsal"})
        for m in spec["per_layer"]:
            m.setdefault("workloads", []).append(f"{name}.analyze")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path


@pytest.fixture
def benchmark_file(tmp_path):
    return tiny_benchmark(tmp_path)
