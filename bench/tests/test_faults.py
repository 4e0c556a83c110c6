"""A run whose timed path is broken underneath comes out not correct.

Each test drives ``bench/run.py``'s ``main`` in this process on a rehearsal
fleet (kernels in interpret mode on the CPU, so the look for a chip is
passed over) with one fault planted in the program: the propagation
returns its input unchanged, half of the profiles are left out of the
analysis, or one propagated value is altered where it is produced.  The
cells run on one chip, so no exchange between chips can be left out."""
import json

import numpy as np
import pytest

from bench import run as bench_run


def run_cell(benchmark_file, capsys, workload="tiny-pelec.analyze"):
    rc = bench_run.main(["--workload", workload, "--seed", str(2**34 + 5),
                         "--seconds", "1", "--benchmark", str(benchmark_file),
                         "--device-interpret"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def env(monkeypatch):
    # the run points JAX's cache at the checkout; keep that out of this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(bench_run.ROOT / ".jax_cache"))


def test_sound_run_is_correct(benchmark_file, capsys, env):
    assert run_cell(benchmark_file, capsys)["correct"] is True


def test_propagation_returning_its_input(benchmark_file, capsys, env,
                                         monkeypatch):
    from repro.kernels.batch import DeviceAggregator

    monkeypatch.setattr(DeviceAggregator, "inclusive",
                        lambda self, cols: np.asarray(cols, np.float32))
    out = run_cell(benchmark_file, capsys)
    assert out["correct"] is False
    assert out["checks"]["pms_gap_u"]["value"] > out["checks"]["pms_gap_u"]["limit"]


@pytest.mark.parametrize("workload", ["tiny-pelec.analyze", "tiny-amg.analyze"])
def test_half_of_the_profiles_left_out(benchmark_file, capsys, env,
                                       monkeypatch, workload):
    from repro.core.aggregate import StreamingAggregator

    whole = StreamingAggregator.run
    monkeypatch.setattr(StreamingAggregator, "run", lambda self, paths: whole(
        self, paths[:len(paths) // 2]))
    out = run_cell(benchmark_file, capsys, workload)
    assert out["correct"] is False
    assert out["checks"]["pms_gap_u"]["value"] > out["checks"]["pms_gap_u"]["limit"]


def test_one_value_altered_where_produced(benchmark_file, capsys, env,
                                          monkeypatch):
    from repro.kernels.batch import DeviceAggregator

    inclusive = DeviceAggregator.inclusive

    def altered(self, cols):
        out = np.array(inclusive(self, cols))
        out.flat[np.argmax(out)] *= 1.001
        return out

    monkeypatch.setattr(DeviceAggregator, "inclusive", altered)
    out = run_cell(benchmark_file, capsys)
    assert out["correct"] is False
    assert out["checks"]["pms_gap_u"]["value"] > out["checks"]["pms_gap_u"]["limit"]
