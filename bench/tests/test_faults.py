"""A run whose timed path is broken underneath comes out not correct.

Each test drives ``bench/run.py``'s ``main`` in this process on a rehearsal
fleet (kernels in interpret mode on the CPU, so the look for a chip is
passed over) with one fault planted in the program: the propagation
returns its input unchanged, half of the profiles are left out of the
analysis, one propagated value is altered where it is produced, or a
routed op's value is not split by its normalised route weights.  The
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


ROUTED = "tiny-pelec-routes.analyze"


def assert_fails(out):
    assert out["correct"] is False
    assert out["checks"]["pms_gap_u"]["value"] > out["checks"]["pms_gap_u"]["limit"]


@pytest.mark.parametrize("workload", ["tiny-pelec.analyze", ROUTED])
def test_sound_run_is_correct(benchmark_file, capsys, env, workload):
    assert run_cell(benchmark_file, capsys, workload)["correct"] is True


def test_propagation_returning_its_input(benchmark_file, capsys, env,
                                         monkeypatch):
    from repro.kernels.batch import DeviceAggregator

    def exclusive_at(self, rows, cols, vals, width, ir, ic):
        at = {(r, c): v for r, c, v in zip(rows.tolist(), cols.tolist(),
                                            vals.tolist())}
        return np.array([at.get(p, 0.0) for p in zip(ir.tolist(), ic.tolist())],
                        np.float32)

    monkeypatch.setattr(DeviceAggregator, "inclusive_at", exclusive_at)
    assert_fails(run_cell(benchmark_file, capsys))


@pytest.mark.parametrize("workload", ["tiny-pelec.analyze", "tiny-amg.analyze",
                                      ROUTED])
def test_half_of_the_profiles_left_out(benchmark_file, capsys, env,
                                       monkeypatch, workload):
    from repro.core.aggregate import StreamingAggregator

    whole = StreamingAggregator.run
    monkeypatch.setattr(StreamingAggregator, "run", lambda self, paths: whole(
        self, paths[:len(paths) // 2]))
    assert_fails(run_cell(benchmark_file, capsys, workload))


def test_one_value_altered_where_produced(benchmark_file, capsys, env,
                                          monkeypatch):
    from repro.kernels.batch import DeviceAggregator

    inclusive_at = DeviceAggregator.inclusive_at

    def altered(self, *request):
        out = np.array(inclusive_at(self, *request))
        out.flat[np.argmax(out)] *= 1.001
        return out

    monkeypatch.setattr(DeviceAggregator, "inclusive_at", altered)
    assert_fails(run_cell(benchmark_file, capsys))


def test_routes_not_redistributed(benchmark_file, capsys, env, monkeypatch):
    """Each placeholder's whole value goes to its first route leaf."""
    from repro.core.propagate import expand_routes

    def first_leaf(ph_rows, routes):
        leaf, lens, _ = expand_routes(ph_rows, routes)
        first = np.cumsum(lens) - lens
        return leaf[first], np.ones_like(lens), np.ones(lens.size)

    monkeypatch.setattr("repro.core.pipeline.expand_routes", first_leaf)
    assert_fails(run_cell(benchmark_file, capsys, ROUTED))


def test_route_weights_not_normalised(benchmark_file, capsys, env,
                                      monkeypatch):
    """A placeholder's value is multiplied by each raw call-sample count."""
    from repro.core.propagate import expand_routes

    def raw_weights(ph_rows, routes):
        leaf, lens, w = expand_routes(ph_rows, routes)
        total = [routes[int(r)][1].sum() for r in ph_rows]
        return leaf, lens, w * np.repeat(total, lens)

    monkeypatch.setattr("repro.core.pipeline.expand_routes", raw_weights)
    assert_fails(run_cell(benchmark_file, capsys, ROUTED))
