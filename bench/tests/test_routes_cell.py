"""The structured PeleC cell: its configuration as the benchmark loads it,
and the readers of the structured unify and the route split, on a CPU
rehearsal (kernels in interpret mode) and on runs without their keys."""
import json
from types import SimpleNamespace as NS

import pytest

from bench import run as bench_run
from bench.fleets import FleetShape, write_fleet

CELL = "pelec-1x82-routes.analyze"
READERS = {"route_split_s": "phase2/routes", "unify_s": "phase1/unify",
           "structures_s": "phase1/structures"}


def test_the_cell_loads_with_its_configuration():
    cell = bench_run.load_cell(bench_run.ROOT / "BENCHMARK.json", CELL)
    assert cell.chips == 1 and cell.mix["name"] == "analyze"
    assert cell.config["name"] == "pelec-1x82-routes"
    assert list(cell.config["reduced"]) == ["n_profiles"]
    assert {"struct_scopes", "routed_share", "n_routes"} <= set(
        cell.config["assumed"])
    assert {m["name"] for m in cell.per_layer} == set(READERS)
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "profiles_per_s", "db_MB"}
    plain = json.loads((bench_run.ROOT / "bench/configs/pelec-1x82.json")
                       .read_text())
    assert cell.config["limits"] == plain["limits"]


def test_the_shape_reads_the_structure_keys():
    cell = bench_run.load_cell(bench_run.ROOT / "BENCHMARK.json", CELL)
    shape = FleetShape.from_config(cell.config)
    assert (shape.n_profiles, shape.n_ctx, shape.n_cpu_metrics,
            shape.n_gpu_metrics, shape.n_private) == (96, 1200, 1, 82, 700)
    assert (shape.ctx_density, shape.met_density) == (0.04, 0.019)
    assert (shape.struct_scopes, shape.routed_share, shape.n_routes) == (
        3, 0.25, 4)


def run_of(*timings):
    return NS(done=[{"summary": {"timings": t}} for t in timings])


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """Two analyses of the structured rehearsal fleet, as the window runs
    them."""
    from repro.launch import analyze

    work = tmp_path_factory.mktemp("routes")
    cell = bench_run.load_cell(
        bench_run.ROOT / "BENCHMARK.json", "pelec-1x82.analyze")
    conf = json.loads((bench_run.ROOT / "bench/tests/configs/"
                       "tiny-pelec-routes.json").read_text())
    fleet = write_fleet(FleetShape.from_config(conf), 2**33 + 5,
                        str(work / "fleet"))
    summaries = [bench_run.analyze_once(analyze, bench_run.analyze_argv(
        fleet.paths, str(work / f"db{k}"), cell.mix, True)) for k in range(2)]
    return run_of(*(s["timings"] for s in summaries))


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_mean_their_spans_on_a_rehearsal(rehearsal, name):
    key = READERS[name]
    values = [a["summary"]["timings"][key] for a in rehearsal.done]
    assert bench_run.load_reader(name)(rehearsal) == pytest.approx(
        sum(values) / len(values))
    if name == "route_split_s":
        assert min(values) > 0


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_run_without_the_keys_reads_nothing(name):
    run = run_of({"phase1": 1.0, "phase2": 2.0, "cms": 0.5})
    assert bench_run.load_reader(name)(run) is None


def test_route_split_reads_zero_without_a_routed_op():
    """A program that counts placeholders, on a fleet that has none."""
    read = bench_run.load_reader("route_split_s")
    assert read(run_of({"route_placeholders": 0.0},
                       {"route_placeholders": 0.0})) == 0.0
    assert read(run_of({"route_placeholders": 8.0, "phase2/routes": 0.5},
                       {"route_placeholders": 8.0, "phase2/routes": 1.5})) \
        == pytest.approx(1.0)


def test_traced_rehearsal_reports_the_structure_metrics(benchmark_file,
                                                        capsys, monkeypatch):
    # the run points JAX's cache at the checkout; keep that out of this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(bench_run.ROOT / ".jax_cache"))
    rc = bench_run.main(["--workload", "tiny-pelec-routes.analyze", "--seed",
                         str(2**33 + 78), "--seconds", "1", "--trace", "1",
                         "--benchmark", str(benchmark_file),
                         "--device-interpret"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    for name in READERS:
        assert out["metrics"][name]["value"] > 0, name
