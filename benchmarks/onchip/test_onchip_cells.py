"""Every cell's whole run at a tiny size on the CPU, and the harness finding
a new configuration, mix and metric by name alone.

The look for a chip is skipped; everything else is a run as the chip sees
it: data from the seed, the served path, the window, the checks against
the numpy reference, the controls and the metric readers.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from onchip import catalog, tinybench

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tinybench.make(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("workload", sorted(tinybench.CELLS))
def test_cell_is_correct_and_its_control_is_not(tiny, workload):
    result, err = tinybench.run(tiny, workload, controls=True)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    cell = catalog.find(workload, bench_dir=tiny,
                        benchmark_json=os.path.join(tiny, "BENCHMARK.json"))
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    for name, m in result["metrics"].items():
        assert m["value"] > 0, name
    # each control breaks its guarantee on the sampled lookups
    assert result["controls"] and all(v > 0 for v in
                                      result["controls"].values())
    # the numbers compared close standard error and the result line
    lines = err.strip().splitlines()
    assert list(result)[-1] == "checks"
    assert lines[-len(result["checks"]):] == [
        f"check {k} = {v['value']} (limit {v['limit']})"
        for k, v in result["checks"].items()]


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A configuration, a mix and a metric reader that no file of the
    benchmark names, placed in a directory of their own."""
    root = tmp_path / "bench"
    (root / "configs").mkdir(parents=True)
    (root / "traffic").mkdir()
    (root / "metrics").mkdir()
    cfg = json.loads(open(os.path.join(HERE, "configs",
                                       "sift1m_l1.json")).read())
    cfg.update(name="found_l1", rows=300, capacity=512, width=9, bits=2,
               query_set=160, clusters=4, max_batch=4)
    (root / "configs" / "found_l1.json").write_text(json.dumps(cfg))
    (root / "traffic" / "found_l1.burst.json").write_text(json.dumps({
        "loop": "open", "rate_per_s": 30, "ops": {"read": 1.0},
        "read_dist": "uniform", "k": 3, "check_sample": 8,
        "exercises": ["exact_top_k"]}))
    (root / "metrics" / "answered_reads.py").write_text(
        "def read(ctx):\n"
        "    return sum(r is not None for r in ctx.log.resp)\n")
    (root / "metrics" / "setup_s.py").write_text(
        "def read(ctx):\n    return ctx.setup_s\n")
    (root / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "found_l1.burst", "config": "found_l1",
                       "traffic": "burst", "chips": 1, "why": "found"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s"},
            {"name": "answered_reads", "unit": "lookups",
             "workloads": ["found_l1.burst"]}],
        "per_layer": []}))
    result, _ = tinybench.run(str(root), "found_l1.burst", seconds=1.0)
    assert result["correct"], result["checks"]
    assert result["metrics"]["answered_reads"]["value"] == \
        result["attempted"] == 30


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    return subprocess.run(
        [sys.executable, "benchmarks/onchip/run.py", "--workload",
         "sift1m_l1.steady_k10", "--seed", str(2**31 + 3), "--seconds", "1",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_chip_no_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    assert p.stdout.strip() == ""


def test_alone_in_a_directory_fails(tmp_path):
    shutil.copytree(HERE, tmp_path / "benchmarks" / "onchip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "BENCHMARK.json").write_text(
        open(os.path.join(ROOT, "BENCHMARK.json")).read())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/onchip/run.py", "--workload",
         "sift1m_l1.steady_k10", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _warmed(tiny, workload):
    from onchip import datagen, harness, loadgen
    cell = catalog.find(workload, bench_dir=tiny,
                        benchmark_json=os.path.join(tiny, "BENCHMARK.json"))
    data = datagen.build(cell.cfg, 5)
    svc = harness.make_service(cell.cfg)
    rows = harness.load(svc, cell.cfg, data)
    client = loadgen.Client(svc, harness.TABLE, cell.cfg, cell.traffic, data,
                            rows)
    harness.warm(svc, client, cell.cfg, cell.traffic)
    return cell, svc


def test_warm_up_sends_every_bucket_as_one_group(tiny):
    """Each power-of-two bucket an open loop's group can reach, a stall's
    arrivals included, is dispatched once, as one group, so none compiles
    in the window."""
    from onchip import harness
    cell, svc = _warmed(tiny, "tiny_l1.steady")
    top = harness.top_bucket(cell.cfg, cell.traffic)
    # 8 + 40/s x 2.5 s = 108 lookups pending at most: the 128 bucket
    assert top == 128
    buckets = top.bit_length()
    assert svc.stats()["compilations"] == svc.flushes == buckets
    assert svc.dispatched == 2 * top - 1
    # the deadline and the batch trigger are the configuration's again
    assert svc.max_batch == cell.cfg["max_batch"]
    assert svc.flush_after == cell.cfg["flush_after_ms"] / 1e3


def test_closed_loop_warms_buckets_up_to_its_clients(tiny):
    """A closed loop never has more lookups pending than clients, so no
    bucket above theirs is warmed."""
    cell, svc = _warmed(tiny, "tiny_kv.latest")
    assert cell.traffic["clients"] == 8
    assert svc.stats()["compilations"] == 4          # buckets 1, 2, 4, 8
    assert svc.dispatched == 15


@pytest.mark.parametrize("kind,key", [("configs", "key_bits"),
                                      ("traffic", "zipf_theta")])
def test_a_setting_no_code_reads_is_refused(tiny, tmp_path, kind, key):
    root = tmp_path / "bench"
    shutil.copytree(tiny, root)
    path = root / kind / ("tiny_kv.json" if kind == "configs"
                          else "tiny_kv.zipf.json")
    obj = json.loads(path.read_text())
    obj[key] = 0.5
    path.write_text(json.dumps(obj))
    with pytest.raises(ValueError, match=key):
        catalog.find("tiny_kv.zipf", bench_dir=str(root),
                     benchmark_json=str(root / "BENCHMARK.json"))


def test_a_cell_on_more_chips_is_refused(tiny, tmp_path):
    """The harness banks no table over chips, so a four-chip cell fails
    instead of running on one chip."""
    root = tmp_path / "bench"
    shutil.copytree(tiny, root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        w["chips"] = 4
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(ValueError, match="4 chips"):
        tinybench.run(str(root), "tiny_l1.steady")
