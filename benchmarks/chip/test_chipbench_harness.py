"""The harness on the CPU at a tiny size: the result line of each traffic
driver, the refusals, and a cell, a configuration and a metric added as
files alone."""
import json
import os

import pytest

from chipbench import harness
from chipbench.testing import run_tiny, tiny_root

KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("workload,metric", [
    ("elm-3c9c-k4-skew", "train_images_per_s"),
    ("serve-3c9c-k4-poisson", "serve_p95_ms"),
])
def test_driver_result_line(root, workload, metric):
    result, out, err = run_tiny(root, workload)
    assert json.loads(out[-1]) == result
    assert list(result) == KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {metric, "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] >= 1
    assert out[0].startswith("device platform=cpu")
    assert any(line.startswith("window compiles=") for line in out)
    assert err[-len(result["compared"]):] == [
        f"compared {k}={v['value']} limit={v['limit']}"
        for k, v in result["compared"].items()]


def test_serving_reports_generator_lateness(root):
    _, out, _ = run_tiny(root, "serve-3c9c-k4-poisson")
    late = [line for line in out if line.startswith("generator ")]
    assert late and "late_ms_p99=" in late[0]


def test_traced_run_reports_device_window(root):
    result, _, _ = run_tiny(root, "serve-3c9c-k4-poisson", trace=1)
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no device plane: only the program's counters read
    assert set(result["metrics"]) <= {"serve_batch_occupancy",
                                      "serve_score_ms"}
    assert result["metrics"]["serve_batch_occupancy"]["value"] >= 1


def test_cell_config_and_metric_added_as_files(tmp_path):
    """A new configuration, traffic mix, cell and per-layer metric, as
    files and entries only: the harness runs them with no edit."""
    root = tiny_root(str(tmp_path))
    bench = os.path.join(root, harness.BENCH_REL)
    with open(os.path.join(bench, "configs", "cnn_elm_6c12c.json")) as f:
        cfg = json.load(f)
    cfg.update(name="cnn_elm_4c8c")
    cfg["model"]["cnn_channels"] = [4, 8]
    with open(os.path.join(bench, "configs", "cnn_elm_4c8c.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "sgd1_iid_k2.json"), "w") as f:
        json.dump({"driver": "train_jobs", "members": 2, "partition": "iid",
                   "batch": 10, "epochs": 1, "rounds": 1, "lr": 0.05,
                   "backend": "stacked"}, f)
    with open(os.path.join(bench, "limits", "train-4c8c-k2.json"), "w") as f:
        json.dump({"limits": {"beta_max_err": 0.5}}, f)
    with open(os.path.join(bench, "metrics", "jobs_done.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx.counters['jobs']\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "train-4c8c-k2", "config":
                              "cnn_elm_4c8c", "traffic": "sgd1_iid_k2",
                              "chips": 1, "why": "a test cell"})
    spec["end_to_end"][0]["workloads"].append("train-4c8c-k2")
    spec["per_layer"].append({"name": "jobs_done", "unit": "jobs",
                              "better": "higher", "source": "host_clock",
                              "layer": "step", "moves":
                              "train_images_per_s",
                              "workloads": ["train-4c8c-k2"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    result, _, _ = run_tiny(root, "train-4c8c-k2", trace=1)
    assert result["correct"] is True
    assert result["metrics"]["jobs_done"]["value"] >= 1
    assert list(result["compared"]) == ["beta_max_err"]


def test_cell_metrics_follow_the_spec():
    spec = harness.load_spec(harness.ROOT)
    for cell in spec["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(spec, cell["name"],
                                                       False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = harness.cell_metrics(spec, cell["name"], True)
        assert layer and all(m["moves"] in e2e for m in layer)


def test_refuses_without_a_tpu(root):
    with pytest.raises(harness.SetupError, match="no TPU"):
        harness.run(["--workload", "elm-3c9c-k4-skew", "--seed", "1",
                     "--seconds", "1"], root=root)


def test_refuses_an_overridden_kernel_policy(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    with pytest.raises(harness.SetupError, match="kernel policy"):
        harness.require_chip(1)


def test_refuses_too_few_chips(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(harness.SetupError, match="needs 64 chips"):
        harness.require_chip(64)


def test_refuses_an_undeclared_cell(root):
    with pytest.raises(harness.SetupError, match="no workload"):
        harness.load_env(root, "no-such-cell", 1, 1.0, False)


def test_job_seeds_take_large_seeds():
    a = harness.job_seeds(2 ** 31 + 12345, 3)
    assert a == harness.job_seeds(2 ** 31 + 12345, 3)
    assert a != harness.job_seeds(2 ** 31 + 12345, 4)
    assert all(0 <= s < 2 ** 31 for s in a)
