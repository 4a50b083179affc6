"""The harness end to end on the CPU, with the chip's look skipped."""

from __future__ import annotations

import json

import pytest

from chiptest import FakeChip, load_run

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def run_cell(root, capsys, monkeypatch, *, trace: int = 0, seed: int = 3,
             cell: str = "tiny_train"):
    run = load_run(root)
    monkeypatch.setattr(run, "setup_jax", lambda chips: [FakeChip()])
    rc = run.main(["--workload", cell, "--seed", str(seed),
                   "--seconds", "1", "--trace", str(trace)])
    out, err = capsys.readouterr()
    return rc, out, err


@pytest.mark.parametrize("cell, trace, metrics", [
    ("tiny_train", 0, {"train_tokens_per_s", "setup_s"}),
    ("tiny_serve", 0, {"serve_latency_p95_s", "setup_s"}),
    ("tiny_train", 1, set()),
    ("tiny_serve", 1, {"serve_sweep_ms"}),
])
def test_cell_found_by_name_and_result_has_contract_keys(
        bench_copy, capsys, monkeypatch, cell, trace, metrics):
    rc, out, err = run_cell(bench_copy, capsys, monkeypatch, seed=2**33 + 5,
                            cell=cell, trace=trace)
    assert rc == 0, err
    result = json.loads(out.strip().splitlines()[-1])
    keys = RESULT_KEYS[:-1] + (["breakdown"] if trace else []) + ["checks"]
    assert list(result) == keys
    assert result["correct"] is True, result["checks"]
    # A CPU trace holds no device plane: the device readers find nothing.
    assert set(result["metrics"]) == metrics
    assert result["device"]["kind"] == "TPU v5 lite"
    if trace:
        assert set(result["device"]) >= {"busy_s", "window_s"}
    # The compared numbers are also the last lines on stderr.
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") for line in tail)


def test_no_tpu_exits_nonzero_without_result(bench_copy, capsys):
    run = load_run(bench_copy)
    rc = run.main(["--workload", "tiny_train", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in err
    assert not any(line.startswith("{") for line in out.splitlines())


def test_bare_benchmark_directory_refuses(tmp_path, capsys):
    """A checkout holding only BENCHMARK.json and the benchmark's files
    (no program) exits non-zero and prints no result."""
    import shutil

    from chiptest import CHIP, REPO
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    run = load_run(tmp_path)
    rc = run.main(["--workload", "lda_k1024_train_bsp", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0 and "src/repro" in err and out == ""


@pytest.mark.parametrize("kind, name", [("configs", "tiny_lda"),
                                        ("traffic", "tiny_train"),
                                        ("limits", "tiny_train")])
def test_dropped_file_is_the_one_read(bench_copy, capsys, monkeypatch,
                                      kind, name):
    """Removing a file the tiny cell names makes the run refuse: the
    harness reads it by name and needs no other edit to find it."""
    (bench_copy / "benchmarks" / "chip" / kind / f"{name}.json").unlink()
    rc, out, err = run_cell(bench_copy, capsys, monkeypatch)
    assert rc != 0 and f"{kind}/{name}.json" in err
    assert not out.strip().startswith("{")


def test_dropped_metric_is_read_by_name(bench_copy, capsys, monkeypatch):
    """A per-layer metric is a reader file and a BENCHMARK.json entry."""
    chip = bench_copy / "benchmarks" / "chip"
    (chip / "metrics" / "window_rounds.py").write_text(
        "def read(r):\n    return float(r.counters['rounds'])\n")
    bench = json.loads((bench_copy / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "window_rounds", "unit": "rounds", "better": "higher",
        "source": "program_counter", "layer": "round program",
        "moves": "train_tokens_per_s", "workloads": ["tiny_train"]})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(bench))
    rc, out, err = run_cell(bench_copy, capsys, monkeypatch, trace=1)
    assert rc == 0, err
    metrics = json.loads(out.strip().splitlines()[-1])["metrics"]
    assert metrics["window_rounds"]["value"] >= 1
    assert metrics["window_rounds"]["unit"] == "rounds"
