"""The harness: found by name, refuses what it cannot measure, and drives
the trainer's own loop over a window it counts."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import cells

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_a_new_cell_and_configuration_are_found_by_name(tmp_path):
    (tmp_path / "bench" / "workloads").mkdir(parents=True)
    (tmp_path / "bench" / "configs").mkdir()
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(
        json.dumps({"family": "llama", "hidden_size": 8}))
    (tmp_path / "bench" / "workloads" / "tiny-w2.json").write_text(
        json.dumps({"config": "tiny", "chips": 1, "argv": []}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "end_to_end": [{"name": "tokens_per_s"}],
        "per_layer": [{"name": "mfu"},
                      {"name": "exposed_permute_ms",
                       "workloads": ["other-cell"]}]}))
    cell = harness.load_cell("tiny-w2", root=tmp_path)
    assert cell["cfg"]["hidden_size"] == 8 and cell["name"] == "tiny-w2"
    assert [m["name"] for m in harness.metrics_of(cell, True, tmp_path)] \
        == ["mfu"]
    assert [m["name"] for m in harness.metrics_of(cell, False, tmp_path)] \
        == ["tokens_per_s"]


def test_a_new_metric_is_found_by_name(tmp_path, monkeypatch):
    import bench.metrics

    (tmp_path / "twice_the_steps.py").write_text(
        "def read(ctx):\n    return 2 * ctx['steps']\n")
    monkeypatch.setattr(bench.metrics, "__path__",
                        [*bench.metrics.__path__, str(tmp_path)])
    assert harness.metric_reader("twice_the_steps")({"steps": 5}) == 10


def test_a_new_model_family_is_found_by_name(tmp_path, monkeypatch):
    """A configuration of another family needs only its reference module
    ``bench/reference/<family>.py``: its sizes and FLOPs come from there."""
    from types import SimpleNamespace

    import bench.reference
    from bench import flops

    (tmp_path / "toyfam.py").write_text(
        "def program_sizes(cfg):\n"
        "    return {'d_model': cfg['hidden_size']}\n\n\n"
        "def matmul_params(cfg):\n    return 7\n\n\n"
        "def flops_per_token(cfg, seq):\n    return 6 * 7 + seq\n")
    monkeypatch.setattr(bench.reference, "__path__",
                        [*bench.reference.__path__, str(tmp_path)])
    cfg = {"family": "toyfam", "hidden_size": 8}
    assert flops.matmul_params(cfg) == 7
    assert flops.flops_per_token(cfg, 2) == 44
    harness.check_config({"cfg": cfg}, SimpleNamespace(d_model=8))
    with pytest.raises(ValueError):
        harness.check_config({"cfg": cfg}, SimpleNamespace(d_model=9))


def test_every_committed_cell_and_metric_resolves():
    bench = json.load(open(ROOT / "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_an_unknown_device_kind_is_an_error():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "smollm-w4-int8", "--seed", "3", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


@pytest.fixture(scope="module")
def window():
    """One run of the reduced smollm cell on the CPU, with the context the
    metrics read."""
    seen = {}
    mp = pytest.MonkeyPatch()
    mp.setattr(harness, "peaks", cells.v5e_peaks)
    real = harness.metric_reader

    def reader(name):
        def read(ctx):
            seen.update(ctx)
            return real(name)(ctx)
        return read

    mp.setattr(harness, "metric_reader", reader)
    try:
        result = cells.run(cells.reduced_cell("smollm-w4-int8"))
    finally:
        mp.undo()
    return result, seen


def test_the_window_counts_every_token_of_its_steps(window):
    result, ctx = window
    st = ctx["settings"]
    assert ctx["steps"] == result["attempted"] >= 1
    assert ctx["tokens"] == ctx["steps"] * st.workers * st.batch * st.seq
    assert ctx["tokens_per_s"] == pytest.approx(
        ctx["tokens"] / ctx["window_s"])
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}


def test_the_result_line_has_its_keys_and_checks_last(window):
    result, _ = window
    assert list(result)[:4] == ["correct", "attempted", "failed", "metrics"]
    assert list(result)[-1] == "checks"
    assert result["device"]["count"] == 1
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}


def test_the_reduced_run_agrees_with_the_reference(window):
    result, _ = window
    assert result["correct"], result["checks"]
