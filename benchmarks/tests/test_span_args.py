"""``readers/span_args.py`` on a hand-made ring, and the two metrics it serves
(``round_hbm_pct.train``, ``setup_peak_hbm_pct.train``, ``round_workspace_hbm_pct.train``)
against ``BENCHMARK.json``."""

import json
import os

import pytest

import run as harness
from test_program_readers import _args, _reader, _span, program  # noqa: F401  (``program`` is a fixture)

METRICS = ("round_hbm_pct.train", "setup_peak_hbm_pct.train", "round_workspace_hbm_pct.train")
GB = 10**9


def _stage(ring, start_ms, **args):
    _span(ring, "feed.stage", start_ms, 2, tid=2)
    if args:
        ring._events[-1]["args"] = args


def test_span_args_on_a_hand_made_ring(program):
    ring, _ = program
    read = _reader("span_args").read
    stage = {"names": ["feed.stage"], "arg": "hbm_in_use"}
    assert read({}, **stage, reduce="max") is None  # no ring spans: never 0
    _stage(ring, 50)  # the parent's span: no argument
    assert read({}, **stage, reduce="max") is None
    # appended out of order: the reader sorts by start
    _stage(ring, 300, hbm_in_use=9 * GB, hbm_peak=12 * GB, hbm_reserved=4 * GB)
    _stage(ring, 100, hbm_in_use=8 * GB, hbm_peak=11 * GB, hbm_reserved=0)
    _stage(ring, 200, hbm_in_use=10 * GB, hbm_peak=11 * GB, hbm_reserved=4 * GB)
    _span(ring, "feed.drain", 210, 1, tid=2)
    ring._events[-1]["args"] = {"hbm_in_use": 99 * GB}  # another span is not read
    assert read({}, **stage, reduce="max") == 10 * GB
    assert read({}, **stage, reduce="first") == 8 * GB
    assert read({}, **stage, reduce="last") == 9 * GB
    assert read({}, names=["feed.stage"], arg="no_such", reduce="max") is None
    assert read({}, names=["no.such"], arg="hbm_in_use", reduce="max") is None
    ctx = {"memory_limit_bytes": 16 * GB}
    assert read(ctx, **stage, reduce="max", pct_of_memory_limit=True) == pytest.approx(62.5)
    assert read({}, **stage, reduce="max", pct_of_memory_limit=True) is None  # no limit: no share
    assert read({"memory_limit_bytes": None}, **stage, reduce="max", pct_of_memory_limit=True) is None
    # the metrics' own files: the round's largest reading, the first span's peak, the workspace
    assert read(ctx, **_args("round_hbm_pct.train")) == pytest.approx(62.5)
    assert read(ctx, **_args("setup_peak_hbm_pct.train")) == pytest.approx(68.75)
    assert read(ctx, **_args("round_workspace_hbm_pct.train")) == pytest.approx(25.0)


def test_a_text_argument_is_not_a_number(program):
    ring, _ = program
    _stage(ring, 100, hbm_in_use="lots", flag=True)
    read = _reader("span_args").read
    assert read({}, names=["feed.stage"], arg="hbm_in_use", reduce="max") is None
    assert read({}, names=["feed.stage"], arg="flag", reduce="max") is None


@pytest.mark.parametrize("metric", METRICS)
def test_the_data_files_load_against_the_declaration(metric):
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {m["name"]: m for m in bench["per_layer"]}[metric]
    with open(os.path.join(harness.HERE, "layer_metrics", f"{metric}.json")) as f:
        spec = json.load(f)
    for key in ("layer", "unit", "better", "source", "moves"):
        assert spec[key] == entry[key], key
    assert entry["layer"] == "device memory" and entry["source"] == "program_span"
    assert spec["reader"] == "span_args" and spec["args"]["pct_of_memory_limit"] is True
    cells = [w["name"] for w in bench["workloads"]]
    assert entry["workloads"] == cells  # every cell reports train_tokens_per_s
    for cell in cells:
        assert metric in {m["name"] for m in harness.load_cell(os.path.join(harness.ROOT, "BENCHMARK.json"), cell)["per_layer"]}
    # appended: what the benchmark had keeps its place
    assert [m["name"] for m in bench["per_layer"]][-3:] == list(METRICS)


class _StubDevice:
    def memory_stats(self):
        return {"bytes_in_use": 8 * GB, "peak_bytes_in_use": 9 * GB, "bytes_limit": 16 * GB, "bytes_reserved": 4 * GB}


def test_the_tiny_cell_traced_prints_both_metrics_and_the_tool_reads_the_trail(tmp_path, capsys, monkeypatch):
    """End to end on the CPU, the allocator stubbed: the program's producer puts
    the readings on its ``feed.stage`` spans, the reader finds them, the result
    line carries both metrics; ``tools/hbm_trail.py`` then reads the same ring
    and the compile log's records. Unstubbed the line leaves both out."""
    import importlib.util

    import jax
    from test_rehearsal import SOLO, TRAIN_CFG, _write

    d = str(tmp_path / "benchmarks")
    _write(os.path.join(d, "configs", "tiny_choco.json"), TRAIN_CFG)
    _write(os.path.join(d, "traffic", "tiny_choco.solo.json"), SOLO)
    _write(os.path.join(d, "peaks.json"), {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "source": "rehearsal"}})
    bench = {
        "command": ["python3", "benchmarks/run.py"], "paths": ["benchmarks"], "run_seconds": 3,
        "configs": [{"name": "tiny_choco", "source": "test", "file": "benchmarks/configs/tiny_choco.json", "reduced": [], "why": "t"}],
        "workloads": [{"name": "tiny_choco.solo", "config": "tiny_choco", "traffic": "solo", "chips": 1, "why": "t"}],
        "end_to_end": [
            {"name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.01, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1, "source": "host_clock"},
        ],
        "per_layer": [
            {"name": n, "unit": "%", "better": "lower", "source": "program_span", "layer": "device memory", "moves": "train_tokens_per_s"}
            for n in METRICS
        ],
    }
    bench_file = str(tmp_path / "BENCHMARK.json")
    _write(bench_file, bench)
    argv = ["--workload", "tiny_choco.solo", "--seed", "3000000001", "--seconds", "2"]

    assert harness.main(argv + ["--trace", "1"], bench_file=bench_file, require_chip=False) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert not set(METRICS) & set(line["metrics"])  # the CPU has no memory_stats(): left out, never 0

    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [_StubDevice()])
    monkeypatch.setattr(harness, "memory_peak", lambda chips: (9 * GB, 16 * GB))
    spec = importlib.util.spec_from_file_location("hbm_trail", os.path.join(harness.ROOT, "tools", "hbm_trail.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    out_file = str(tmp_path / "trail.json")
    monkeypatch.setenv("HBM_TRAIL_OUT", out_file)
    from consensusml_tpu.obs import get_tracer

    get_tracer().clear()
    assert tool.main(argv, bench_file=bench_file, require_chip=False) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metrics"]["round_hbm_pct.train"]["value"] == pytest.approx(50.0)
    assert line["metrics"]["setup_peak_hbm_pct.train"]["value"] == pytest.approx(56.25)
    assert line["metrics"]["round_workspace_hbm_pct.train"]["value"] == pytest.approx(25.0)
    with open(out_file) as f:
        trail = json.load(f)
    assert trail["with_a_reading"] == trail["feed_stage_spans"] >= 2
    assert trail["setup_peak_bytes"] == 9 * GB and trail["round_in_use_max_bytes"] == 8 * GB
    assert trail["round_reserved_bytes"] == [4 * GB]
    assert 0 <= trail["share_of_round_passed_at_the_reading"]["median"] <= 1
    funs = [p["fun"] for p in trail["setup_programs"]]
    assert "init" in funs and "train_step" in funs
    assert trail["setup_programs"][-1]["peak_after_bytes"] == 9 * GB
    assert trail["traced_window"]["train_tokens_per_s"] > 0 and trail["traced_window"]["rounds"] == line["attempted"]
