"""The benchmark's own arithmetic: operation and byte counts, the trace
reduction, the files a cell is found by, and the device check."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import bench, checks, trace
from chipbench.tests import cells

ROOT = cells.ROOT


def test_mamba2_370m_operations_per_token_by_hand():
    cfg = bench.config("mamba2-370m")
    kind = bench.kind(cfg["kind"])
    # in_proj 2*1024*4384, conv 2*4*2304, intra-chunk 2*128*(128+2048), chunk
    # state and its read 2*(2*32*64*128), out_proj 2*2048*1024: 14,796,800 a
    # layer; 48 layers and the tied head 2*1024*50280.
    assert kind.forward_flops_per_token(cfg) == 48 * 14_796_800 + 102_973_440
    assert kind.train_flops_per_token(cfg) == 3 * 813_219_840


def test_qwen3_4b_L9_decode_counts_by_hand():
    cfg = bench.config("qwen3-4b-L9")
    kind = bench.kind(cfg["kind"])
    # q,o 2*2560*4096*2; k,v 2*2560*1024*2; SwiGLU 2*2560*9728*3: 201,850,880 a
    # layer, plus q.k and p.v 2*4096*2 a live position; the head 2*2560*151936.
    assert kind.decode_flops_per_token(cfg, 0) == 9 * 201_850_880 + 777_912_320
    assert kind.decode_flops_per_token(cfg, 1000) - kind.decode_flops_per_token(cfg, 0) \
        == 9 * 16_384 * 1000
    assert kind.weight_count(cfg) == 9 * 100_930_816 + 151_936 * 2560 + 2560
    # 36 KiB of bf16 keys and values a position over 9 layers.
    assert kind.decode_step_bytes(cfg, 4, 99) == 2 * 1_297_336_064 + 4 * 100 * 36_864


def test_gradient_readings_by_hand():
    import numpy as np

    got = {"a": np.array([3.0, 4.0]), "b": np.array([0.0, 2.0])}
    want = {"a": np.array([6.0, 8.0]), "b": np.array([1.0, 0.0])}
    r = checks.gradient_readings(got, want)
    assert r["got"].tolist() == [5.0, 2.0] and r["want"].tolist() == [10.0, 1.0]
    # The same direction at another norm reads 0; a right angle reads sqrt(2).
    assert r["direction"] == pytest.approx([0.0, 2**0.5])


def test_every_kind_holds_init_reference_and_counts():
    for path in sorted((bench.HERE / "configs").glob("*.json")):
        kind = bench.kind(bench.load_json(path)["kind"])
        assert callable(kind.init) and kind.reference.__name__.startswith("chipbench.reference.")


# A window of 100 us on one device: ops at [10, 30) and [30, 50), one
# at [70, 75) and one that runs past the window's end; the host spans say
# what the host did in each gap.
RECORDED = {
    "ops": {"0": [["fusion.1", 10_000, 20_000], ["copy.2", 30_000, 20_000],
                  ["fusion.1", 70_000, 5_000], ["late", 95_000, 10_000]]},
    "modules": {"0": [["jit_step(1)", 10_000, 40_000], ["jit_step(1)", 70_000, 10_000]]},
    "host": [["window", 0, 100_000], ["dispatch", 0, 9_000], ["sync", 50_000, 20_000],
             ["batch_prep", 80_000, 14_000]],
}


def test_trace_reduction_of_a_recorded_window():
    r = trace.reduce(RECORDED)
    assert r["window_s"] == pytest.approx(100e-6)
    # busy: [10,50) + [70,75) + [95,100) clipped to the window = 50 us
    assert r["busy_s"] == pytest.approx(50e-6)
    assert r["idle_share"] == pytest.approx(0.5)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(25e-6)]
    assert r["device_ops"][1] == ["copy.2", pytest.approx(20e-6)]
    gaps = dict((name, round(s * 1e6, 6)) for name, s in r["idle_gaps"])
    assert gaps == {"sync": 20.0, "batch_prep": 20.0, "dispatch": 10.0}
    assert r["module_s"] == {"jit_step(1)": [pytest.approx(40e-6), pytest.approx(10e-6)]}


def test_nested_ops_count_their_self_time():
    # A loop [0, 100) whose body ops take [10, 30) and [40, 90); one of them
    # holds a nested op [50, 60).
    ivs = [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"), (50, 60, "c")]
    assert dict(trace.self_times(ivs)) == {"while": 30, "a": 20, "b": 40, "c": 10}


def test_trace_reduction_needs_a_window_and_device_ops():
    with pytest.raises(ValueError, match="window"):
        trace.reduce(dict(RECORDED, host=[]))
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce(dict(RECORDED, ops={}))


def test_every_cell_finds_its_files_and_a_known_arch():
    from repro.configs import ARCHS

    b = bench.benchmark()
    for w in b["workloads"]:
        wl = bench.cell(w["name"])
        assert wl["config"] == w["config"]
        assert (bench.HERE / "drivers" / f"{wl['driver']}.py").exists()
        cfg = bench.config(w["config"])
        assert cfg["arch"] in ARCHS
        bench.program_config(cfg)  # the file holds what the program runs
        assert set(wl["limits"]) and all(v > 0 for v in wl["limits"].values())
    for c in b["configs"]:
        assert (ROOT / c["file"]).exists()
        assert bench.config(c["name"])["reduced"] == c["reduced"]
    for m in b["per_layer"]:
        assert callable(bench.metric_reader(m["name"]))


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    for w in bench.benchmark()["workloads"]:
        e2e = [m["name"] for m in bench.metrics_for(w["name"], False)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert bench.metrics_for(w["name"], True)


def test_a_device_kind_without_peaks_is_refused():
    with pytest.raises(SystemExit, match="peaks.json"):
        bench.peaks("TPU v4")


def test_the_harness_refuses_a_cpu():
    with pytest.raises(SystemExit, match="no TPU"):
        bench.check_devices(1)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "qwen3-4b.serve.chat1k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_a_result_line_is_one_json_object_with_the_checks_last():
    import jax

    from chipbench import run

    wl, cfg = cells.small_cell("qwen3-4b.serve.chat1k")
    c = cells.ctx(seed=2**31 + 11)
    try:
        r = run.run_cell(wl, cfg, c, bench.metrics_for("qwen3-4b.serve.chat1k", False))
    finally:
        c.compiles.close()
    line = json.loads(json.dumps(r))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert set(line["metrics"]) == {"decode_tokens_per_s", "decode_step_p95_ms",
                                    "peak_hbm_gib", "setup_s"}
    assert line["device"]["count"] == 1 and line["device"]["platform"] == jax.devices()[0].platform
