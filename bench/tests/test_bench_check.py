"""The comparison that decides `correct`, at a size a test run can hold.

On the CPU, with the harness's look for a chip skipped: a whole run of a
cell at 32x32 pixels and 512 Gaussians reads correct; the same run with the
served path broken underneath (an answer altered where it is produced, half
of the batch left out) reads not correct; and the control, the reference in
bfloat16 in the program's place, fails the cell's limits. The control and
the faults were also read on the chip at the cells' own sizes (PERF.md).
"""
import copy
import dataclasses
import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, reference, scenes

SPEC = harness.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
ENGINES = {}


def tiny(cell_name):
    """The cell with its configuration cut to 32x32 pixels, 256 Gaussians
    and 4 spill passes of 32 slots (lists stay under 128 on its views)."""
    cell = harness.workload(SPEC, cell_name)
    cfg = copy.deepcopy(harness.config(SPEC, cell["config"]))
    cfg.update(width=32, height=32, gaussians=256, check_frames=2)
    cfg["engine"].update(k_pass=32, k_max=128)
    return (cell, cfg, harness.traffic(cell["traffic"]),
            harness.limits(cell_name),
            harness.cell_metrics(SPEC, cell_name, False))


@pytest.fixture(autouse=True)
def one_engine_per_config(monkeypatch):
    """Runs of one configuration share its engine, as the runs of a
    calibration do: each run registers its own scene, and the compiled
    batch program is reused."""
    build = harness.build_engine

    def shared(cfg):
        if cfg["name"] not in ENGINES:
            ENGINES[cfg["name"]] = build(cfg)[0]
        from repro.serving import Scheduler
        return ENGINES[cfg["name"]], Scheduler(ENGINES[cfg["name"]])
    monkeypatch.setattr(harness, "build_engine", shared)


def run_tiny(cell_name, seed=2**35 + 9):
    cell, cfg, mix, lims, metrics = tiny(cell_name)
    return harness.execute(cell, cfg, mix, lims, metrics, seed, 0.0, False,
                           time.perf_counter(), log=lambda msg: None)


def _altered(frames):
    """An answer altered where it is produced: one tile of the first frame
    brightened by a quarter."""
    f = frames[0]
    img = f.image.at[:16, :16].add(0.25)
    return [dataclasses.replace(f, image=img)] + list(frames[1:])


def _first_half(frames):
    half = frames[:max(len(frames) // 2, 1)]
    return half + [dataclasses.replace(half[i % len(half)], request=f.request)
                   for i, f in enumerate(frames[len(half):])]


FAULTS = {
    "answer_altered": lambda orig, self, reqs: _altered(orig(self, reqs)),
    # Half of the batch left out: the first half's frames are handed to
    # the second half's requests too.
    "half_batch_left_out": lambda orig, self, reqs: _first_half(
        orig(self, reqs)),
}


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_run_reads_correct(cell_name):
    line = run_tiny(cell_name)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "check"]
    assert line["correct"] is True, line["check"]
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in tiny(cell_name)[4]}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["check"]) == set(check.NUMBERS)
    json.dumps(line)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_served_path_reads_not_correct(fault, monkeypatch):
    from repro.serving.engine import RenderEngine
    orig = RenderEngine.render_batch
    monkeypatch.setattr(RenderEngine, "render_batch",
                        lambda self, reqs: FAULTS[fault](orig, self, reqs))
    line = run_tiny(CELLS[0])
    assert line["correct"] is False
    assert any(v["value"] > v["limit"] for v in line["check"].values())


@pytest.mark.parametrize("cell_name", CELLS)
def test_bfloat16_control_fails_the_limits(cell_name):
    _, cfg, mix, lims, _ = tiny(cell_name)
    from bench import loadgen
    scene = scenes.make_scene(cfg["scene"], cfg["gaussians"], 5)
    poses = loadgen.Poses(cfg["views"], mix, 5, 32, 32)
    rows = []
    for _ in range(2):
        cam = poses.camera()
        ref = reference.render(scene, cam, k_pass=32)
        low = reference.render(scene, cam, k_pass=32, dtype=jnp.bfloat16)
        rows.append(check.frame_numbers(low["image"], low["counters"], ref))
    ok, _ = check.verdict(check.worst(rows), lims)
    assert not ok


def test_frame_numbers_and_verdict():
    ref = {"image": np.zeros((32, 32, 3), np.float32),
           "counters": {k: 100.0 for ks in check.GROUPS.values()
                        for k in ks}}
    img = np.zeros((32, 32, 3), np.float32)
    img[0, 0, 0] = 0.5
    img[20, 20, 1] = 0.25
    counters = dict(ref["counters"], vru_pairs=101.0)
    got = check.frame_numbers(img, counters, ref)
    assert got["image_mae"] == pytest.approx(0.75 / 3072)
    assert got["tile_mae_max"] == pytest.approx(0.5 / 768)
    assert got["image_off_share"] == pytest.approx(2 / 3072)
    assert got["ctu_gap"] == pytest.approx(0.01)
    assert got["stage1_gap"] == 0.0 and got["blend_gap"] == 0.0
    lims = dict.fromkeys(check.NUMBERS, 0.005)
    ok, rows = check.verdict(got, lims)
    assert not ok and [r[0] for r in rows] == list(check.NUMBERS)
    assert check.verdict(dict.fromkeys(check.NUMBERS, 0.0), lims)[0]


def test_one_altered_tile_shows_in_the_tile_number():
    """One 16x16 tile of a Full-HD frame brightened by a quarter: the
    frame's mean moves by 0.25 / 8160, its worst tile's by 0.25."""
    diff = np.zeros((1088, 1920, 3), np.float32)
    diff[16:32, 48:64] = 0.25
    assert check.tile_mae_max(diff) == pytest.approx(0.25)
    assert float(diff.mean()) == pytest.approx(0.25 / 8160)


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_refuses_a_cpu_backend():
    p = _run_cli(harness.ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr


def test_run_fails_without_the_program(tmp_path):
    import shutil
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr
