"""The traffic generator: seed determinism, and the window arithmetic of
the closed loop, driven against a scheduler stand-in on a fake clock.
"""
import json
import math
from concurrent.futures import Future
from pathlib import Path

import numpy as np
import pytest

from bench import harness, loadgen

SPEC = harness.load_spec()
MIXES = sorted(p.stem for p in (harness.ROOT / "bench" / "traffic").glob(
    "*.json"))
CONFIGS = [e["name"] for e in SPEC["configs"]]
SEEDS = [0, 7, 2**31 + 5, 2**40 + 11]


def _poses(cfg, mix, seed, n):
    p = loadgen.Poses(cfg["views"], mix, seed, cfg["width"], cfg["height"])
    return [p.next() for _ in range(n)]


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("mix_name", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_requests(config, mix_name, seed):
    cfg = harness.config(SPEC, config)
    mix = harness.traffic(mix_name)
    a, b = _poses(cfg, mix, seed, 16), _poses(cfg, mix, seed, 16)
    assert a == b
    assert a != _poses(cfg, mix, seed + 1, 16)
    for az, el in a:
        assert any(lo <= az <= hi for lo, hi in cfg["views"]["azimuth_deg"])
        assert cfg["views"]["elevation_deg"][0] <= el \
            <= cfg["views"]["elevation_deg"][1]


@pytest.mark.parametrize("kind", ["path", "sideways"])
def test_unknown_pose_kind_is_refused(kind):
    cfg = harness.config(SPEC, CONFIGS[0])
    with pytest.raises(ValueError):
        loadgen.Poses(cfg["views"], {"poses": {"kind": kind}}, 3, 32, 32)


def test_orbit_camera_looks_at_the_center():
    cfg = harness.config(SPEC, CONFIGS[0])
    from bench import scenes
    cam = scenes.orbit_pose(cfg["views"], 30.0, 20.0, 64, 48)
    center = np.asarray(cfg["views"]["center"], np.float32)
    pc = cam["R_wc"] @ center + cam["t_wc"]
    assert abs(pc[0]) < 1e-4 and abs(pc[1]) < 1e-4
    assert pc[2] == pytest.approx(cfg["views"]["radius"], rel=1e-5)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeScheduler:
    """Serves up to `chunk` queued requests per step in `batch_s` of fake
    time; a request is failed when `fail` says so."""

    def __init__(self, clock, chunk, batch_s, fail=lambda i: False):
        self.clock, self.chunk, self.batch_s = clock, chunk, batch_s
        self.queue, self.fail, self.n = [], fail, 0

    def submit(self, cam):
        fut = Future()
        self.queue.append((self.n, fut))
        self.n += 1
        return fut

    def step(self):
        self.clock.t += self.batch_s
        served, self.queue = self.queue[:self.chunk], self.queue[self.chunk:]
        for i, fut in served:
            if self.fail(i):
                fut.set_exception(RuntimeError("refused"))
            else:
                fut.set_result(i)

    def pending(self):
        return len(self.queue)


def _drive(mix, seconds, chunk, batch_s, **kw):
    cfg = harness.config(SPEC, CONFIGS[0])
    clock = FakeClock()
    sched = FakeScheduler(clock, chunk, batch_s, **kw)
    poses = loadgen.Poses(cfg["views"], mix, 1, 32, 32)
    win = loadgen.drive(mix, poses, sched.submit, sched.step,
                        sched.pending, seconds, chunk, clock=clock)
    return win, sched


CLOSED = {"loop": "closed", "in_flight": "chunk",
          "poses": {"kind": "uniform"}}


@pytest.mark.parametrize("seconds,batch_s,batches", [
    (10.0, 56.0, 1),     # one batch longer than the window: one batch
    (10.0, 4.0, 3),      # closes at the end of the batch crossing 10 s
    (12.0, 4.0, 3),      # a batch ending exactly at the mark closes it
])
def test_closed_window_closes_on_a_batch_boundary(seconds, batch_s,
                                                  batches):
    win, _ = _drive(CLOSED, seconds, 2, batch_s)
    assert win.seconds == pytest.approx(batches * batch_s)
    assert len(win.requests) == 2 * batches
    m = loadgen.window_metrics(win)
    assert m["frames_per_s"] == pytest.approx(2 * batches
                                              / (batches * batch_s))
    assert m["frame_ms_p50"] == pytest.approx(1e3 * batch_s)
    assert m["frame_ms_p95"] == pytest.approx(1e3 * batch_s)


def test_failed_requests_leave_the_rate_and_the_tail():
    win, _ = _drive(CLOSED, 10.0, 2, 4.0, fail=lambda i: i == 0)
    m = loadgen.window_metrics(win)
    assert m["frames_per_s"] == pytest.approx(5 / 12.0)
    assert sum(r.result() is None for r in win.requests) == 1


@pytest.mark.parametrize("values,p50,p95", [
    ([5.0], 5.0, 5.0),
    ([3.0, 1.0], 1.0, 3.0),
    (list(range(1, 101)), 50, 95),
    (list(range(1, 21)), 10, 19),
])
def test_percentile_is_nearest_rank_over_all_values(values, p50, p95):
    assert loadgen.percentile(values, 50) == p50
    assert loadgen.percentile(values, 95) == p95


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        loadgen.percentile([], 50)


@pytest.mark.parametrize("in_flight,requests,seconds", [
    (1, 3, 12.0),        # one 4-s dispatch per round: rounds end 4, 8, 12
    (3, 6, 16.0),        # two dispatches (2 + 1) per round: 8, 16
    (4, 8, 16.0),        # two full dispatches per round: 8, 16
])
def test_closed_loop_keeps_in_flight_outstanding(in_flight, requests,
                                                 seconds):
    """Each round submits `in_flight` requests and waits for all of them;
    a dispatch serves up to 2 in 4 s; the 10-s window closes at the end
    of the round that crosses it."""
    mix = dict(CLOSED, in_flight=in_flight)
    win, sched = _drive(mix, 10.0, 2, 4.0)
    assert len(win.requests) == requests and sched.pending() == 0
    assert win.seconds == pytest.approx(seconds)
    assert all(r.t_submit == r.t_due for r in win.requests)
    assert all(r.t_done <= win.t_close for r in win.requests)


def test_unknown_loop_is_refused():
    with pytest.raises(ValueError):
        _drive({"loop": "open", "rate_rps": 2.0,
                "poses": {"kind": "uniform"}}, 1.0, 1, 1.0)


def test_traffic_files_are_data_only():
    for p in (harness.ROOT / "bench" / "traffic").iterdir():
        assert p.suffix in (".json", ".jsonl", ".toml", ".txt", ".csv")
    assert Path(loadgen.__file__).parent == harness.ROOT / "bench"


def test_spread_is_the_quartile_distance_over_the_median(tmp_path):
    from bench import spread
    lines = [{"metrics": {"frames_per_s": {"value": v, "unit": "frames/s"}}}
             for v in (1.0, 2.0, 3.0, 4.0, 5.0, 6.0)]
    p = tmp_path / "a.jsonl"
    p.write_text("\n".join(json.dumps(x) for x in lines) + "\n")
    (name, meds, sprs, widest, bound, shift), = spread.table(
        [spread.load(p), spread.load(p)])
    assert name == "frames_per_s" and meds == [3.5, 3.5]
    assert widest == pytest.approx((5.25 - 1.75) / 3.5)
    assert bound == pytest.approx(5 * widest) and shift == 0.0
