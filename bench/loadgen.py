"""The one general traffic generator: reads a traffic mix and drives it.

A traffic mix is a JSON file under `bench/traffic/`. Its keys:

  loop       "closed": keep `in_flight` requests outstanding, submit the
             next batch when the last one has resolved (callers that wait
             for their frame). The only loop so far; an open loop (a
             Poisson schedule, latency timed from each request's due time)
             comes with the first cell that offers load at a rate.
  in_flight  requests outstanding, a whole number or "chunk" (the
             scheduler's batch for the cell's resolution).
  poses      {"kind": "uniform"}: every request an independent pose drawn
             from the configuration's view space.

Every pose comes from the run's seed through one NumPy
generator, so the same seed gives the same requests in the same order.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Callable, Optional

import numpy as np

from bench import scenes


def _azimuth(rng, arcs) -> float:
    """Uniform over a union of azimuth arcs [[lo, hi], ...] in degrees."""
    widths = np.array([hi - lo for lo, hi in arcs], np.float64)
    i = int(rng.choice(len(arcs), p=widths / widths.sum()))
    lo, hi = arcs[i]
    return float(rng.uniform(lo, hi))


class Poses:
    """The request poses of one run, drawn in order from the seed."""

    def __init__(self, views: dict, mix: dict, seed: int, width: int,
                 height: int):
        if mix["poses"]["kind"] != "uniform":
            raise ValueError(f"unknown pose kind {mix['poses']['kind']!r}")
        self.views, self.width, self.height = views, width, height
        self.rng = np.random.default_rng(int(seed))

    def next(self) -> tuple[float, float]:
        """(azimuth, elevation) in degrees of the next request."""
        return (_azimuth(self.rng, self.views["azimuth_deg"]),
                float(self.rng.uniform(*self.views["elevation_deg"])))

    def camera(self) -> dict:
        az, el = self.next()
        return scenes.orbit_pose(self.views, az, el, self.width,
                                 self.height)


@dataclasses.dataclass
class Request:
    """One request of the window: its camera, when it was due and
    submitted, when it resolved, and what it resolved to."""
    camera: dict
    t_due: float
    t_submit: float = math.nan
    t_done: float = math.nan
    future: object = None

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_due

    def result(self):
        """The served result, or None where the request failed."""
        return None if self.future.exception() is not None \
            else self.future.result()


@dataclasses.dataclass
class Window:
    """What one measured window did."""
    t_open: float
    t_close: float
    requests: list

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


def drive(mix: dict, poses: Poses, submit: Callable, step: Callable,
          pending: Callable, seconds: float, chunk: int,
          clock: Callable = time.perf_counter,
          span: Optional[Callable] = None) -> Window:
    """Run the mix against a scheduler for `seconds` and return the window.

    submit(camera) -> future; step() serves one dispatch; pending() counts
    queued requests. The window opens now and closes at the end of the
    first dispatch that ends `seconds` or more after it opened, so it
    holds whole batches and never reads empty. Requests still queued then
    are served (and kept) but their completions fall outside the window.
    `span(name)` wraps the generator's own work (camera build, submit,
    dispatch) in a named host span for the profiler; None adds nothing.
    """
    span = span or (lambda name: contextlib.nullcontext())
    reqs: list[Request] = []
    t_open = clock()

    def run_step():
        with span("bench.step"):
            step()
        now = clock()
        for r in reqs:
            if math.isnan(r.t_done) and r.future.done():
                r.t_done = now
        return now

    def new_request(t_due):
        with span("bench.camera"):
            cam = poses.camera()
        r = Request(camera=cam, t_due=t_due)
        with span("bench.submit"):
            r.t_submit = clock()
            r.future = submit(cam)
        if r.future.done():          # refused at admission
            r.t_done = r.t_submit
        reqs.append(r)

    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    n = chunk if mix["in_flight"] == "chunk" else int(mix["in_flight"])
    while True:
        for _ in range(n):
            new_request(clock())
        now = t_open
        while pending():
            now = run_step()
        if now - t_open >= seconds:
            break
    t_close = clock()
    while pending():
        run_step()
    return Window(t_open=t_open, t_close=t_close, requests=reqs)


def percentile(values, q: float) -> float:
    """The q-th percentile by nearest rank: the smallest value with at
    least q % of the values at or below it (of two values, p50 is the
    smaller and p95 the larger)."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    return v[max(math.ceil(q / 100.0 * len(v)), 1) - 1]


def window_metrics(window: Window) -> dict:
    """End-to-end numbers of a window: frames completed in it per second
    of it, and the latency percentiles of every request completed in it."""
    done = [r for r in window.requests
            if r.t_done <= window.t_close and r.result() is not None]
    lat_ms = [1e3 * r.latency_s for r in done]
    return dict(frames_per_s=len(done) / window.seconds,
                frame_ms_p50=percentile(lat_ms, 50),
                frame_ms_p95=percentile(lat_ms, 95))
