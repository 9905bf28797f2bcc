"""Arithmetic shared by the per-layer metric readers (``bench/metrics``).

A reader takes the run's context -- ``cfg``, ``run`` (right-hand sides
solved, iterations of each, steps, window seconds), ``trace`` (the
reduction of ``bench/trace.py``, or ``None``), ``n_local``, ``lanes`` and
``peaks`` -- and returns a number, or ``None`` where it finds nothing to
read.
"""
from __future__ import annotations

import numpy as np

from bench.roofline import bytes_per_iter


def iters_to_tol(ctx):
    """Mean iterations per right-hand side (``SolveResult.iters``)."""
    it = ctx.run["iters"]
    return float(np.mean(it)) if it else None


#: bytes of a word by the program's ``precision=`` names of storage
_PRECISION_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8}


def storage_bytes(cfg) -> int:
    """Bytes of one stored window word: the ``precision`` the solver is
    given (its storage side), else the configuration's dtype."""
    p = cfg["solver"].get("precision")
    for name, size in _PRECISION_BYTES.items():
        if isinstance(p, str) and p.startswith(name):
            return size
    return np.dtype(p or cfg["dtype"]).itemsize


def body_roofline(ctx):
    """Useful iterations' HBM bytes at peak bandwidth over device busy
    time, in percent.  Bodies run after convergence do no useful work
    and count only in the time."""
    t = ctx.trace
    if (t is None or ctx.peaks is None or t["busy_s"] <= 0
            or not ctx.run["iters"]):
        return None
    word = storage_bytes(ctx.cfg)
    useful = sum(ctx.run["iters"]) * bytes_per_iter(
        ctx.cfg["solver"]["l"], ctx.n_local, word)
    return 100.0 * useful / ctx.peaks["hbm_bytes_s"] / t["busy_s"]


def device_idle_pct(ctx):
    """Share of the traced window in which no op ran on the device."""
    t = ctx.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def _exposed_us(ctx, key):
    t = ctx.trace
    if t is None or t["devices"] < 2 or not ctx.run["iters"]:
        return None
    return t[key] / sum(ctx.run["iters"]) * 1e6


def reduction_exposed_us(ctx):
    """Microseconds per useful iteration in which a reduction collective
    runs and no compute op does, averaged over the devices."""
    return _exposed_us(ctx, "reduction_exposed_s")


def halo_exposed_us(ctx):
    """Microseconds per useful iteration in which a halo
    collective-permute runs and no compute op does."""
    return _exposed_us(ctx, "halo_exposed_s")
