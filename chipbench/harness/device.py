"""The device as JAX reports it, and the table of its published peaks."""

from __future__ import annotations

import json
import os

import jax

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json")


def peaks_for(kind: str) -> dict:
    """The peaks of exactly this ``device_kind``; an unlisted device is an
    error, never a default."""
    with open(PEAKS_FILE, encoding="utf-8") as fh:
        table = json.load(fh)
    if kind not in table:
        raise KeyError(
            f"device_kind {kind!r} is not in {PEAKS_FILE}: add it with its source "
            f"rather than report a share of a guessed peak")
    return table[kind]


def require(chips: int, devices=None) -> dict:
    """The attached devices are ``chips`` TPUs with known peaks, or an error."""
    devices = jax.devices() if devices is None else devices
    if devices[0].platform != "tpu":
        raise RuntimeError(f"the benchmark runs on a TPU, JAX found {devices[0].platform!r}")
    if len(devices) != chips:
        raise RuntimeError(f"the cell asks for {chips} chip(s), JAX found {len(devices)}")
    return peaks_for(devices[0].device_kind)


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest device; 0 where the backend
    reports none (the CPU)."""
    stats = [d.memory_stats() for d in devices]
    return max((s["peak_bytes_in_use"] for s in stats if s), default=0)
