"""Shared set-up for the benchmark's tests: a checkout root in a temp dir
holding a copy of the benchmark's data files, with the cohorts cut to a
size the CPU runs in a second."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(REPO, "benchmark")


def load_config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def tiny_cohort(cfg: dict, sites: int = 3000, shrink: int = 10,
                max_carriers: int = 6) -> dict:
    """The config's cohort section at ``sites`` sites and populations
    ``shrink`` times smaller; private classes capped at ``max_carriers``."""
    c = json.loads(json.dumps(cfg["cohort"]))
    c["sites"] = sites
    c["populations"] = [[p, n // shrink] for p, n in c["populations"]]
    for cls in c["classes"]:
        if cls["kind"] == "private":
            cls["max_carriers"] = min(cls["max_carriers"], max_carriers)
    return c


def tiny_root(tmp_path, sites: int = 3000) -> str:
    """A checkout root with BENCHMARK.json, the benchmark's data files and
    tiny copies of its configurations."""
    root = str(tmp_path / "checkout")
    os.makedirs(os.path.join(root, "benchmark", "configs"))
    for sub in ("traffic", "metrics", "cohorts"):
        shutil.copytree(os.path.join(BENCH, sub),
                        os.path.join(root, "benchmark", sub))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for fn in os.listdir(os.path.join(BENCH, "configs")):
        cfg = load_config(fn[:-5])
        cfg["cohort"] = tiny_cohort(cfg, sites)
        with open(os.path.join(root, "benchmark", "configs", fn), "w") as f:
            json.dump(cfg, f)
    return root


def stub_chip(monkeypatch):
    """Let run.py drive the CPU: no TPU check, no persistent compile
    cache, and placeholder peaks for the CPU's device kind."""
    import jax

    from benchmark import peaks, run

    monkeypatch.setattr(run, "require_devices", lambda chips: jax.devices())
    monkeypatch.setattr(run, "use_compile_cache", lambda root: None)
    monkeypatch.setitem(peaks.DEVICE_PEAKS, jax.devices()[0].device_kind,
                        dict(peaks.DEVICE_PEAKS["TPU v5 lite"]))


def last_json_line(text: str) -> dict:
    lines = [ln for ln in text.splitlines() if ln.strip()]
    return json.loads(lines[-1])
