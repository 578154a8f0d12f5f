"""Finds every piece of a cell by the name ``BENCHMARK.json`` gives it.

* configuration ``<c>``: ``configs/<c>.json`` (the deployment as it is
  run) and ``configs/<c>.py`` (its builder, ``build(cfg, rng)``);
* traffic mix ``<t>``: ``traffic/<t>.json``, whose ``loop`` key names the
  general loop ``loops/<loop>.py`` (``run(ctx)``);
* metric ``<m>``: ``metrics/<m>.py`` (``read(run)``);
* limits of the correctness check for cell ``<w>``: ``limits/<w>.json``.

Adding one of these is adding files and entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent.parent      # benchmarks/chip


class UnknownName(KeyError):
    """A cell, configuration, traffic mix or metric that has no entry or
    no file."""

    def __str__(self):
        return str(self.args[0])


def load_benchmark(root: Path) -> dict:
    """``BENCHMARK.json`` at the checkout's root."""
    path = root / "BENCHMARK.json"
    with open(path) as f:
        return json.load(f)


def _entry(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(sorted(e["name"] for e in entries))
    raise UnknownName(f"unknown {what} {name!r}; known: {known}")


def cell(bench: dict, name: str) -> dict:
    """The ``workloads`` entry of cell ``name``."""
    return _entry(bench["workloads"], name, "workload")


def _json(path: Path, what: str, name: str) -> dict:
    if not path.is_file():
        raise UnknownName(f"no file for {what} {name!r}: {path}")
    with open(path) as f:
        return json.load(f)


def _module(path: Path, what: str, name: str) -> ModuleType:
    if not path.is_file():
        raise UnknownName(f"no file for {what} {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"psbench_{what}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(bench: dict, name: str, base: Path = HERE
           ) -> tuple[dict, ModuleType]:
    """(configuration as run, builder module) of configuration ``name``."""
    entry = _entry(bench["configs"], name, "config")
    cfg = _json(base.parent.parent / entry["file"], "config", name)
    return cfg, _module(base / "configs" / f"{name}.py", "config", name)


def traffic(name: str, base: Path = HERE) -> tuple[dict, ModuleType]:
    """(mix parameters, general loop module) of traffic mix ``name``."""
    mix = _json(base / "traffic" / f"{name}.json", "traffic", name)
    return mix, _module(base / "loops" / f"{mix['loop']}.py", "loop",
                        mix["loop"])


def limits(name: str, base: Path = HERE) -> dict:
    """{check name: limit} of cell ``name``."""
    return _json(base / "limits" / f"{name}.json", "limits", name)


def metrics(bench: dict, cell_name: str, traced: bool) -> list[dict]:
    """The metrics a run of ``cell_name`` reports: the end-to-end ones
    untraced, the per-layer ones traced. A metric with a ``workloads`` key
    is reported in those cells; one without it wherever the end-to-end
    metric it belongs to is."""
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not traced:
        return e2e
    here = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and ("workloads" in m or m["moves"] in here)]


def reader(name: str, base: Path = HERE) -> ModuleType:
    """The module that reads metric ``name`` (its ``read(run)``)."""
    return _module(base / "metrics" / f"{name}.py", "metric", name)
