"""Where the benchmark finds its own files.

``BENCHMARK.json`` at the root of the checkout names cells, configurations
and metrics; everything else is looked up BY NAME under ``benchmark/``:

    configs/<config>.json          one per configuration
    traffic/<mix>.json             one per traffic mix
    end_to_end/<metric>.json       one per end-to-end metric
    layer_metrics/<metric>.json    one per per-layer metric (names its reader)
    families/<family>.py           the program's ModelConfig and the byte
                                   counts of a model family
    reference/<family>.py          the plain float32 forward pass

A name that cannot be found is an error that lists what was looked for.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class ManifestError(SystemExit):
    """A file or name the benchmark needs is missing; exit code 2."""

    def __init__(self, msg: str):
        print(f"benchmark: {msg}", file=sys.stderr, flush=True)
        super().__init__(2)


def load_json(*parts: str) -> Dict[str, Any]:
    path = os.path.join(BENCH_DIR, *parts)
    if not os.path.isfile(path):
        have = sorted(os.listdir(os.path.dirname(path))) \
            if os.path.isdir(os.path.dirname(path)) else []
        raise ManifestError(f"looked for {os.path.relpath(path, ROOT)} and "
                            f"did not find it; that directory holds {have}")
    with open(path) as f:
        return json.load(f)


def load_manifest() -> Dict[str, Any]:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise ManifestError(f"no BENCHMARK.json at {ROOT}")
    with open(path) as f:
        return json.load(f)


def find_cell(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise ManifestError(f"no workload {name!r} in BENCHMARK.json; it has "
                        f"{[c['name'] for c in manifest['workloads']]}")


def cell_files(cell: Dict[str, Any]):
    """(configuration, traffic mix) of a cell, each from its own file."""
    return (load_json("configs", cell["config"] + ".json"),
            load_json("traffic", cell["traffic"] + ".json"))


def metrics_for(manifest: Dict[str, Any], cell_name: str, kind: str
                ) -> List[Dict[str, Any]]:
    """The manifest's ``end_to_end`` or ``per_layer`` entries that this
    cell reports: those without a ``workloads`` key, and those that list
    the cell."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


_MODULES: Dict[str, Any] = {}


def load_module(package: str, name: str):
    """``benchmark/<package>/<name>.py`` as a module, loaded from its file
    once a process.  The two packages found this way are keyed by a tier's
    ``family``: ``families`` (what the harness knows of a model family)
    and ``reference`` (the plain forward pass, which knows nothing of the
    harness)."""
    path = os.path.join(BENCH_DIR, package, name + ".py")
    if path in _MODULES:
        return _MODULES[path]
    if not os.path.isfile(path):
        have = sorted(f[:-3] for f in os.listdir(os.path.dirname(path))
                      if f.endswith(".py")) \
            if os.path.isdir(os.path.dirname(path)) else []
        raise ManifestError(f"{package} {name!r}: looked for "
                            f"{os.path.relpath(path, ROOT)} and did not "
                            f"find it; that directory holds {have}")
    spec = importlib.util.spec_from_file_location(f"{package}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _MODULES[path] = mod
    return mod


FAMILY_NAMES = ("model_config", "rehearsal_model", "weight_bytes_per_chip",
                "kv_bytes_per_token", "decode_step_bytes_per_chip")


def load_family(family: str):
    """What the harness knows of a model family
    (``families/<family>.py``): ``model_config``, ``rehearsal_model`` and
    the byte counts.  A name a family's file lacks is an error here, not
    in the middle of a run."""
    mod = load_module("families", family)
    missing = [n for n in FAMILY_NAMES if not callable(getattr(mod, n, None))]
    if missing:
        raise ManifestError(f"families/{family}.py has no {missing}; a "
                            f"family gives {list(FAMILY_NAMES)}")
    return mod


def load_callable(spec: str, package: str):
    """``module:function`` under ``benchmark/<package>/``."""
    mod_name, _, fn_name = spec.partition(":")
    path = os.path.join(BENCH_DIR, package, mod_name + ".py")
    if not os.path.isfile(path):
        raise ManifestError(
            f"reader {spec!r}: looked for "
            f"{os.path.relpath(path, ROOT)} and did not find it")
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    mod = importlib.import_module(f"{package}.{mod_name}")
    fn = getattr(mod, fn_name, None)
    if fn is None:
        raise ManifestError(f"reader {spec!r}: {mod_name}.py has no "
                            f"{fn_name}")
    return fn
