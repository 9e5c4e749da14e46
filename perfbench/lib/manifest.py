"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix, one driver,
one reference or one metric is a file of its own, found by name in the
directories under `paths` (and in this package): a later PR adds files and
entries and edits none.

    configs/<config>.json      the sizes as run, `reference` and `run` keys:
                               widths, depth, slots, the reference and the
                               control belong to the configuration alone
    traffic/<traffic>.json     parameters for the one generator, `driver`
    drivers/<driver>.py        run(ctx) -> the run's record
    references/<name>.py       the plain float32 reference
    metrics/<metric>.py        read(run) -> number, or None if nothing to read

What the router and the proxy are told (`serve_env`, the deployment's
`RAY_TPU_SERVE_*` settings) belongs to either: `run.serve_env` of the
configuration, and `serve_env` of a traffic file laid over it, the traffic
file winning (`lay_serve_env`). Overload traffic comes with the queue that
its deployment would run, and a cell that differs from another only there
needs no configuration of its own.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Manifest:
    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.data: Dict[str, Any] = json.load(f)
        dirs = [os.path.join(self.root, p) for p in self.data["paths"]]
        if PACKAGE_DIR not in dirs:
            dirs.append(PACKAGE_DIR)
        self.dirs: List[str] = dirs

    # ------------------------------------------------------------- entries
    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"perfbench: no workload {name!r} in BENCHMARK.json; "
                         f"it has {[w['name'] for w in self.data['workloads']]}")

    def config_entry(self, name: str) -> dict:
        return next(c for c in self.data["configs"] if c["name"] == name)

    def metrics_for(self, cell: str, kind: str) -> List[dict]:
        """The `end_to_end` or `per_layer` metrics this cell reports: those
        that list it under `workloads`, and those with no such key whose
        end-to-end metric (for a per-layer one: `moves`) the cell reports."""
        e2e = [m for m in self.data["end_to_end"]
               if "workloads" not in m or cell in m["workloads"]]
        if kind == "end_to_end":
            return e2e
        reported = {m["name"] for m in e2e}
        return [m for m in self.data["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in reported)]

    # --------------------------------------------------------------- files
    def find(self, sub: str, filename: str) -> str:
        for d in self.dirs:
            p = os.path.join(d, sub, filename)
            if os.path.isfile(p):
                return p
        raise SystemExit(f"perfbench: no {sub}/{filename} under {self.dirs}")

    def load_config(self, name: str) -> dict:
        entry = self.config_entry(name)
        with open(os.path.join(self.root, entry["file"])) as f:
            return json.load(f)

    def load_traffic(self, name: str) -> dict:
        with open(self.find("traffic", name + ".json")) as f:
            return json.load(f)

    def load_module(self, sub: str, name: str):
        return load_py(self.find(sub, name + ".py"))


def prepare_env(root: str, rehearsal: bool) -> None:
    """The environment every process of a run inherits. jax's persistent
    cache: where the machine says, else ONE fixed place in the checkout (the
    path is part of the key); every program is kept, however small and
    quick, so that a second run compiles nothing. A rehearsal is held to the
    CPU, with four virtual devices."""
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_compile_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")


SERVE_ENV_PREFIX = "RAY_TPU_SERVE_"


def lay_serve_env(ctx: dict) -> Dict[str, Dict[str, str]]:
    """The deployment's Serve settings under this cell's traffic, put into
    the environment before any process of the cluster exists, so that the
    proxy, the router and the replica inherit them: the configuration's
    `run.serve_env` first, the traffic file's `serve_env` over it. Says on
    the run's `[traffic]` line which are in force and where each came from,
    and leaves the same under `ctx["serve_env"]` for the run's record. A key
    that is no Serve setting ends the run."""
    cell = ctx["cell"]
    overridden = "serve_env" in ctx.get("overridden", ())
    layers = ((f"configuration {cell['config']}",
               ctx["config"]["run"].get("serve_env", {})),
              ("--override" if overridden else f"traffic {cell['traffic']}",
               ctx["traffic"].get("serve_env", {})))
    in_force: Dict[str, Dict[str, str]] = {}
    for origin, settings in layers:
        for key, value in settings.items():
            if not key.startswith(SERVE_ENV_PREFIX):
                raise SystemExit(
                    f"perfbench: serve_env of {origin} names {key!r}: only "
                    f"{SERVE_ENV_PREFIX}* settings may be stated there")
            in_force[key] = {"value": str(value), "from": origin}
    os.environ.update({k: v["value"] for k, v in in_force.items()})
    print("[traffic] serve_env in force: " + ("; ".join(
        f"{k}={v['value']} ({v['from']})" for k, v in in_force.items())
        or "none stated"), flush=True)
    ctx["serve_env"] = in_force
    return in_force


def load_py(path: str):
    """Import a file by path: metric and cell names carry dots and dashes,
    so they are not module names."""
    tag = "perfbench_file_" + "".join(
        ch if ch.isalnum() else "_" for ch in os.path.abspath(path))
    spec = importlib.util.spec_from_file_location(tag, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def apply_overrides(obj: dict, overrides: Optional[List[str]]) -> dict:
    """`key=value` pairs (value parsed as JSON) laid over a traffic file,
    for a sweep by hand; a run that used any says so in its result line."""
    out = dict(obj)
    for item in overrides or []:
        key, _, raw = item.partition("=")
        out[key] = json.loads(raw)
    return out
