"""Run one benchmark cell once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name from ``BENCHMARK.json``: the
configuration file it names, ``chipbench/traffic/<traffic>.json`` (whose
``driver`` names ``chipbench/drivers/<driver>.py``),
``chipbench/limits/<cell>.json`` (the limits of the correctness check),
``chipbench/models/<client_model>.py`` (the plain reference of the
configuration's model) and, with ``--trace 1``,
``chipbench/metrics/<metric>.py`` for each per-layer metric of the cell.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (end-to-end with ``--trace 0``,
per-layer with ``--trace 1``), ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number the correctness check
compared, with its limit. The same numbers end standard error. Without
a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "chipbench"
# fixed paths inside the checkout (gitignored by chipbench/.gitignore)
CACHE_DIR = BENCH / ".cache" / "jax"
OUT_DIR = BENCH / "out"


class BenchError(SystemExit):
    """A run that cannot produce a result: message to stderr, exit 2."""

    def __init__(self, msg: str):
        print(f"chipbench: {msg}", file=sys.stderr, flush=True)
        super().__init__(2)


def process_age_s() -> float:
    """Seconds since this process started (Linux /proc)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")


def load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path):
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


def find_cell(manifest: dict, workload: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == workload:
            return cell
    raise BenchError(f"no workload {workload!r} in BENCHMARK.json")


def cell_metrics(manifest: dict, cell: dict, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    mine = []
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest[kind]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                mine.append(m)
        elif kind == "end_to_end" or cell["name"] in e2e[m["moves"]].get(
                "workloads", [cell["name"]]):
            mine.append(m)
    return mine


def device_gate(chips: int):
    """The chips this cell runs on; exits (no result) without them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    """The chip's published peaks (``chipbench/peaks.json``); a kind not
    in the table is an error, never a default."""
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         "chipbench/peaks.json")
    return table[kind]


def use_compile_cache():
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # every program, however quick to compile, comes from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class Context:
    """What a driver and the per-layer readers get about their cell."""

    def __init__(self, args, manifest, cell, config, traffic, limits,
                 model, peaks, devices):
        self.args, self.manifest, self.cell = args, manifest, cell
        self.config, self.traffic, self.limits = config, traffic, limits
        self.model, self.peaks, self.devices = model, peaks, devices
        self.seed, self.seconds = args.seed, args.seconds
        self.trace_dir = OUT_DIR / "trace" / cell["name"]
        self.process_age_s = process_age_s


def emit(ctx, res: dict):
    """Print the check lines to stderr and the result line to stdout."""
    checks = {k: {"value": c["value"] if math.isfinite(c["value"]) else None,
                  "limit": c["limit"]} for k, c in res["checks"].items()}
    for name, c in checks.items():
        ok = c["value"] is not None and c["value"] <= c["limit"]
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr)
    kind = "per_layer" if ctx.args.trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(ctx.manifest, ctx.cell, kind):
        value = res["metrics"].get(m["name"])
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = ctx.devices[0]
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(ctx.devices),
                      "memory_peak_bytes": res["memory_peak_bytes"]}}
    if ctx.args.trace:
        out["device"].update(busy_s=res["busy_s"], window_s=res["window_s"])
        out["breakdown"] = res["breakdown"]
    out["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def resolve(root: Path, workload: str) -> dict:
    """The cell's entries and files, found by name under ``root``:
    ``manifest``, ``cell``, ``config``, ``traffic``, ``limits``,
    ``model`` (module) and ``driver`` (module)."""
    manifest = load_json(root / "BENCHMARK.json")
    cell = find_cell(manifest, workload)
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = load_json(root / conf["file"])
    bench = root / "chipbench"
    traffic = load_json(bench / "traffic" / f"{cell['traffic']}.json")
    return dict(
        manifest=manifest, cell=cell, config=config, traffic=traffic,
        limits=load_json(bench / "limits" / f"{cell['name']}.json"),
        model=load_module(bench / "models" / f"{config['client_model']}.py",
                          "chipbench_model"),
        driver=load_module(bench / "drivers" / f"{traffic['driver']}.py",
                           "chipbench_driver"))


def metric_readers(root: Path, manifest: dict, cell: dict) -> dict:
    """name -> module, for each per-layer metric of the cell."""
    return {m["name"]: load_module(
        root / "chipbench" / "metrics" / f"{m['name']}.py", "chipbench_metric")
        for m in cell_metrics(manifest, cell, "per_layer")}


def context(args) -> Context:
    """Everything about the cell, found by name, and its chips; exits
    without a result where a file or a chip is missing."""
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError("the program under test (src/repro) is not in "
                         "this checkout")
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    found = resolve(ROOT, args.workload)
    devices = device_gate(found["cell"]["chips"])
    peaks = peaks_for(devices[0].device_kind)
    use_compile_cache()
    ctx = Context(args, found["manifest"], found["cell"], found["config"],
                  found["traffic"], found["limits"], found["model"], peaks,
                  devices)
    ctx.driver = found["driver"]
    return ctx


def main(argv=None):
    ctx = context(parse(argv))
    res = ctx.driver.run(ctx)
    if ctx.args.trace:
        res["metrics"] = {
            name: r.read(res["layer_ctx"])
            for name, r in metric_readers(ROOT, ctx.manifest,
                                          ctx.cell).items()}
    emit(ctx, res)


if __name__ == "__main__":
    main()
