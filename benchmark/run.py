"""Run one benchmark cell once and print the contract's result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything the run needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``benchmark/configs/<config>.json`` (which names the
driver, ``benchmark/drivers/<driver>.py``), its traffic in
``benchmark/traffic/<cell>.json``, and each per-layer metric's reader in
``benchmark/metrics/<metric>.py``. A run set-up, warms up, measures for
``--seconds``, checks what the window produced against the plain
reference, and prints one JSON line last on stdout. It runs on the TPU,
or on the CPU only where ``JAX_PLATFORMS=cpu`` asks for it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(bench: dict, cell: str):
    work = next((w for w in bench["workloads"] if w["name"] == cell), None)
    if work is None:
        raise SystemExit(f"run.py: no workload {cell!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == work["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", f"{work['traffic']}.json")) as f:
        traffic = json.load(f)
    return work, config, traffic


def reports(metric: dict, cell: str, e2e_names=None) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads`` key
    lists, else every cell (an end-to-end metric) or every cell that
    reports the end-to-end metric it moves (a per-layer one)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


class Ctx:
    """What a driver is handed: the cell's files, the run's arguments, and
    the harness's window, spans, phases and compile counter."""

    def __init__(self, work, config, traffic, args, scratch):
        from common import Compiles, Spans

        self.work, self.config, self.traffic = work, config, traffic
        self.cell, self.chips = work["name"], work["chips"]
        self.seed, self.seconds = args.seed, args.seconds
        self.traced, self.control = bool(args.trace), bool(args.control)
        self.scratch = scratch
        self.trace_dir = os.path.join(scratch, "trace")
        self.spans = Spans(self.traced)
        self.compiles = Compiles()
        self.phases: dict = {}
        self._window = None

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        yield
        self.phases[name] = self.phases.get(name, 0.0) + time.perf_counter() - t0

    def window(self):
        from common import Window

        self._window = Window(self.traced, self.trace_dir)
        return self._window

    def memory_peak(self) -> int:
        from common import memory_peak_bytes

        return memory_peak_bytes(self.chips)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the control (the reference with a broken "
                         "guarantee) in the program's place in the comparison, "
                         "so that correct reads false; not for timed runs")
    return ap.parse_args(argv)


def run_cell(args, bench: dict, overrides=None) -> dict:
    """One run: returns the result line as a dict (and the checks)."""
    work, config, traffic = cell_spec(bench, args.workload)
    for key, val in (overrides or {}).items():
        (config if key in config else traffic)[key] = val

    import jax

    from common import device_info

    devs = jax.devices()
    if devs[0].platform != "tpu" and os.environ.get("JAX_PLATFORMS", "") != "cpu":
        raise SystemExit(f"run.py: no TPU (JAX found {devs[0].platform!r}); "
                         "set JAX_PLATFORMS=cpu to rehearse on the CPU")
    if len(devs) < work["chips"]:
        raise SystemExit(f"run.py: the cell needs {work['chips']} chips, "
                         f"JAX found {len(devs)}")
    from crdt_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    scratch = tempfile.mkdtemp(prefix="bench-")
    try:
        ctx = Ctx(work, config, traffic, args, scratch)
        driver = load_module(os.path.join(HERE, "drivers", f"{config['driver']}.py"),
                             f"drivers_{config['driver']}")
        res = driver.run(ctx)
        win = ctx._window
        setup_s = win.t0 - T_START
        e2e_names = [m["name"] for m in bench["end_to_end"]
                     if reports(m, args.workload)]
        device = device_info(work["chips"])
        device["memory_peak_bytes"] = res["memory_peak_bytes"]
        metrics, breakdown = {}, None
        if not ctx.traced:
            for m in bench["end_to_end"]:
                if m["name"] not in e2e_names:
                    continue
                val = setup_s if m["name"] == "setup_s" else res["e2e"][m["name"]]
                if val is not None:
                    metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        else:
            red = None
            path = win.xplane()
            if path is not None and device["platform"] == "tpu":
                import trace_reduce

                red = trace_reduce.reduce(path)
                device["busy_s"] = red["busy_s"]
                device["window_s"] = red["window_s"]
                breakdown = trace_reduce.breakdown(red)
                log("trace: window_s", red["window_s"], "busy_s", red["busy_s"],
                    "devices", red["devices"])
                for name, secs in sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:10]:
                    log(f"trace op {secs:.6f}s n={red['op_n'][name]} {name[:300]}")
                for name in red["op_s"]:
                    if "custom-call" in name:   # kernels, by the names the compiler gave them
                        log(f"trace kernel {red['op_s'][name]:.6f}s n={red['op_n'][name]} "
                            f"{name[:200]} :: {red['op_detail'].get(name, '')[:400]}")
            rctx = {"cell": args.workload, "config": config, "traffic": traffic,
                    "res": res, "spans": ctx.spans.as_dict(), "trace": red,
                    "device": device, "peaks": _peaks(device)}
            for m in bench["per_layer"]:
                if not reports(m, args.workload, e2e_names):
                    continue
                reader = load_module(os.path.join(HERE, "metrics", f"{m['name']}.py"),
                                     "metric_" + m["name"].replace(".", "_"))
                val = reader.read(rctx)
                if val is not None:
                    metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        checks = res["checks"]
        correct = all(lim is None or val <= lim for _n, val, lim in checks)
        line = {"correct": bool(correct), "attempted": res["attempted"],
                "failed": res["failed"], "metrics": metrics, "device": device}
        if breakdown is not None:
            line["breakdown"] = breakdown
        line["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
        extra = {"setup": dict(ctx.phases, setup_s=setup_s), "window_s": res["window_s"],
                 "counters": res["counters"], "spans": ctx.spans.as_dict(),
                 "e2e": res["e2e"]}
        return {"line": line, "extra": extra}
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


def _peaks(device: dict):
    """The chip's published peaks; an unknown TPU is an error. A CPU run
    has none: no device metric is read from it."""
    if device["platform"] != "tpu":
        return None
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device["kind"] not in table["devices"]:
        raise SystemExit(f"run.py: no peaks for device {device['kind']!r} in peaks.json")
    return table["devices"][device["kind"]]


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [HERE, ROOT]
    from common import use_checkout_cache

    use_checkout_cache()  # before JAX reads its environment
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = run_cell(args, bench)
    line, extra = out["line"], out["extra"]
    log("run:", json.dumps(extra, default=float))
    for name, c in line["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(line, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
