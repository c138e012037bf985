"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

reads BENCHMARK.json at the root of the checkout and finds everything else
BY NAME: the cell's configuration file (its `file`), its traffic mix
(benchmark/traffic/<traffic>.json), the driver of the configuration's kind
(benchmark/drivers/<kind>.py), the configuration's plain reference
(benchmark/reference/<config>.py) and one reader per per-layer metric
(benchmark/metrics/<name>.py). No name of a cell, configuration, mix or
metric appears in code, so a later PR adds any of them by adding files and
entries. A name with no file is an error that names the missing path.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result. `--rehearse` runs the same control flow at the tiny sizes
each data file gives under `rehearsal`, on the CPU: it says platform cpu, is
for finding faults in the harness, and measures nothing.

The last line of stdout is the object the driver reads; `check_line` holds
it to the contract first, and a line that fails is not printed (exit 4).
Earlier lines are for the reader. Exit 3: no device plane in the trace.
"""
import argparse
import importlib
import importlib.util
import json
import os
import resource
import shutil
import sys
import time

T0 = time.perf_counter()    # set-up is counted from here
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# a rehearsal's shares are worked out against this chip's peaks, so that the
# readers run; its numbers mean nothing
REHEARSAL_PEAKS = "TPU v5 lite"


def load_json(path):
    full = os.path.join(ROOT, path)
    if not os.path.isfile(full):
        sys.exit(f"benchmark: no file {path}")
    with open(full) as f:
        return json.load(f)


def load_by_name(folder, name):
    """The module benchmark/<folder>/<name>.py; names need not be Python
    identifiers, so this goes by path."""
    path = os.path.join(HERE, folder, name + ".py")
    if not os.path.isfile(path):
        sys.exit(f"benchmark: no file benchmark/{folder}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{name}".replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(dotted):
    mod, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


class Run:
    """What a driver and a metric reader get: the cell, its data files and
    the run's arguments."""

    def __init__(self, args, manifest, cell):
        self.cell, self.seed, self.seconds = cell, args.seed, args.seconds
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.chips = cell["chips"]
        self.sweep = [float(r) for r in args.sweep.split(",") if r]
        entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
        self.config = load_json(entry["file"])
        self.traffic = load_json(f"benchmark/traffic/{cell['traffic']}.json")
        if self.rehearse:
            small = dict(self.config.get("rehearsal", {}))
            self.config["run"].update(small.pop("run", {}))
            self.config.update(small)
            self.traffic.update(self.traffic.get("rehearsal", {}))
        run = self.config["run"]
        self.model_kwargs = {kw: self.config[key] for kw, key
                             in run["config_kwargs"].items()}
        self.model_kwargs.update(run["config_overrides"])
        self.trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
        self.load, self.resolve = load_by_name, resolve

    def say(self, msg):
        print(f"[bench +{time.perf_counter() - T0:6.1f}s] {msg}", flush=True)


def peak_bytes(ctx, devices):
    """Peak memory on the fullest chip: the allocator's peak plus the
    temporaries of the program that needs most of them. The allocator's
    `peak_bytes_in_use` counts arguments and results and leaves out what a
    program needs while it runs (on the chip, PR 24: a train step whose
    compiled temporaries are 11.7 GB read 3.4 GB), so the largest
    `temp_size_in_bytes` among the process's loaded executables is added:
    an upper bound that assumes that program ran at the allocator's peak.
    The CPU rehearsal, which keeps no such statistic, gives the process's
    peak resident size."""
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    held = max(int(s["peak_bytes_in_use"]) for s in stats)
    temps, unread = [0], 0
    for exe in devices[0].client.live_executables():
        try:
            temps.append(int(exe.get_compiled_memory_stats()
                             .temp_size_in_bytes))
        except Exception:        # an executable without the statistic
            unread += 1
    ctx.say(f"memory: allocator peak {held} B + largest program's "
            f"temporaries {max(temps)} B ({len(temps) - 1} programs read, "
            f"{unread} without statistics)")
    return held + max(temps)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="", help="comma-separated rates: a "
                    "serving cell's knee sweep; prints a table, no result")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; debugging only")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from benchmark import check_line, trace_reduce
    manifest = load_json("BENCHMARK.json")
    check_line.check_manifest(manifest, ROOT)
    cell = check_line.cell_of(manifest, args.workload)
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if (platform != "tpu" and not args.rehearse) \
            or len(devices) < cell["chips"]:
        print(f"benchmark: {cell['name']} needs {cell['chips']} TPU chip(s)"
              f", jax found {len(devices)} {platform} device(s); refusing "
              f"to measure (--rehearse debugs the harness on the CPU)",
              file=sys.stderr)
        sys.exit(2)
    import paddle_tpu as paddle
    if args.rehearse:
        paddle.set_flags({"FLAGS_flash_attention_interpret": True})
    ctx = Run(args, manifest, cell)
    ctx.say(f"{cell['name']}: seed {args.seed}, {args.seconds}s, trace "
            f"{args.trace}, {platform} x{len(devices)} "
            f"({devices[0].device_kind}), compile cache "
            f"{paddle.device.compilation_cache_dir()}")
    used = devices[:cell["chips"]]
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    out = load_by_name("drivers", ctx.config["kind"]).run(ctx)
    if "sweep" in out:
        print(json.dumps(out))
        return

    values = dict(out["end_to_end"], setup_s=out["setup_end"] - T0)
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(used), "memory_peak_bytes": peak_bytes(ctx, used)}
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"], "device": device}
    if ctx.trace:
        try:
            where = {} if platform == "tpu" else {
                "device_prefix": "/host:CPU", "op_line": None}
            red = trace_reduce.reduce(trace_reduce.load(ctx.trace_dir),
                                      cell["chips"], **where)
        except trace_reduce.NoDeviceTrace as e:
            print(f"benchmark: {e}", file=sys.stderr)
            sys.exit(3)
        shutil.rmtree(ctx.trace_dir)
        ctx.say(f"trace: window {red['window_s']:.4f}s, busy per device "
                f"{red['per_device_busy_s']}, longest gap "
                f"{red['longest_gap_s'] * 1e3:.2f} ms")
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
        record = dict(out["record"], trace=red, chips=cell["chips"],
                      model=ctx.model_kwargs, end_to_end=values,
                      device_kind=(device["kind"] if platform == "tpu"
                                   else REHEARSAL_PEAKS))
        for m in check_line.metrics_of(manifest, cell["name"], "per_layer"):
            v = load_by_name("metrics", m["name"]).read(record)
            if v is not None:
                values[m["name"]] = v
    units = {m["name"]: m["unit"] for g in ("end_to_end", "per_layer")
             for m in manifest[g]}
    line["metrics"] = {k: {"value": float(v), "unit": units[k]}
                       for k, v in values.items()}
    try:
        check_line.check_line(manifest, cell["name"], ctx.trace, line)
    except check_line.BadLine as e:
        print(f"benchmark: the last line is not what the driver reads: {e}\n"
              f"{json.dumps(line)}", file=sys.stderr)
        sys.exit(4)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
