"""One cell, once, in a new process:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Places the compile cache, takes the chip (and fails where there is
none), hands the cell to its driver, turns what the driver observed
into the cell's metrics through their readers, and prints the result as
the last line of standard output. Everything that belongs to one
configuration, one traffic mix, one cell or one metric is a data file
found by its name; no such name stands in this file (README.md).
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
REHEARSAL_DIR = os.path.join(BENCH_DIR, "tests", "rehearsal")
NO_CHIP = 3


def load(data_dir: str, kind: str, name: str) -> dict:
    with open(os.path.join(data_dir, kind, name + ".json")) as f:
        return json.load(f)


def load_metric(kind: str, name: str) -> dict:
    """A metric's definition, with its reader's ``read`` function."""
    doc = load(BENCH_DIR, kind, name)
    doc["read"] = importlib.import_module(f"benchmark.readers.{doc['reader']}").read
    return doc


@dataclasses.dataclass
class Context:
    """What a driver is given, and the two services it calls back for."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    trace_dir: str
    run_file: str
    marks: list = dataclasses.field(default_factory=list)

    def key(self):
        """The run's PRNG key. ``--seed`` may pass 32 bits, a key's seed
        may not: the high bits seed it and the low 31 are folded in."""
        import jax

        return jax.random.fold_in(jax.random.key(self.seed >> 31),
                                  self.seed & 0x7FFFFFFF)

    def log(self, doc) -> None:
        print(json.dumps(doc), flush=True)

    def save(self, doc) -> None:
        """What is too long for a line, into this run's file under out/."""
        os.makedirs(os.path.dirname(self.run_file), exist_ok=True)
        with open(self.run_file, "w") as f:
            json.dump(doc, f)

    def mark(self, phase: str) -> None:
        """Seconds since the process started at which ``phase`` ended:
        where set-up goes, on an earlier line of the output."""
        self.marks.append([phase, round(time.monotonic() - self.t_start, 3)])

    @contextlib.contextmanager
    def profiler(self):
        """A profiler trace into this cell's directory under out/:
        TraceMe annotations on, Python's own tracer off (its events
        would be most of the file and say nothing a span does not)."""
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    def reduce_trace(self, labels) -> dict | None:
        from benchmark import trace_reduce

        path = trace_reduce.find_xplane(self.trace_dir)
        if path is None:
            return None
        return trace_reduce.reduce_trace(path, tuple(labels))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="dry run on the CPU over the tiny data under "
                         "tests/rehearsal; prints no metric under its own name")
    args = ap.parse_args()

    sys.path[0] = ROOT  # the checkout, not this directory
    data_dir = REHEARSAL_DIR if args.rehearse else BENCH_DIR
    cell = load(data_dir, "workloads", args.workload)
    ends = {name: load_metric("end_to_end", name) for name in cell["end_to_end"]}
    layers = {name: load_metric("layer_metrics", name) for name in cell["per_layer"]}
    for name, doc in layers.items():
        if doc["moves"] not in ends:
            raise SystemExit(
                f"{args.workload}: per-layer metric {name!r} moves "
                f"{doc['moves']!r}, which this cell does not report")

    from benchmark import costs
    from nanodiloco_tpu.utils import enable_compile_cache

    enable_compile_cache()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" and not args.rehearse:
        print(f"JAX reports platform {device['platform']!r}, not 'tpu': a "
              "measurement needs the chip (the dry run is --rehearse)",
              file=sys.stderr)
        return NO_CHIP
    if device["count"] < cell["chips"]:
        print(f"{args.workload} needs {cell['chips']} chip(s), JAX reports "
              f"{device['count']}", file=sys.stderr)
        return NO_CHIP

    ctx = Context(
        cell=cell, config=load(data_dir, "configs", cell["config"]),
        traffic=load(data_dir, "traffic", cell["traffic"]),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START,
        trace_dir=os.path.join(BENCH_DIR, "out", "trace", args.workload),
        run_file=os.path.join(BENCH_DIR, "out", "runs",
                              f"{args.workload}.{args.seed}.{args.trace}.json"),
    )
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    ctx.mark("imports_and_backend")
    obs = driver.run(ctx)
    ctx.mark("driver_done")
    ctx.log({"phases_end_s": ctx.marks})
    obs["device_kind"] = device["kind"]
    peaks = [d.memory_stats() or {} for d in jax.local_devices()]
    ctx.log({"memory_stats": peaks[0]})
    obs["memory_peak_bytes"] = max(p.get("peak_bytes_in_use", 0) for p in peaks)

    metrics = {}
    for name, doc in (layers if args.trace else ends).items():
        try:
            value = doc["read"](obs)
        except costs.UnknownDevice as e:
            if not args.rehearse:
                raise
            ctx.log({"rehearsal_skips": name, "because": str(e)})  # a CPU has no peaks
            continue
        if value is not None:
            shown = f"rehearsal.{name}" if args.rehearse else name
            metrics[shown] = {"value": value, "unit": doc["unit"]}
    device["memory_peak_bytes"] = obs["memory_peak_bytes"]
    result = {"correct": all(c["ok"] for c in obs["checks"]),
              "attempted": obs["attempted"], "failed": obs["failed"],
              "metrics": metrics, "device": device}
    tr = obs.get("trace")
    if tr:
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    if args.rehearse:
        result["rehearsal"] = True
    ctx.log({"checks": obs["checks"]})
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
