"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json`` in one new process: build the
system under test from the cell's configuration and traffic files (and
its own file under ``cells/``, if it has one),
warm up every program the traffic uses (set-up), measure for
``--seconds``, check the outputs against the plain reference, and print
one JSON object as the last line of standard output. ``--trace 0``
prints the cell's end-to-end metrics; ``--trace 1`` profiles the last
seconds of the window and prints its per-layer metrics. Diagnostics go
to standard error as ``bench: {...}`` lines.

It needs a TPU and as many chips as the cell asks for, and exits with a
non-zero code before building anything otherwise. ``--rehearse`` runs
the same code on the CPU at the tiny widths of the configuration's
``rehearse`` group: it proves the control flow and prints
``"platform": "cpu"`` and no device metric. ``--rate-rps`` overrides an
open-loop cell's rate, for the sweep that finds its knee; the driver
never passes it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def fail(msg: str, code: int = 2):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def for_cell(metrics, cell: str):
    return [m for m in metrics
            if "workloads" not in m or cell in m["workloads"]]


@functools.lru_cache(maxsize=None)
def reader_module(fname: str):
    mod_spec = importlib.util.spec_from_file_location(
        "layer_metrics_" + fname[:-3],
        os.path.join(HERE, "layer_metrics", fname))
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(spec: str):
    """``file.py:function`` under ``benchmark/layer_metrics``."""
    fname, func = spec.split(":")
    return getattr(reader_module(fname), func)


def layer_readers() -> dict:
    """{metric name: reader}: one descriptor file per metric, found by
    listing the directory; nothing is registered anywhere else."""
    out = {}
    folder = os.path.join(HERE, "layer_metrics")
    for f in sorted(os.listdir(folder)):
        if f.endswith(".json"):
            d = read_json(folder, f)
            out[d["name"]] = d["reader"]
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rate-rps", type=float, default=None)
    args = ap.parse_args()

    bench = read_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        fail(f"no workload {args.workload!r}; have {sorted(cells)}")
    cell_entry = cells[args.workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = read_json(ROOT, configs[cell_entry["config"]]["file"])
    traffic = read_json(HERE, "traffic", cell_entry["traffic"] + ".json")
    # what belongs to this cell alone (rate, slots); a cell may have none
    cell_file = os.path.join(HERE, "cells", args.workload + ".json")
    cell = read_json(cell_file) if os.path.exists(cell_file) else {}
    chips = int(cell_entry["chips"])

    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        fail(f"no paddle_tpu package beside {HERE}: nothing to measure")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    # libtpu logs under /tmp unless told otherwise; keep them in the checkout
    os.makedirs(os.path.join(OUT, "tpu_logs"), exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(OUT, "tpu_logs"))
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        fail(f"needs a TPU; jax.devices()[0].platform is {platform!r} "
             f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). "
             f"--rehearse runs the control flow on the CPU.")
    if len(devices) < chips:
        fail(f"{args.workload} needs {chips} chips, found {len(devices)}")

    # the persistent compile cache: where the environment says, else at
    # a fixed path inside the checkout (the path is part of the key)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(OUT, "jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from harness import compile_clock, model, peaks, serve, trace_reduce, train
    clock = compile_clock.CompileClock()
    config = model.effective_config(config, args.rehearse)
    ctx = dict(config=config, traffic=traffic, cell=cell, chips=chips,
               seed=args.seed, seconds=args.seconds, trace=args.trace,
               rehearse=args.rehearse, rate_rps=args.rate_rps, clock=clock,
               t_start=T_START,
               trace_dir=os.path.join(OUT, "trace", args.workload))
    kinds = {"serve_open": serve.run, "serve_backlog": serve.run,
             "train": train.run}
    if traffic["kind"] not in kinds:
        fail(f"traffic kind {traffic['kind']!r} has no driver")
    obs = kinds[traffic["kind"]](ctx)

    dev = devices[0]
    device = {"platform": platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices[:chips])}
    on_device = platform == "tpu"
    if on_device:
        obs["peaks"] = peaks.peaks_for(dev.device_kind)
    line = {"correct": bool(obs["correct"]), "attempted": obs["attempted"],
            "failed": obs["failed"], "metrics": {}, "device": device}

    if not args.trace:
        values = dict(obs["end_to_end"], setup_s=obs["setup_s"])
        for m in for_cell(bench["end_to_end"], args.workload):
            if m["name"] not in values:
                fail(f"{args.workload} produced no {m['name']}", 3)
            line["metrics"][m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
    else:
        if on_device:
            if not obs["trace_path"]:
                fail("the profiler wrote no trace", 3)
            obs["trace"] = red = trace_reduce.reduce(
                trace_reduce.load(obs["trace_path"]))
            device["busy_s"], device["window_s"] = red.busy_s, red.window_s
            line["breakdown"] = trace_reduce.breakdown(red)
        readers = layer_readers()
        for m in for_cell(bench["per_layer"], args.workload):
            got = load_reader(readers[m["name"]])(obs)
            if got is None:
                # nothing to read (on the CPU: no trace, no peaks, no
                # memory statistics): leave the metric out
                continue
            got = got if isinstance(got, dict) else {"value": got}
            line["metrics"][m["name"]] = dict(got, unit=m["unit"])
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
