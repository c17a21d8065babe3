#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Boots the cell's configuration through the program's normal entry
points with weights, games and sampling drawn from ``--seed``, plays
rounds until enough are proved to be the work the cell's traffic file
declares (the first is set-up's, and the warm-up), plays the proved ones
again as whole rounds for ``--seconds`` (the window), frees the program,
compares what the window served with the plain reference the
configuration's file names, and prints one JSON
object as the last line of standard output.  No accelerator, or fewer
chips than the cell asks for, is a non-zero exit and no result line.
The harness is data: a cell, a configuration (with the reference and
the cost model its file names), a traffic mix (and the driver of its
``mode``) and a per-layer metric (and its reader) are files found by
the names in ``BENCHMARK.json``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)   # the program (bcg_tpu)
sys.path.insert(0, HERE)   # lib, readers


def log(msg: str) -> None:
    sys.stderr.write(f"bench[{time.perf_counter() - T_START:7.1f}s] {msg}\n")
    sys.stderr.flush()


def load(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> tuple:
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{[w['name'] for w in bench['workloads']]}")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    bench_dir = bench["paths"][0]
    return (cell, load(entry["file"]),
            load(os.path.join(bench_dir, "traffic", cell["traffic"] + ".json")))


def metrics_for(bench: dict, group: str, cell_name: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def metric_file(bench: dict, name: str) -> dict:
    return load(os.path.join(bench["paths"][0], "metrics", name + ".json"))


def end_to_end(win: dict, setup_s: float) -> dict:
    """The three numbers a user of the system sees, over all the work
    and all the time of the window."""
    return {
        "decisions_per_s": win["decisions"] / win["seconds"],
        "round_s": win["seconds"] * win["games_in_flight"] / win["game_rounds"],
        "setup_s": setup_s,
    }


def read_per_layer(bench: dict, cell_name: str, ctx: dict) -> dict:
    out = {}
    for m in metrics_for(bench, "per_layer", cell_name):
        spec = metric_file(bench, m["name"])
        reader = importlib.import_module("readers." + spec["reader"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:       # nothing to read: left out of the line
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(bench: dict, cell: dict, config: dict, traffic: dict, seed: int,
             seconds: float, traced: bool, device: dict, fault=None) -> dict:
    """Everything of a run after the look for a chip; returns the result
    line's object.  ``fault`` (tests only) alters the texts an engine
    call produced, where it produced them."""
    from lib import correct, spans, system, trace, window

    sysm = system.System(config, traffic, seed, log=log)
    sysm._alter = fault
    log(f"booted in {sysm.boot_s:.1f}s (weights {sysm.weights_s:.1f}s)")
    games = window.driver_for(sysm)
    proved = window.warm_up(games)
    setup_s = proved["setup_end"] - T_START     # to the end of the first round played
    log(f"set-up done: {setup_s:.1f}s to the first round's end, "
        f"{time.perf_counter() - proved['setup_end']:.1f}s of proving after it; "
        f"{proved['rounds_passed_over']} round(s) passed over and "
        f"{proved['rounds_off_band']} off the band, "
        f"backend compile {sysm.compiles.backend_s:.1f}s, "
        f"cache hits {sysm.compiles.cache_hits} misses {sysm.compiles.cache_misses}")

    counters0 = system.program_counters()
    trace_dir = os.path.join(HERE, ".trace")
    if traced:
        trace.start(trace_dir)
    try:
        win = window.measure(games, seconds)
    finally:
        if traced:
            trace.stop()
    log(f"window: {win['game_rounds']} game round(s) in {win['seconds']:.3f}s")
    counters1 = system.program_counters()
    device = dict(device, memory_peak_bytes=sysm.memory_peak_bytes())
    calls = list(sysm.calls)
    boot = {"boot_s": sysm.boot_s, "weights_s": sysm.weights_s, **proved}
    invalid = correct.invalid_rows(calls)
    sample = correct.distinct_rows(calls, traffic["compare"]["kinds"])
    weights_seed = sysm.weights_seed
    sysm.close()

    t0 = time.perf_counter()
    numbers = correct.compare_rows(config, traffic, weights_seed, sample)
    numbers["invalid_rows"] = invalid
    numbers["failed_rows"] = win["failed"]
    ok, compared = correct.verdict(numbers, config["limits"])
    log(f"reference over {numbers['greedy_tokens']} greedy and "
        f"{numbers['sampled_tokens']} sampled tokens of {len(sample)} rows: "
        f"{time.perf_counter() - t0:.1f}s")

    result = {
        "correct": bool(ok),
        "attempted": win["rows"],
        "failed": win["failed"] + invalid,
    }
    if traced:
        reduced = trace.reduce(trace_dir)
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        ctx = {
            "config": config, "traffic": traffic, "cell": cell, "device": device,
            "window": win, "calls": calls, "spans": spans, "trace": reduced,
            "boot": boot,
            "counters": {k: counters1.get(k, 0) - counters0.get(k, 0)
                         for k in counters1},
        }
        result["metrics"] = read_per_layer(bench, cell["name"], ctx)
        result["breakdown"] = reduced["breakdown"]
    else:
        values = end_to_end(win, setup_s)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_for(bench, "end_to_end", cell["name"])
        }
    result["device"] = device
    result["compared"] = compared   # each number compared beside its limit: last
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    bench = load("BENCHMARK.json")
    cell, config, traffic = find_cell(bench, args.workload)
    traced = bool(args.trace)
    if traced:   # counters a per-layer metric's file asks the program to keep
        for m in metrics_for(bench, "per_layer", cell["name"]):
            for k, v in metric_file(bench, m["name"]).get("env", {}).items():
                os.environ[k] = v

    import jax

    # Every program of a run goes into the persistent cache, the small
    # ones too: the second run of a cell in a checkout compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if device["platform"] != "tpu" or device["count"] != cell["chips"]:
        log(f"cell {cell['name']} needs {cell['chips']} TPU chip(s); JAX reports "
            f"{json.dumps(device)}: no result")
        return 3

    from lib import peaks

    peaks.peaks_for(device["kind"])   # an unknown device is an error, early
    result = run_cell(bench, cell, config, traffic, args.seed, args.seconds,
                      traced, device)
    for name, row in result["compared"].items():
        sys.stderr.write(f"compared {name}: {row['value']} (limit {row['limit']})\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
