#!/usr/bin/env python3
"""The two readings a limit is set from (builder's instructions, "How
correct is decided", steps 3 to 5), in one process.

For each of ``--seeds`` seeds: weights and sampling from the seed, as
many proved rounds of the cell as a window plays, through the timed
path; every distinct decide row is the run's own sample.  With
``--program-control-seeds`` the program is booted a second time with its
own lower-precision path switched on (``quantization="int4"``) and
plays the first seeds again.  Then, with the program's state freed, the
reference over each sample: the program's numbers (lower reading: their
largest), on the first ``--control-seeds`` seeds the control's at the
same positions (the reference in the program's place at the precision
below the stated one; upper reading: its smallest), and the program's
own lower-precision path's.  With ``--fault-seeds`` the timed path is
broken underneath (a served character altered where it is produced).
``--dump`` keeps every compared position (reference logits, control
logits, served token) under ``chiprun_out/`` for a look off the chip.

    python3 benchmark/tools/limits.py --seeds 12 --control-seeds 8
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(HERE))

from lib import correct, system, window  # noqa: E402


def say(msg: str) -> None:
    print(msg, flush=True)


def reseed(sysm: system.System, seed: int) -> None:
    """New weights and a new sampling stream in the booted engine."""
    import jax

    sysm.remake_weights(seed)
    sysm.restore_sampling_state(jax.random.PRNGKey(seed % 2 ** 31))
    sysm.seed = seed
    sysm._next_game = 0


def alter_one_character(text: str) -> str:
    """The fault of a token altered where it is produced: the 6th
    character inside the first string of an answer becomes another
    letter the grammar allows there."""
    at = text.find('":"') + 3 + 5
    if text.startswith('{"internal_strategy":"') and at < len(text) - 1 \
            and text[at] not in '"\\' and text[at - 1] != "\\":
        return text[:at] + ("x" if text[at] != "x" else "y") + text[at + 1:]
    return text


def play_seeds(config: dict, traffic: dict, seeds: list, fault_seeds: int) -> dict:
    """Boot once, then for each seed prove as many rounds as a window
    plays; returns per seed the rows a run would compare."""
    sysm = system.System(config, traffic, seeds[0], log=say)
    games = window.driver_for(sysm)
    games.play(games.draw())           # compiles or loads the declared shapes
    sysm.calls.clear()
    kinds = traffic["compare"]["kinds"]
    out = {}
    for n, seed in enumerate(seeds):
        reseed(sysm, seed)
        t0 = time.perf_counter()
        proved = window.warm_up(games)          # as a run's set-up proves rounds
        calls = [c for recipe in games.proved for c in recipe["calls"]]
        out[seed] = {
            "sound": correct.distinct_rows(calls, kinds),
            "invalid": correct.invalid_rows(calls),
            "calls": len(calls), "retried": proved["rounds_passed_over"],
            "off_band": proved["rounds_off_band"],
            "weights_seed": sysm.weights_seed,
        }
        if n < fault_seeds:
            sysm._alter = alter_one_character
            made = games.play(games.draw())
            sysm._alter = None
            out[seed]["fault"] = correct.distinct_rows(made, kinds)
            sysm.calls.clear()
        say(f"seed {seed}: {len(calls)} calls, {len(out[seed]['sound'])} rows to compare, "
            f"invalid {out[seed]['invalid']}, rounds passed over {out[seed]['retried']}, "
            f"off the band {out[seed]['off_band']}, "
            f"{time.perf_counter() - t0:.1f}s")
    sysm.close()
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="qwen3-8b-int8.lockstep")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=8)
    ap.add_argument("--program-control-seeds", type=int, default=0)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--base-seed", type=int, default=2147300000)
    ap.add_argument("--dump", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="the tiny files under benchmark/tests, any device")
    args = ap.parse_args()

    import jax
    import numpy as np

    devices = jax.devices()
    say(f"devices: {devices[0].platform} {devices[0].device_kind} x{len(devices)}")
    if devices[0].platform != "tpu" and not args.rehearse:
        return 2
    config_name, traffic_name = args.workload.split(".", 1)
    sub = ("benchmark", "tests") if args.rehearse else ("benchmark",)
    config = system.load_json(os.path.join(ROOT, *sub, "configs", config_name + ".json"))
    traffic = system.load_json(os.path.join(ROOT, *sub, "traffic", traffic_name + ".json"))
    seeds = [args.base_seed + 104729 * i for i in range(args.seeds)]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    readings = []

    def read(n: int, seed: int, played: dict, side: str) -> None:
        """Reference over one seed's rows; ``side`` is ``sound`` (with the
        control and the fault beside it) or ``program_control``."""
        t0 = time.perf_counter()
        row = next((r for r in readings if r["seed"] == seed), None)
        if row is None:
            row = {"seed": seed}
            readings.append(row)
        weights_seed = played["weights_seed"]
        table = correct.positions(config, traffic, weights_seed, played["sound"])
        row[side] = dict(correct.numbers(table), invalid_rows=played["invalid"])
        keep = {"scores": table["scores"].astype(np.float16), "token": table["token"],
                "temp": table["temp"], "row": table["row"]}
        if side == "sound" and n < args.control_seeds:
            low = correct.positions(config, traffic, weights_seed, played["sound"],
                                    config["control"]["weights"])
            row["control"] = correct.numbers(correct.as_control(table, low, seed))
            keep["low_scores"] = low["scores"].astype(np.float16)
        if "fault" in played:
            row["fault"] = correct.numbers(
                correct.positions(config, traffic, weights_seed, played["fault"]))
        row[side + "_reference_s"] = round(time.perf_counter() - t0, 2)
        for judged in (side, "control", "fault"):
            if judged in row and "correct" not in row[judged]:
                numbers = dict({"invalid_rows": 0}, failed_rows=0, **row[judged])
                row[judged]["correct"] = correct.verdict(numbers, config["limits"])[0]
        say("reading " + json.dumps(row))
        if args.dump:
            np.savez_compressed(
                os.path.join(out_dir, f"positions_{config_name}_{side}_{seed}.npz"), **keep)
        with open(os.path.join(out_dir, f"limits_{config_name}.json"), "w") as f:
            json.dump(readings, f, indent=1)

    played = play_seeds(config, traffic, seeds, args.fault_seeds)
    for n, seed in enumerate(seeds):
        read(n, seed, played[seed], "sound")
    if args.program_control_seeds:
        low_config = copy.deepcopy(config)
        low_config["program"]["engine"]["quantization"] = config["control"]["weights"]
        try:
            lowered = play_seeds(low_config, traffic,
                                 seeds[: args.program_control_seeds], 0)
            for n, seed in enumerate(lowered):
                read(n, seed, lowered[seed], "program_control")
        except Exception as e:  # a control that crashes has failed: say so, keep the readings
            say(f"the program's own lower-precision path did not run: {e!r}\n"
                + traceback.format_exc()[-3000:])

    for side in ("sound", "control", "program_control", "fault"):
        verdicts = [r[side]["correct"] for r in readings if side in r]
        if verdicts:
            say(f"{side}: correct on {sum(verdicts)} of {len(verdicts)} seeds")
    for name in config["limits"]:
        sound = [r["sound"][name] for r in readings if name in r.get("sound", {})]
        if not sound:
            continue
        line = f"{name}: lower reading (largest of {len(sound)} sound seeds) {max(sound)}"
        for side in ("control", "program_control", "fault"):
            got = [r[side][name] for r in readings if name in r.get(side, {})]
            if got:
                line += f"; {side} smallest of {len(got)}: {min(got)}"
        say(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
