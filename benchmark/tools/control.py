#!/usr/bin/env python3
"""Where a train cell's limits come from, read on the chip in ONE process:
the cell's first window on many seeds (the sound readings), and on some of
them the control — the program's own pure-bfloat16 path (AMP O3: no float32
master weights) in the program's place, which has to come out as NOT correct.
The benchmark's own runs never run this.

    python3 benchmark/tools/control.py --workload <cell> --seeds 1 2 3 4 5 \\
        --control-seeds 1 2 3 [--earlier loss_rel_gap=4.1e-5 ...] \\
        [--write-traffic <file>]

Every limit is three times the sound runs' largest reading (``--earlier``
adds the largest of readings taken before, of the same number), rounded up to
two digits; the control's smallest reading of at least one number has to lie
three times above its limit, or this exits 1.  ``--write-traffic`` writes the
cell's traffic file with those limits and the readings they came from.

The references run first, before any program is loaded, as in a run; then
the program's window on every seed; then the control's.
"""
import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONTROL_OPT_LEVEL = "O3"


def round_up(x: float, digits: int = 2) -> float:
    if x <= 0:
        return 0.0
    scale = 10 ** (digits - 1 - math.floor(math.log10(x)))
    return math.ceil(x * scale) / scale


def limits_from(sound: dict, control: dict, earlier: dict):
    """``(limits, names the control fails, a line of text for each)``."""
    limits, fails, text = {}, [], []
    for name, values in sound.items():
        largest = max(values + ([earlier[name]] if name in earlier else []))
        limits[name] = round_up(3 * largest)
        line = (f"{name}: sound {min(values):.3g}..{max(values):.3g} "
                f"({len(values)} seeds)")
        if name in earlier:
            line += f", earlier readings' largest {earlier[name]:.3g}"
        got = control.get(name, [])
        if got:
            line += f", control {min(got):.3g}..{max(got):.3g}"
            if min(got) >= 3 * limits[name]:
                fails.append(name)
                line += " (fails, as it must)"
        text.append(line + f" -> limit {limits[name]:.3g}")
    return limits, fails, text


def main(argv=None, root: str = ROOT) -> int:
    from benchmark import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--earlier", nargs="*", default=[], metavar="NAME=VALUE")
    ap.add_argument("--write-traffic")
    args = ap.parse_args(argv)
    if not set(args.control_seeds) <= set(args.seeds):
        ap.error("--control-seeds have to be among --seeds")
    earlier = {k: float(v) for k, v in (e.split("=") for e in args.earlier)}

    loaded = harness.load_cell(root, args.workload)
    chips = int(loaded["cell"]["chips"])
    device = harness.tpu_or_exit(chips)
    harness.place_compile_cache(root)
    tag = f"[{device['platform']} {device['kind']} x{device['count']}]"
    cfg, traffic = loaded["cfg"], loaded["traffic"]
    fam = harness.load_module(root, "families", cfg["family"])
    train = harness.load_module(root, "runners", traffic["kind"])
    job = train.Job(cfg, traffic, fam, chips)

    want = {}
    for seed in args.seeds:
        want[seed] = job.reference(seed)
        print(f"{tag} reference seed {seed}: losses {want[seed]['losses']}",
              flush=True)

    readings = {"sound": {}, "control": {}}
    for variant, level, seeds in (
            ("sound", cfg["precision"]["opt_level"], args.seeds),
            ("control", CONTROL_OPT_LEVEL, args.control_seeds)):
        if not seeds:
            continue
        driver, make_carry = job.program(level)
        for seed in seeds:
            carry, got = job.first_window(driver, make_carry(seed), seed)
            del carry
            compared = train.compare(got, want[seed], fam)
            row = {k: v for k, (v, _) in compared.items()}
            for name, value in row.items():
                readings[variant].setdefault(name, []).append(value)
            print(f"{tag} READING " + json.dumps(
                {"variant": variant, "opt_level": level, "seed": seed, **row,
                 "losses": got["losses"], "reference_losses":
                 want[seed]["losses"], "first_grad_norm":
                 got["first_grad_norm"], "reference_first_grad_norm":
                 want[seed]["first_grad_norm"],
                 "what": {k: w for k, (_, w) in compared.items()}}),
                flush=True)
        del driver, make_carry

    limits, fails, text = limits_from(readings["sound"], readings["control"],
                                      earlier)
    for line in text:
        print(f"{tag} {line}", flush=True)
    if args.write_traffic:
        origin = (f"{device['kind']}, PR 23, benchmark/tools/control.py: sound "
                  f"seeds {args.seeds}, control (AMP {CONTROL_OPT_LEVEL}, no "
                  f"float32 masters) seeds {args.control_seeds}; each limit 3 x "
                  f"the sound runs' largest, rounded up. " + "; ".join(text))
        with open(args.write_traffic, "w") as f:
            json.dump({**traffic, "limits": limits, "limits_from": origin},
                      f, indent=2)
            f.write("\n")
    if args.control_seeds and not fails:
        print(f"{tag} the control passes every limit: no limit holds",
              flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(main())
