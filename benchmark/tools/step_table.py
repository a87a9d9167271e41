#!/usr/bin/env python3
"""A traced window as one table, for people: scope × phase × kind of
operation, from the program's own names (``benchmark/step_table.py``).

    python3 benchmark/tools/step_table.py [profile.xplane.pb] [--top 25]
                                          [--keep-index] [--json out.json]

Reads the newest profile under ``.bench_trace`` (what a ``--trace 1`` run of
a cell leaves) or the file given; needs no chip.  In ms a step (the steps the
trace's ``apex/train/dispatch`` spans carry) it prints:

1. the five phases — forward, recompute, backward, optimizer, unscoped — and
   each one's share of the busy step;
2. the dearest rows ``(scope path, phase, kind)``;
3. for every scope path one line ``forward | recompute | backward`` (an event
   counts under its path and every prefix of it): column two is what keeping
   that scope's output as a residual would save;
4. the ``unscoped`` events by kind and result shape;
5. the ``mixed`` time: events whose joined ``op_name``s disagree on the phase;
6. the bare events (innermost scope ``layer_<i>``) by kind and result shape;
7. who holds the time XLA names itself (kinds with ``fusion`` in them,
   ``copy*``, ``convolution*``) by ``(scope path, phase)``.

``--json`` writes the same, every list whole, to a file.
"""
import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
#: kinds of operation that bear the compiler's word, not a kernel's name
XLA_KIND = re.compile(r"fusion|^copy|^convolution")
RUN_PHASES = ("forward", "recompute", "backward")


def report(trace, keep_index: bool = False) -> dict:
    """Everything the tool prints, in ms a step, lists sorted dearest first."""
    from benchmark import program_trace, step_table

    tab = step_table.table(trace, keep_index)
    steps = program_trace.reduce(trace)["steps"]
    ms = lambda ns: ns * 1e-6 / (steps or 1)
    busy = tab["op_ns"] or 1
    by_scope, xla = {}, {}
    for (path, phase, kind), (ns, _, _) in tab["rows"].items():
        parts = path.split("/") if path else []
        if phase in RUN_PHASES:
            for n in range(1, len(parts) + 1):
                line = by_scope.setdefault("/".join(parts[:n]),
                                           dict.fromkeys(RUN_PHASES, 0))
                line[phase] += ns
        if XLA_KIND.search(kind):
            xla[(path, phase)] = xla.get((path, phase), 0) + ns
    dearest = lambda d: sorted(d.items(), key=lambda kv: -kv[1][0])
    return {
        "steps": steps, "busy_ms": ms(tab["op_ns"]),
        "phases": {p: [ms(ns), 100.0 * ns / busy]
                   for p, ns in tab["phase_ns"].items()},
        "rows": [[*key, ms(ns), calls] for key, (ns, calls, _)
                 in dearest(tab["rows"])],
        "scopes": [[path, *(ms(line[p]) for p in RUN_PHASES)]
                   for path, line in sorted(
                       by_scope.items(), key=lambda kv: -sum(kv[1].values()))],
        "unscoped": [[kind, ms(ns), calls] for (_, phase, kind), (ns, calls, _)
                     in dearest(tab["rows"]) if phase == "unscoped"],
        "mixed_ms": ms(tab["mixed_ns"]),
        "bare_share_pct": (100.0 * tab["bare_ns"] / tab["layer_ns"]
                           if tab["layer_ns"] else None),
        "bare": [[*key, ms(ns), calls] for key, (ns, calls)
                 in dearest(tab["bare"])],
        "xla_named": [[path, phase, ms(ns)] for (path, phase), ns
                      in sorted(xla.items(), key=lambda kv: -kv[1])],
    }


def show(rep: dict, top: int) -> None:
    print(f"steps {rep['steps']}, busy {rep['busy_ms']:.2f} ms a step"
          + ("" if rep["steps"] else " (no dispatch span: ms over the trace)"))
    print("\n1. phases: ms a step, share of busy")
    for phase, (v, share) in rep["phases"].items():
        print(f"  {phase:10s} {v:9.2f} {share:6.2f}%")
    print("\n2. dearest rows: ms a step, calls, scope path | phase | kind")
    for path, phase, kind, v, calls in rep["rows"][:top]:
        print(f"  {v:8.2f} {calls:6d}  {path or '-'} | {phase} | {kind}")
    print("\n3. scope paths: forward | recompute | backward")
    for path, f, r, b in rep["scopes"][:top]:
        print(f"  {f:8.2f} | {r:8.2f} | {b:8.2f}  {path}")
    print("\n4. unscoped events: ms a step, calls, kind and result shape")
    for kind, v, calls in rep["unscoped"][:top]:
        print(f"  {v:8.2f} {calls:6d}  {kind}")
    print(f"\n5. mixed: {rep['mixed_ms']:.3f} ms a step")
    share = rep["bare_share_pct"]
    print("\n6. bare events"
          + (f" ({share:.2f}% of the time under layer_<i>)"
             if share is not None else "")
          + ": ms a step, calls, scope path | phase | kind | result")
    for path, phase, kind, shape, v, calls in rep["bare"][:top]:
        print(f"  {v:8.2f} {calls:6d}  {path} | {phase} | {kind} | {shape}")
    print("\n7. holders of the time XLA names (fusion, copy, convolution): "
          "ms a step, scope path | phase")
    for path, phase, v in rep["xla_named"][:top]:
        print(f"  {v:8.2f}  {path or '-'} | {phase}")


def main(argv=None) -> int:
    sys.path.insert(0, ROOT)
    from benchmark import program_trace, step_table

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("profile", nargs="?", help="an .xplane.pb; default: the "
                    "newest under .bench_trace")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--keep-index", action="store_true",
                    help="layer_3, not layer_*")
    ap.add_argument("--json", help="write everything to this file")
    args = ap.parse_args(argv)
    path = args.profile or step_table.newest_profile()
    if not path:
        raise SystemExit(f"no profile under {program_trace.TRACE_DIR}")
    rep = report(program_trace.load(path), args.keep_index)
    show(rep, args.top)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rep, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
