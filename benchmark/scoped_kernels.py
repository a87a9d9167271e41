"""Device time of Pallas kernels BY THE SCOPE that called them: what
``program_trace.reduce`` keeps apart (time by kernel, time by scope) taken
together, for the metrics that ask for one kernel under one scope (the flash
kernels of the window layers against those of the full layers).  Same file,
same events, same self times as ``program_trace``."""
from __future__ import annotations

import functools
import glob
import os
from typing import Dict, Optional, Tuple

from benchmark import program_trace
from benchmark.trace_reduce import (MOSAIC_RE, OPS_LINE, device_planes,
                                    self_times, short_name)


def reduce(trace: Dict) -> Dict[Tuple[str, str], int]:
    """``{(kernel instruction, scope): self ns}`` over the first chip's
    Mosaic events; an event counts under every scope along its path."""
    planes = device_planes(trace)
    events = sorted((ev for line in planes[0]["lines"]
                     if line["name"] == OPS_LINE for ev in line["events"]),
                    key=lambda ev: (ev[1], -ev[2])) if planes else []
    out: Dict[Tuple[str, str], int] = {}
    for ev, (name, self_ns) in zip(events,
                                   self_times([ev[:3] for ev in events])):
        if not MOSAIC_RE.search(name):
            continue
        stats = ev[3] if len(ev) > 3 else {}
        for scope in program_trace.scopes_of(stats.get("op_name", "")):
            key = (short_name(name), scope)
            out[key] = out.get(key, 0) + self_ns
    return out


@functools.lru_cache(maxsize=1)
def newest(trace_dir: str = program_trace.TRACE_DIR) -> Optional[Dict]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return reduce(program_trace.load(paths[-1])) if paths else None


def kernel_ms_per_step_under(run: Dict, family: str, scope: str
                             ) -> Optional[float]:
    """Self time a step of the Mosaic kernels whose instruction bears
    ``family``, called from under ``scope``; None where the trace holds no
    such call (a program without that scope)."""
    t = program_trace.of(run)
    if not t or not t["steps"]:
        return None
    by = newest()
    ns = sum(v for (kernel, under), v in (by or {}).items()
             if family in kernel and under == scope)
    return ns * 1e-6 / t["steps"] if ns else None
