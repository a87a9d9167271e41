"""Layer: model.  Device self time under the gated delta net's four scopes
(``gdn_proj``, ``gdn_conv``, ``gdn_scan``, ``gdn_out``;
``models/qwen3_next.py::GatedDeltaNet``), forward, recomputed forward and
backward, the rule's kernels included, over the optimizer steps of the
trace, in milliseconds.  None for a program without such scopes."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(
        run, r"gdn_(proj|conv|scan|out)") or None
