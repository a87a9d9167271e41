"""Layer: model.  Of the device self time under the blocks' scopes
(``layer_<i>``), the share of the events whose INNERMOST scope is the block
itself — residual adds, gates, casts and splits of a block's own ``__call__``
that no scope of the program names (``benchmark/step_table.py``) — in percent
(train cells, traced lines).  A fusion bears its root's ``op_name``: what the
compiler merged into a named neighbour's fusion counts there."""
from benchmark import step_table


def read(run):
    got = step_table.of(run)
    if not got or not got[0]["layer_ns"]:
        return None
    return 100.0 * got[0]["bare_ns"] / got[0]["layer_ns"]
