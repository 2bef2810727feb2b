"""Device time of the ops of the step that `analysis/hlo_scopes.owners`
can give to nobody (`via: none`): no name of their own, none on what
they hold, no reader and no producer with one.  The alarm of the split
(an executable that is not the one that ran puts everything under
`step.unscoped_ms` already): it should read about 0 (the copy of the
step counter is such an op) and stay there; time here is time
`step.unscoped_ms` holds for no reason a reader could name.  Device 0,
ops as `program.split_ms` takes them, ms per optimizer step, a TOTAL."""

from benchmark import owners

NAME, UNIT, SOURCE = "step.unowned_ms", "ms", "device_trace"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return owners.ms_per_step(trace, lambda e: e["via"] == "none")
