"""Device time of the ops of the step that only MOVE data, whoever owns
them: `copy`, `transpose`, materialised `reshape`, `slice`,
`concatenate`, `pad`, `dynamic-(update-)slice`, the halves of the
compiler's async copies and slices, and fusions of nothing else
(`analysis/hlo_scopes.owners`' kind `move`, which is
`hlo_scopes.relayouts`' rule).  An overlay of the `step.*_ms` parts, not
one of them: each such op is also in the part of its owner.  The budget
of a layout change.  Device 0, the ops inside train-step modules as
`program.split_ms` takes them (`dwt_fa_*` and collectives left out), ms
per optimizer step, a TOTAL."""

from benchmark import owners

NAME, UNIT, SOURCE = "step.move_ms", "ms", "device_trace"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return owners.ms_per_step(trace, lambda e: e["kind"] == "move")
