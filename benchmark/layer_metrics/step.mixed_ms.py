"""Device time of the fusions whose instructions fall in two or more
parts of the split: the scopes a fusion's members carried
(`analysis/hlo_scopes.owners`' `members`), each put in a part by the
model class's `scopes.json` as `program.part_of` puts an op's scope;
what no part claims counts as the part it is (`step.unscoped_ms`: a
norm's pass inside a projection's fusion is such a mix).  The whole
fusion is counted in ONE part, its owner's: this is how much of the
split the compiler fused across the parts' borders, the error bar of
`step.mlp_ms` and its neighbours from one program to the next.  Device
0, ops as `program.split_ms` takes them, ms per optimizer step, a
TOTAL.  A model class without a scopes file reports nothing."""

from benchmark import owners, program

NAME, UNIT, SOURCE = "step.mixed_ms", "ms", "device_trace"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def read(trace, events, ledgers, cell):
    rules = program.part_rules(cell["config"]["model_class"])
    if rules is None:
        return None
    return owners.ms_per_step(trace, lambda e: len(
        {program.part_of(m, rules) for m in e["members"]}) > 1)
