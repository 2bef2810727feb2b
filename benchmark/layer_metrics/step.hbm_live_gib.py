"""What the step program the loop dispatched was compiled to hold at
once, per device (the fullest of a sharded run is every device: one SPMD
program), in GiB: `live_bytes` = argument + temp + output - alias of
`telemetry.perf.step_memory()` — `Compiled.memory_analysis()` of the
executable the program finds again in JAX's caches, asked here, after
the run.  A property of one executable: the same in every run of one
tree, and it moves with every change to the step's memory.  Where the
process kept several step programs (a fused-K cutover), the one kept
last: the one running in the window.  A program without `step_memory`
(a parent of PR 64) reports nothing.

`budget_gib(key)` serves `step.hbm_temp_gib` and `step.hbm_args_gib`,
which read other keys of the same budget."""

from benchmark import program

NAME, UNIT, SOURCE = "step.hbm_live_gib", "GiB", "program_counter"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def budget_gib(key: str):
    """`key` of the budget of the step program kept last, in GiB."""
    kept = getattr(program._module("telemetry.perf"), "step_memory", None)
    budgets = kept() if kept is not None else {}
    if not budgets:
        return None
    return list(budgets.values())[-1][key] / 2 ** 30


def read(trace, events, ledgers, cell):
    return budget_gib("live_bytes")
