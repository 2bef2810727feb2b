"""`trainer:build`'s end -> `trainer:train`'s start (the last of each
before `open`): the stretch in which the program is not running.  In
a steady cell the harness draws the seeded state twice, executes the
step once for its check (the step's trace, lowering and compile or
load fall here) and runs the plain reference."""

from benchmark import setup_chain

NAME, UNIT, SOURCE = "setup.caller_s", "s", "program_span"
LAYER, MOVES = "trainer loop", "setup_s"


def read(trace, events, ledgers, cell):
    return setup_chain.caller_s(events)
