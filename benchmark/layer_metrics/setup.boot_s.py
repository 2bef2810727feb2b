"""`proc:boot`'s duration: the kernel's start of the process ->
`Trainer.__init__` entered — interpreter, imports, the backend's start
(the harness's `require_tpu` makes it here, so it lies in this part),
the caller's own preparation."""

from benchmark import setup_chain

NAME, UNIT, SOURCE = "setup.boot_s", "s", "program_span"
LAYER, MOVES = "trainer loop", "setup_s"


def read(trace, events, ledgers, cell):
    return setup_chain.boot_s(events)
