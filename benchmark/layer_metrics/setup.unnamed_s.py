"""|(`window_open` - `proc_start`: `setup_s`' own interval of this run) -
(`setup.boot_s` + `setup.build_s` + `setup.caller_s` +
`setup.warmup_s`)|: what of set-up still lies under no span."""

from benchmark import setup_chain

NAME, UNIT, SOURCE = "setup.unnamed_s", "s", "program_span"
LAYER, MOVES = "trainer loop", "setup_s"


def read(trace, events, ledgers, cell):
    return setup_chain.unnamed_s(events)
