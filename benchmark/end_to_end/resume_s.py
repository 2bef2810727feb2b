"""From the SIGKILL the harness sends to the worker's process group to
the instant generation 2's first optimizer step is complete on the
device, on time.monotonic(), which both processes share.  PARKED
(parked.json): it spread 6.5% and 10.2% in the driver's check, over
half the largest bound the contract admits."""

from benchmark import readers

NAME, UNIT, SOURCE = "resume_s", "s", "host_clock"


def read(trace, events, ledgers, cell):
    kill = readers.first(events, "kill")
    done = readers.resumed(events)
    if kill is None or done is None:
        return None
    return done["t"] - kill["t"]
