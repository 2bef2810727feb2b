"""SIGKILL -> the CLI's next "launched worker" line, on the harness's
clock (polled every 20 ms): failure-save, report, node check, relaunch."""

from benchmark import readers

NAME, UNIT, SOURCE = "agent.relaunch_s", "s", "host_clock"
LAYER, MOVES = "launcher / agent", "resume_s"


def read(trace, events, ledgers, cell):
    kill = readers.first(events, "kill")
    seen = readers.first(events, "relaunch_seen")
    if kill is None or seen is None:
        return None
    return seen["t"] - kill["t"]
