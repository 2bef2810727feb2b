"""The most the fullest chip held while the window's steps ran, in GiB:
the largest CURRENT `bytes_in_use + bytes_reserved` over the
`trainer:memory` span events inside the measured stretch — one a logging
boundary, written by the metrics pump right after the loss readback (the
device has finished that step and later steps are queued).  The same sum
`device.peak_hbm_gib` takes of the two process-wide peaks, read while
the step runs and after the harness's reference check is gone.  A
program without the event (a parent of PR 64) reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "device.hbm_window_gib", "GiB", "program_counter"
LAYER, MOVES = "device", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    held = [s["attrs"]["bytes_in_use"] + s["attrs"]["bytes_reserved"]
            for s in program.setup_spans()
            if s["name"] == "trainer:memory"
            and bounds[0] <= s["t_mono"] <= bounds[1]]
    return max(held) / 2 ** 30 if held else None
