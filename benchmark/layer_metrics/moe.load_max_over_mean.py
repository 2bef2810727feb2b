"""Expert load imbalance: `max_i(tokens_i) / mean_i(tokens_i)` over the
experts of the worst layer, as the step program counted it
(`models/moe.py`: the grouped path's `group_sizes`), averaged over the
logging boundaries inside the measured stretch.  1.0 is a perfectly even
routing; under expert parallelism the fullest expert's chip bounds the
layer.  The Trainer's metrics pump reads what a step counted where it
reads the loss back (no sync of its own) and keeps it as one
`trainer:step_metrics` span event per boundary, the counters in its
`attrs`; a program without the event, or a model whose step counts no
expert load, reports nothing.  The event's other counter, the
assignments that reached no expert (0 by construction in the grouped
path), goes to stderr."""

import sys

from benchmark import program

NAME, UNIT, SOURCE = "moe.load_max_over_mean", "ratio", "program_counter"
LAYER, MOVES = "expert layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    inside = [s["attrs"] for s in program.setup_spans()
              if s["name"] == "trainer:step_metrics"
              and bounds[0] <= s["t_mono"] <= bounds[1]
              and "moe_load_max_over_mean" in s["attrs"]]
    if not inside:
        return None
    print(f"benchmark: {len(inside)} trainer:step_metrics events in the "
          f"measured stretch, {sum(a['moe_dropped'] for a in inside):.0f} "
          f"dropped assignments", file=sys.stderr)
    return sum(a["moe_load_max_over_mean"] for a in inside) / len(inside)
