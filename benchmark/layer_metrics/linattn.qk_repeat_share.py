"""The share of the q and k head-rows the gated delta rule's route reads
that the model does not have: `1 - delta_qk_rows_model /
delta_qk_rows_run`, as the step program counted them
(`models/gated_delta.py`: a mixer whose value heads outnumber its key
heads hands the recurrence one q and one k a STATE, so a key head's rows
are repeated to its value heads in HBM — 32 rows run for the 16 the
model has reads 50%; kernels that index a key head for its states would
read 0), averaged over the logging boundaries inside the measured
stretch.  It is the PLAN's share, a static number: what the repeat costs
in time lies inside `step.linattn_scan_ms`, and `kernel.delta_roofline`
counts the model's 16 key heads whatever this reads.  Read from the same
`trainer:step_metrics` span events as `linattn.padded_lanes_share`; a
program without the counters, or a model whose mixers have as many key
heads as value heads, reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "linattn.qk_repeat_share", "%", "program_counter"
LAYER, MOVES = "linear-attention layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    shares = [1.0 - s["attrs"]["delta_qk_rows_model"]
              / s["attrs"]["delta_qk_rows_run"]
              for s in program.setup_spans()
              if s["name"] == "trainer:step_metrics"
              and bounds[0] <= s["t_mono"] <= bounds[1]
              and s["attrs"].get("delta_qk_rows_run")
              and "delta_qk_rows_model" in s["attrs"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
