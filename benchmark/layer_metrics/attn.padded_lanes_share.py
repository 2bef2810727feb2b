"""The share of the lanes a score entry's two products run in the
attention kernels that the model's widths do not ask for:
`1 - attn_lanes_model / attn_lanes_run`, as the step program counted
them (`models/latent_attention.py`: QK^T over q's and k's width and PV
over v's, each as `ops/flash_attention.py` blocks it, beside the 192 +
128 the model asks), averaged over the logging boundaries inside the
measured stretch.  0 when the kernels run 192 and 128 as they are; v
padded to q's 192 would read 16.7%, both padded to 256 lanes 37.5%.  It
is the PLAN's share, a static number, and bounds `kernel.attn_roofline`
from the plan's side.  Read from the same `trainer:step_metrics` span
events as `moe.load_max_over_mean`; a program without the counters, or
a model without a latent attention layer, reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "attn.padded_lanes_share", "%", "program_counter"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    shares = [1.0 - s["attrs"]["attn_lanes_model"] / s["attrs"]["attn_lanes_run"]
              for s in program.setup_spans()
              if s["name"] == "trainer:step_metrics"
              and bounds[0] <= s["t_mono"] <= bounds[1]
              and s["attrs"].get("attn_lanes_run")
              and "attn_lanes_model" in s["attrs"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
