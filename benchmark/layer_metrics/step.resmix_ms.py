"""Device time of the residual mixing: every op owned by the `hc/*`
scopes of `models/hyper_connection.py` — `hc/coeff` (the mixing norm's
statistic, the one (lanes x hidden) x (lanes^2 + 2 lanes) product, the
gains, biases and sigmoids), `hc/sinkhorn` (exp and the rounds of two
normalisations), `hc/pre` (the sublayer's input as a mix of the lanes)
and `hc/post_res` (the lanes rewritten from the doubly-stochastic mix
and the branch's output) — forward, recomputed forward and backward, in
every sublayer of every block.  An OVERLAY over `step.unscoped_ms`
(the class's scopes file says so), not a part beside it.  The class's
scopes file names the scopes under `resmix_parts`; `resmix_parts_ms`
here runs `program.split_ms` with those rules as `step.attn_latent_ms`
does with `attn_parts` — over a trace WITHOUT its `while` and
`conditional` spans: Sinkhorn's rounds are one `lax.scan` a call, a
`while` in the step, and the device trace holds such a span beside the
ops that ran inside it (`xtrace.ops_in_steps` keeps both: ROADMAP
S8(k)), so a loop left in would be counted twice.  Device 0, ms per
optimizer step, a TOTAL.  A model class whose scopes file has no
`resmix_parts`, or a program whose step holds no such scope, reports
nothing."""

import json
import os

from benchmark import cells, program

NAME, UNIT, SOURCE = "step.resmix_ms", "ms", "device_trace"
LAYER, MOVES = "residual path", "tokens_per_s"


_HOLDS_OTHER_OPS = ("while", "conditional")


def _without_loop_spans(trace: dict) -> dict:
    """The trace with every device's ops that only hold other ops left
    out: their bodies' ops stay."""
    return {**trace, "devices": {
        dev: {**rec, "ops": [op for op in rec["ops"]
                             if not op[0].startswith(_HOLDS_OTHER_OPS)]}
        for dev, rec in trace["devices"].items()}}


def resmix_parts_ms(trace, cell):
    """{"resmix_sinkhorn": ms, "resmix": ms (the rest under hc)}, or
    None where there is no trace, no such key, or no scope table."""
    path = os.path.join(cells.HERE, "models",
                        cell["config"]["model_class"] + ".scopes.json")
    if not trace or not os.path.isfile(path):
        return None
    with open(path) as f:
        rules = json.load(f).get("resmix_parts")
    table = program.scope_table()
    if rules is None or table is None:
        return None
    return program.split_ms(_without_loop_spans(trace), table, rules)


def read(trace, events, ledgers, cell):
    split = resmix_parts_ms(trace, cell)
    if not split:
        return None
    return (split.get("resmix", 0.0)
            + split.get("resmix_sinkhorn", 0.0)) or None
